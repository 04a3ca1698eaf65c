"""BENCHMARK.json: loading it, finding a cell's files by name, and the
self-check that runs at the start of every ``benchmark/run.py`` and under
``pytest benchmark/tests``.

The self-check holds the manifest to the rules a driver would refuse it
for, above all the one PR 23 broke: a per-layer metric moves ONE end-to-end
metric, and every cell that reports the per-layer metric reports that one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


class ManifestError(ValueError):
    pass


def load(path: str = MANIFEST) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def read_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_by_name(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (a name may hold dots).
    This is how a cell's runner, its traffic kind, its configuration's
    family and its per-layer metric readers are found: by the names in the
    manifest and in the cell's files, never through a list in code."""
    key = "benchmark_%s_%s" % (kind, re.sub(r"[^A-Za-z0-9_]", "_", name))
    if key not in sys.modules:
        path = os.path.join(HERE, kind, name + ".py")
        if not os.path.isfile(path):
            raise ManifestError(f"benchmark/{kind}/{name}.py does not exist")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]


def family(config: Dict[str, Any]):
    """The configuration's family, ``reference/<model_type>.py``: its
    weights' shapes, the program's parameter tree, its required work and
    its plain reference."""
    return load_by_name("reference", config["model_type"])


def traffic_kind(traffic: Dict[str, Any]):
    """The code of a traffic mix's kind, ``traffic/<kind>.py``."""
    return load_by_name("traffic", traffic["kind"])


def cell_of(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json; it has "
                        f"{[w['name'] for w in manifest['workloads']]}")


def config_of(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in manifest["configs"]:
        if c["name"] == name:
            with open(os.path.join(ROOT, c["file"])) as f:
                return json.load(f)
    raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")


def metrics_of(manifest: Dict[str, Any], kind: str, cell: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports: a
    metric without a ``workloads`` list is reported by every cell (for a
    per-layer one: every cell that reports what it moves)."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    out = []
    for m in manifest[kind]:
        cells = m.get("workloads")
        if cells is None and kind == "per_layer":
            cells = e2e[m["moves"]].get("workloads")
        if cells is None or cell in cells:
            out.append(m)
    return out


def _line(text: Any, what: str, errors: List[str]) -> None:
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or "\n" in text or "\t" in text):
        errors.append(f"{what} must be 1 to 200 characters on one line")


def _cell_files(w: Dict[str, Any]) -> List[str]:
    """Every file that a cell names, through its own files too, exists."""
    missing = []

    def need(*parts):
        ok = os.path.isfile(os.path.join(HERE, *parts))
        if not ok:
            missing.append(f"{'/'.join(parts)} of {w['name']} does not exist")
        return ok

    if need("workloads", w["name"] + ".json"):
        need("runners", read_json("workloads", w["name"] + ".json")
             .get("runner", "") + ".py")
    if need("traffic", w["traffic"] + ".json"):
        need("traffic", read_json("traffic", w["traffic"] + ".json")
             .get("kind", "") + ".py")
    return missing


def self_check(manifest: Dict[str, Any], root: str = ROOT) -> List[str]:
    """Every breach found, as text; empty when the manifest is sound."""
    errors: List[str] = []
    if set(manifest) != TOP_KEYS:
        errors.append(f"top-level keys {sorted(manifest)} != "
                      f"{sorted(TOP_KEYS)}")
        return errors
    paths = manifest["paths"]
    under = lambda p: any(p == d or p.startswith(d.rstrip("/") + "/")
                          for d in paths)
    for word in manifest["command"]:
        _line(word, f"command word {word!r}", errors)
        if word.startswith("/") or ".." in word.split("/"):
            errors.append(f"command word {word!r} leaves the repo")
    if not isinstance(manifest["run_seconds"], int) \
            or not 1 <= manifest["run_seconds"] <= 51:
        errors.append("run_seconds must be a whole number from 1 to 51")

    def names(items, what):
        seen = set()
        for it in items:
            n = it.get("name", "")
            if not NAME.match(n):
                errors.append(f"{what} name {n!r} is not a name")
            if n in seen:
                errors.append(f"{what} name {n!r} appears twice")
            seen.add(n)
        return seen

    configs = names(manifest["configs"], "configuration")
    cells = names(manifest["workloads"], "workload")
    names(manifest["end_to_end"] + manifest["per_layer"], "metric")

    files = set()
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errors.append(f"configuration {c.get('name')} has keys "
                          f"{sorted(c)}")
            continue
        _line(c["source"], f"source of {c['name']}", errors)
        _line(c["why"], f"why of {c['name']}", errors)
        if not under(c["file"]) or c["file"] in files:
            errors.append(f"file of {c['name']} is outside paths or shared")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(root, c["file"])):
            errors.append(f"file {c['file']} of {c['name']} does not exist")
        else:
            with open(os.path.join(root, c["file"])) as f:
                kind = json.load(f).get("model_type", "")
            if not os.path.isfile(os.path.join(HERE, "reference",
                                               kind + ".py")):
                errors.append(f"reference/{kind}.py of {c['name']} does "
                              "not exist")
        for key in c["reduced"]:
            if not NAME.match(key):
                errors.append(f"reduced key {key!r} is not a name")
    used, pairs = set(), set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errors.append(f"workload {w.get('name')} has keys {sorted(w)}")
            continue
        _line(w["why"], f"why of {w['name']}", errors)
        if w["config"] not in configs:
            errors.append(f"workload {w['name']} names no configuration")
        if not NAME.match(w["traffic"]):
            errors.append(f"traffic {w['traffic']!r} is not a name")
        if w["chips"] not in (1, 4):
            errors.append(f"workload {w['name']} asks for {w['chips']} chips")
        if (w["config"], w["traffic"]) in pairs:
            errors.append(f"pair of {w['name']} appears twice")
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        errors.extend(_cell_files(w))
    for c in configs - used:
        errors.append(f"configuration {c} has no cell")
    four = sum(1 for w in manifest["workloads"] if w.get("chips") == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        errors.append(f"{four} of {len(manifest['workloads'])} cells ask for "
                      "4 chips; at most 25% (rounded down, at least one) may")

    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        errors.append("end_to_end lacks setup_s")
    for m in manifest["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"} or not {
                              "name", "unit", "better", "bound",
                              "source"} <= set(m):
            errors.append(f"end_to_end {m.get('name')} has keys {sorted(m)}")
            continue
        if m["source"] not in ("host_clock", "device_trace"):
            errors.append(f"end_to_end {m['name']} has source {m['source']}")
        if not 0 < m["bound"] <= 0.1:
            errors.append(f"bound of {m['name']} is outside (0, 0.1]")
    for m in manifest["per_layer"]:
        if set(m) != {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}:
            errors.append(f"per_layer {m.get('name')} needs exactly name, "
                          f"unit, better, source, layer, moves and an "
                          f"explicit workloads list; has {sorted(m)}")
            continue
        _line(m["layer"], f"layer of {m['name']}", errors)
        if m["source"] not in SOURCES:
            errors.append(f"per_layer {m['name']} has source {m['source']}")
        if m["moves"] not in e2e:
            errors.append(f"per_layer {m['name']} moves {m['moves']!r}, "
                          "which is no end-to-end metric")
            continue
        if not os.path.isfile(os.path.join(HERE, "metrics",
                                           m["name"] + ".py")):
            errors.append(f"metrics/{m['name']}.py does not exist")
        moved = e2e[m["moves"]].get("workloads")
        for cell in m["workloads"]:
            if cell not in cells:
                errors.append(f"per_layer {m['name']} lists {cell}, which "
                              "is no workload")
            elif moved is not None and cell not in moved:
                errors.append(
                    f"per_layer metric {m['name']} is reported on workload "
                    f"{cell}, where {m['moves']}, which it should move, "
                    "is not")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(str(m.get("unit", ""))):
            errors.append(f"unit {m.get('unit')!r} of {m.get('name')}")
        if m.get("better") not in ("lower", "higher"):
            errors.append(f"better of {m.get('name')}")
        for cell in m.get("workloads") or ():
            if cell not in cells:
                errors.append(f"{m.get('name')} lists unknown cell {cell}")
    for w in manifest["workloads"]:
        have = [m["name"] for m in metrics_of(manifest, "end_to_end",
                                              w["name"])]
        if "setup_s" not in have or len(have) < 2:
            errors.append(f"cell {w['name']} reports {have}: it needs "
                          "setup_s and one more end-to-end metric")
        if not metrics_of(manifest, "per_layer", w["name"]):
            errors.append(f"cell {w['name']} reports no per-layer metric")
    return errors
