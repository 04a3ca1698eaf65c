#!/usr/bin/env python
"""Repo static-analysis CLI: invariant linter + parallelism census.

Usage::

    python tools/lint.py [paths...]          # lint (default: automodel_tpu tools __graft_entry__.py)
    python tools/lint.py --format json       # machine-readable findings
    python tools/lint.py --select L004,L006  # subset of rules
    python tools/lint.py --check-golden      # audit the dryrun legs vs the
                                             # golden censuses (needs jax;
                                             # builds an 8-device CPU mesh)
    python tools/lint.py --update-golden     # regenerate the golden census
                                             # files under tests/data/

Exit status: 0 when clean, 1 on any unsuppressed finding / census mismatch.
The default lint run imports NO heavy deps (pure-AST), so it is safe as a
pre-commit hook; the census modes bootstrap a virtual 8-device CPU mesh the
same way tests/conftest.py does.  Rules, suppression syntax and the golden
workflow are documented in docs/guides/static_analysis.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

_DEFAULT_PATHS = ("automodel_tpu", "tools", "__graft_entry__.py")


def _bootstrap_cpu_mesh(n_devices: int = 8) -> None:
    """Force an n-device virtual CPU platform BEFORE any jax backend
    initializes (mirrors tests/conftest.py: the goldens are CPU censuses
    whatever machine this runs on)."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ["JAX_PLATFORMS"] = "cpu"


def _run_lint(args) -> int:
    from automodel_tpu.analysis.lint import lint_paths

    paths = args.paths or [os.path.join(_REPO_ROOT, p)
                           for p in _DEFAULT_PATHS]
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"error: no such path(s): {', '.join(missing)}",
              file=sys.stderr)
        return 2
    select = args.select.split(",") if args.select else None
    findings = lint_paths(paths, select=select, repo_root=_REPO_ROOT)
    if args.format == "json":
        print(json.dumps([f.to_json_dict() for f in findings], indent=2))
    else:
        for f in findings:
            print(f.format())
        print(f"{len(findings)} finding(s)" if findings else "lint: clean")
    return 1 if findings else 0


def _legs(args):
    from automodel_tpu.analysis import legs as legs_mod

    names = args.legs.split(",") if args.legs else legs_mod.LEG_NAMES
    for name in names:
        yield name, legs_mod.build_leg(name)


def _update_golden(args) -> int:
    from automodel_tpu.analysis import legs as legs_mod
    from automodel_tpu.analysis.jaxpr_audit import save_census

    os.makedirs(legs_mod.golden_dir(), exist_ok=True)
    for name, leg in _legs(args):
        census = leg.census()
        path = legs_mod.golden_path(name)
        save_census(census, path)
        print(f"wrote {os.path.relpath(path, _REPO_ROOT)}")
    return 0


def _check_golden(args) -> int:
    from automodel_tpu.analysis import legs as legs_mod
    from automodel_tpu.analysis.jaxpr_audit import (
        audit_param_shardings,
        load_census,
    )

    rc = 0
    for name, leg in _legs(args):
        path = legs_mod.golden_path(name)
        if not os.path.isfile(path):
            print(f"{name}: MISSING golden {path} "
                  "(run tools/lint.py --update-golden)")
            rc = 1
            continue
        diff = leg.census().diff(load_census(path))
        audit = audit_param_shardings(
            leg.abstract_args[0], leg.plan,
            min_bytes=legs_mod.TINY_AUDIT_MIN_BYTES)
        if not diff and not audit:
            print(f"{name}: census matches golden; sharding audit clean")
            continue
        rc = 1
        for line in diff:
            print(f"{name}: {line}")
        for f in audit:
            print(f"{name}: sharding audit: {f.format()}")
    return rc


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="tools/lint.py",
        description="automodel_tpu invariant linter + parallelism census")
    p.add_argument("paths", nargs="*",
                   help="files/dirs to lint (default: %s)"
                   % " ".join(_DEFAULT_PATHS))
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--select", help="comma-separated rule IDs (e.g. L004,L006)")
    p.add_argument("--legs", help="comma-separated census leg names "
                   "(default: all)")
    p.add_argument("--check-golden", action="store_true",
                   help="audit the dryrun flagship legs against the golden "
                   "censuses + run the sharding audit")
    p.add_argument("--update-golden", action="store_true",
                   help="regenerate the golden census files")
    args = p.parse_args(argv)

    if args.update_golden or args.check_golden:
        _bootstrap_cpu_mesh()
        return (_update_golden if args.update_golden else _check_golden)(args)
    return _run_lint(args)


if __name__ == "__main__":
    sys.exit(main())
