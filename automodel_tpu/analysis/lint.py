"""Repo invariant linter: AST-based, zero third-party deps.

Every rule encodes an invariant that a past PR was bitten by (or that the
next frontier — pp, multi-slice, the kernel library — will be bitten by if
it drifts silently):

* **L002** — enum-like config domains (module-level ``FOO_LAYOUTS``-style
  constants of string literals) not registered in
  ``config/loader.py::_enum_fields``: an unregistered knob means a typo'd
  YAML value silently selects the default instead of failing at load.
* **L003** — Python-side nondeterminism or wall-clock (``time.time``,
  ``np.random.*``, stdlib ``random.*``) inside jit-decorated/traced
  functions: baked in at trace time, frozen into the compiled program, and
  different on every retrace — the classic irreproducible-run generator.
* **L004** — host-sync calls (``jax.device_get``, ``.item()``,
  ``block_until_ready``, the ``float(m["loss"])`` metric-fetch idiom) in
  hot-loop modules (``training/``, ``ops/``, ``generation/``,
  ``serving/``, and the ``_run_*`` bodies in ``recipes/``) outside an
  explicit suppression with
  a one-line justification.  PR-2/5 earned the async hot loop; one stray
  fetch re-serializes it.
* **L005** — ``fault_point("...")`` names must exist in
  ``utils/fault_injection.py::KNOWN_FAULT_POINTS`` and be exercised by at
  least one ``pytest.mark.fault`` test — an undrilled crash site is a
  crash-safety claim nobody ever tested.
* **L006** — raw Pallas construction (``pl.BlockSpec`` / ``pl.GridSpec`` /
  ``pltpu.PrefetchScalarGridSpec`` / ``pltpu.CompilerParams``) outside
  ``ops/kernel_lib/``: every kernel builds its blocks,
  grids and compiler params through the substrate
  (``ops/kernel_lib/tiling.py``) so block-size choices stay on the
  autotuner and the VMEM-limit defaults stay uniform — a kernel that
  drifts off the substrate silently loses both.
* **L007** — ``jax.lax.ppermute`` constructed outside ``ops/`` and
  ``training/train_step.py``: the golden collective censuses pin every
  permute's axis AND count, which is only a meaningful invariant while
  the census can name the home of each one (the ring's cp rotation in
  ``ops/ring_attention.py``, the pipeline's pp stage boundary in
  ``training/train_step.py``).  A permute constructed elsewhere would
  show up in a census diff with no owner to audit.

Suppression syntax (same line as the finding)::

    jax.device_get(x)  # lint: disable=L004 (once-per-epoch fetch)

The parenthesized justification is REQUIRED — a bare ``disable`` does not
suppress.  See ``docs/guides/static_analysis.md``.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

RULES: Dict[str, str] = {
    "L002": "enum-like config domain not registered in "
            "config/loader.py::_enum_fields",
    "L003": "nondeterminism/wall-clock inside a jit-traced function",
    "L004": "host-sync call in a hot-loop module",
    "L005": "fault point not registered or not covered by a "
            "fault-marked test",
    "L006": "raw Pallas BlockSpec/grid-spec/compiler-params construction "
            "outside ops/kernel_lib/",
    "L007": "jax.lax.ppermute constructed outside ops/ and "
            "training/train_step.py",
}

# L002: a module-level ALL_CAPS constant with one of these suffixes whose
# value is a tuple/list/set of >= 2 string literals declares an enum-like
# config domain (the convention CP_LAYOUTS / MOE_DISPATCHES established).
_ENUM_CONST_RE = re.compile(
    r"^_?[A-Z][A-Z0-9_]*(LAYOUTS|DISPATCHES|MODES|SCHEMES|STRATEGIES|"
    r"POLICIES|BACKENDS|FORMATS|KINDS|CHOICES|DTYPES|RECIPES|SCHEDULES|"
    r"ALGORITHMS|SOURCES)$")

# L003: banned call chains inside jit scope.
_WALLCLOCK_CALLS = {
    "time.time", "time.time_ns", "time.perf_counter",
    "time.perf_counter_ns", "time.monotonic", "time.monotonic_ns",
    "datetime.now", "datetime.datetime.now", "datetime.utcnow",
    "datetime.datetime.utcnow",
}
_NONDET_PREFIXES = ("np.random.", "numpy.random.", "random.")

# L004: explicit host-sync call chains; ``.item()`` / ``.block_until_ready()``
# method calls are matched by attribute name, and ``float(m["loss"])`` /
# ``int(dm["step"])`` by the metric-fetch idiom below.
_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready"}
_SYNC_METHODS = {"item", "block_until_ready"}
_METRIC_NAMES_RE = re.compile(r"^(m|dm|dmv|metrics|device_metrics)$")

# L006: Pallas grid/block/compiler-params construction belongs to the
# kernel substrate; flagged only on a pallas-looking base so an unrelated
# ``foo.CompilerParams`` does not false-positive.
_L006_GRID_NAMES = {"BlockSpec", "GridSpec", "PrefetchScalarGridSpec",
                    "CompilerParams"}
_PALLAS_BASES = {"pl", "pallas", "pltpu", "tpu", "pallas_tpu"}
_L006_EXEMPT_PREFIX = "automodel_tpu/ops/kernel_lib/"

# L007: every ppermute's home must be known to the census.  Allowed: any
# kernel/op under ops/ (the ring's cp rotation and friends) and the
# pipelined step's stage-boundary shift in training/train_step.py.
_L007_ALLOWED_PREFIX = "automodel_tpu/ops/"
_L007_ALLOWED_FILES = {"automodel_tpu/training/train_step.py"}

_SUPPRESS_RE = re.compile(
    r"#\s*lint:\s*disable=([A-Z0-9,\s]+?)\s*\(([^)]+)\)")

_HOT_DIRS = ("automodel_tpu/training/", "automodel_tpu/ops/",
             "automodel_tpu/generation/", "automodel_tpu/serving/")
_RECIPES_DIR = "automodel_tpu/recipes/"
_HOT_FUNC_RE = re.compile(r"^_run_")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One linter hit: rule ID + location + message."""

    rule: str
    path: str
    line: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"

    def to_json_dict(self) -> dict:
        return dataclasses.asdict(self)


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` attribute chain -> ``"a.b.c"``; None for non-chains."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def parse_suppressions(source: str) -> Dict[int, Set[str]]:
    """{1-based line: set of suppressed rule IDs} for lines carrying a
    ``# lint: disable=L00x (reason)`` comment WITH a non-empty reason."""
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(line)
        if m and m.group(2).strip():
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


# ---------------------------------------------------------------------------
# Repo context: the cross-file facts the rules check against
# ---------------------------------------------------------------------------
def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _registered_enum_consts(repo_root: str) -> Set[str]:
    """Constant names referenced inside ``config/loader.py::_enum_fields``
    (imports included) — the registration surface L002 checks against."""
    loader = os.path.join(repo_root, "automodel_tpu", "config", "loader.py")
    names: Set[str] = set()
    try:
        tree = ast.parse(open(loader).read())
    except (OSError, SyntaxError):
        return names
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == "_enum_fields":
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    names.add(sub.id)
                elif isinstance(sub, ast.ImportFrom):
                    names.update(a.asname or a.name for a in sub.names)
    return names


def _known_fault_points(repo_root: str) -> Set[str]:
    """String elements of ``utils/fault_injection.py::KNOWN_FAULT_POINTS``."""
    path = os.path.join(repo_root, "automodel_tpu", "utils",
                        "fault_injection.py")
    points: Set[str] = set()
    try:
        tree = ast.parse(open(path).read())
    except (OSError, SyntaxError):
        return points
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
            if "KNOWN_FAULT_POINTS" not in targets:
                continue
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Constant) and isinstance(
                        sub.value, str):
                    points.add(sub.value)
    return points


def _fault_marked_test_text(repo_root: str) -> str:
    """Concatenated source of every test module that uses the ``fault``
    marker — L005's coverage surface (a point name must appear in one)."""
    chunks: List[str] = []
    tests_dir = os.path.join(repo_root, "tests")
    for dirpath, dirnames, filenames in os.walk(tests_dir):
        dirnames[:] = [d for d in dirnames if not d.startswith((".", "__"))]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            try:
                text = open(os.path.join(dirpath, fn)).read()
            except OSError:
                continue
            if "mark.fault" in text:
                chunks.append(text)
    return "\n".join(chunks)


@dataclasses.dataclass
class _RepoContext:
    repo_root: str
    registered_enums: Set[str]
    known_fault_points: Set[str]
    fault_test_text: str

    @classmethod
    def build(cls, repo_root: Optional[str] = None) -> "_RepoContext":
        root = repo_root or _repo_root()
        return cls(
            repo_root=root,
            registered_enums=_registered_enum_consts(root),
            known_fault_points=_known_fault_points(root),
            fault_test_text=_fault_marked_test_text(root),
        )


# ---------------------------------------------------------------------------
# Per-file analysis
# ---------------------------------------------------------------------------
def _is_jit_decorator(dec: ast.AST) -> bool:
    """``@jax.jit``, ``@jit``, ``@partial(jax.jit, ...)``/
    ``@functools.partial(jax.jit, ...)``."""
    if isinstance(dec, ast.Call):
        head = _dotted(dec.func)
        if head in ("partial", "functools.partial") and dec.args:
            return _dotted(dec.args[0]) in ("jax.jit", "jit")
        return head in ("jax.jit", "jit")
    return _dotted(dec) in ("jax.jit", "jit")


def _jit_called_names(tree: ast.AST) -> Set[str]:
    """Function names passed to ``jax.jit(f, ...)`` anywhere in the module
    (the ``train_jit = jax.jit(train_step, ...)`` pattern)."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and _dotted(node.func) in ("jax.jit", "jit")
                and node.args and isinstance(node.args[0], ast.Name)):
            names.add(node.args[0].id)
    return names


def _enum_const_defs(tree: ast.Module) -> List[Tuple[str, int]]:
    """Module-level (name, line) of enum-like string-domain constants."""
    out: List[Tuple[str, int]] = []
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Name) and _ENUM_CONST_RE.match(tgt.id)):
            continue
        val = node.value
        if isinstance(val, ast.Call) and _dotted(val.func) in (
                "frozenset", "set", "tuple", "list") and val.args:
            val = val.args[0]
        if not isinstance(val, (ast.Tuple, ast.List, ast.Set)):
            continue
        elems = val.elts
        if len(elems) >= 2 and all(
                isinstance(e, ast.Constant) and isinstance(e.value, str)
                for e in elems):
            out.append((tgt.id, node.lineno))
    return out


class _FileLinter(ast.NodeVisitor):
    """One pass over one file; accumulates findings (pre-suppression)."""

    def __init__(self, rel_path: str, tree: ast.Module, ctx: _RepoContext):
        self.rel = rel_path
        self.tree = tree
        self.ctx = ctx
        self.findings: List[Finding] = []
        posix = rel_path.replace(os.sep, "/")
        self.is_kernel_lib = _L006_EXEMPT_PREFIX in posix
        self.is_ppermute_home = (_L007_ALLOWED_PREFIX in posix
                                 or any(posix.endswith(f)
                                        for f in _L007_ALLOWED_FILES))
        self.hot_file = any(d in posix for d in _HOT_DIRS)
        self.recipes_file = _RECIPES_DIR in posix
        self._jit_names = _jit_called_names(tree)
        self._jit_depth = 0      # inside a jit-traced function scope
        self._hot_depth = 0      # inside a recipes/ _run_* scope
        self._func_stack: List[str] = []

    def _emit(self, rule: str, node: ast.AST, msg: str) -> None:
        self.findings.append(Finding(rule, self.rel,
                                     getattr(node, "lineno", 0), msg))

    # -- L006 / L007 at imports ----------------------------------------------
    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if (not self.is_kernel_lib and node.module
                and "pallas" in node.module):
            for alias in node.names:
                if alias.name in _L006_GRID_NAMES:
                    self._emit(
                        "L006", node,
                        f"'from {node.module} import {alias.name}': build "
                        "Pallas block/grid specs through ops/kernel_lib/"
                        "tiling.py (the substrate's single construction "
                        "path)")
        if (not self.is_ppermute_home and node.module
                and node.module in ("jax.lax", "jax._src.lax.parallel")):
            for alias in node.names:
                if alias.name == "ppermute":
                    self._emit(
                        "L007", node,
                        f"'from {node.module} import ppermute': collective "
                        "permutes live in ops/ or training/train_step.py "
                        "so the golden censuses can name every permute's "
                        "home")
        self.generic_visit(node)

    # -- scope tracking (L003 / L004) ---------------------------------------
    def _visit_func(self, node) -> None:
        is_jit = (any(_is_jit_decorator(d) for d in node.decorator_list)
                  or node.name in self._jit_names)
        is_hot_entry = (self.recipes_file and not self._func_stack
                        and _HOT_FUNC_RE.match(node.name) is not None)
        self._func_stack.append(node.name)
        self._jit_depth += is_jit
        self._hot_depth += is_hot_entry
        self.generic_visit(node)
        self._hot_depth -= is_hot_entry
        self._jit_depth -= is_jit
        self._func_stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    # -- L003 / L004 / L005 at call sites -----------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        chain = _dotted(node.func)
        if self._jit_depth > 0 and chain:
            if chain in _WALLCLOCK_CALLS:
                self._emit("L003", node,
                           f"wall-clock call {chain!r} inside a jit-traced "
                           "function is frozen at trace time")
            elif chain.startswith(_NONDET_PREFIXES) and not chain.startswith(
                    "jax.random."):
                self._emit("L003", node,
                           f"host-side nondeterminism {chain!r} inside a "
                           "jit-traced function; thread an explicit "
                           "jax.random key instead")
        if self.hot_file or self._hot_depth > 0:
            self._check_sync_call(node, chain)
        if not self.is_kernel_lib and chain:
            tail = chain.split(".")[-1]
            base = chain.rsplit(".", 1)[0] if "." in chain else ""
            if (tail in _L006_GRID_NAMES
                    and base.split(".")[-1] in _PALLAS_BASES):
                self._emit(
                    "L006", node,
                    f"raw {chain!r} construction: build Pallas block/grid "
                    "specs and compiler params through ops/kernel_lib/"
                    "tiling.py (the substrate's single construction path, "
                    "which also applies the VMEM-limit default)")
        if (not self.is_ppermute_home and chain
                and chain.split(".")[-1] == "ppermute"):
            self._emit(
                "L007", node,
                f"{chain!r} constructed outside ops/ and "
                "training/train_step.py: the golden censuses pin permute "
                "axes/counts and can only audit permutes whose home they "
                "know — move it, or suppress with a justification")
        if chain and chain.split(".")[-1] == "fault_point" and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                self._check_fault_point(node, arg.value)
        self.generic_visit(node)

    def _check_sync_call(self, node: ast.Call, chain: Optional[str]) -> None:
        if chain in _SYNC_CALLS:
            self._emit("L004", node,
                       f"host-sync {chain!r} in the hot path stalls the "
                       "device pipeline; defer the fetch or suppress with "
                       "a justification")
            return
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _SYNC_METHODS and not node.args):
            self._emit("L004", node,
                       f"'.{node.func.attr}()' in the hot path is a device "
                       "sync; defer the fetch or suppress with a "
                       "justification")
            return
        if (isinstance(node.func, ast.Name)
                and node.func.id in ("float", "int")
                and len(node.args) == 1
                and isinstance(node.args[0], ast.Subscript)
                and isinstance(node.args[0].value, ast.Name)
                and _METRIC_NAMES_RE.match(node.args[0].value.id)):
            self._emit("L004", node,
                       f"'{node.func.id}(<device metrics>[...])' in the hot "
                       "path forces a per-step d2h round trip; fetch via "
                       "the deferred metrics pipeline instead")

    def _check_fault_point(self, node: ast.Call, name: str) -> None:
        if name not in self.ctx.known_fault_points:
            self._emit("L005", node,
                       f"fault point {name!r} is not registered in "
                       "utils/fault_injection.py::KNOWN_FAULT_POINTS")
        elif name not in self.ctx.fault_test_text:
            self._emit("L005", node,
                       f"fault point {name!r} is never exercised by a "
                       "pytest.mark.fault test — an undrilled crash site")

    # -- L002 ----------------------------------------------------------------
    def lint_module_level(self) -> None:
        for name, line in _enum_const_defs(self.tree):
            if name not in self.ctx.registered_enums:
                self.findings.append(Finding(
                    "L002", self.rel, line,
                    f"enum-like config domain {name!r} is not registered "
                    "in config/loader.py::_enum_fields (load-time "
                    "validation + null-normalization)"))


def lint_source(source: str, rel_path: str, ctx: Optional[_RepoContext] = None,
                select: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint one file's source text.  Public so rule unit tests can feed
    synthetic snippets without touching disk."""
    ctx = ctx or _RepoContext.build()
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("L000", rel_path, e.lineno or 0,
                        f"file does not parse: {e.msg}")]
    linter = _FileLinter(rel_path, tree, ctx)
    linter.visit(tree)
    linter.lint_module_level()
    suppressed = parse_suppressions(source)
    chosen = set(select) if select else None
    out = []
    for f in linter.findings:
        if chosen is not None and f.rule not in chosen:
            continue
        if f.rule in suppressed.get(f.line, ()):  # justified allowlist entry
            continue
        out.append(f)
    return sorted(out, key=lambda f: (f.path, f.line, f.rule))


def iter_python_files(paths: Sequence[str]) -> List[str]:
    files: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            files.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames
                           if not d.startswith((".", "__pycache__"))]
            files.extend(os.path.join(dirpath, fn)
                         for fn in filenames if fn.endswith(".py"))
    return sorted(set(files))


def lint_paths(paths: Sequence[str], select: Optional[Iterable[str]] = None,
               repo_root: Optional[str] = None) -> List[Finding]:
    """Lint files/directories; returns unsuppressed findings, sorted."""
    ctx = _RepoContext.build(repo_root)
    findings: List[Finding] = []
    for path in iter_python_files(paths):
        rel = os.path.relpath(path, ctx.repo_root)
        if rel.startswith(".."):
            rel = path
        try:
            source = open(path).read()
        except OSError as e:
            findings.append(Finding("L000", rel, 0, f"unreadable: {e}"))
            continue
        findings.extend(lint_source(source, rel, ctx, select))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))
