"""Layer 1: repo-specific AST lint rules (pure stdlib — no jax import).

Each rule is scoped to the subtree where its invariant lives:

  * ``host-transfer``   — ``src/repro/kernels/``
  * ``unseeded-random`` — ``src/repro/{net,runtime,core}/``
  * ``mutable-default`` / ``bare-except`` / ``silent-except`` — ``src/``
  * ``protocol-write``  — ``src/repro/runtime/{control,export}.py``
  * ``unused-import``   — src + tests + benchmarks + examples + tools
                          (``__init__.py`` re-export modules excluded)

Paths are repo-root-relative posix strings, so the same scoping works
on fixture trees that mirror the real layout (tests/test_analysis.py).
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Set

from .findings import Finding, apply_suppressions, suppressions

#: Whitelisted host-boundary functions inside kernels/ — the only
#: places device data may legally materialize on the host.  Keyed by
#: repo-relative path; values are function names within that file.
KERNEL_BOUNDARY_FUNCS: Dict[str, Set[str]] = {
    "src/repro/kernels/sketch_update/kernel.py": {
        # trace-time inspection of concrete *input* values ("auto" mode)
        "resolve_value_mode",
    },
    "src/repro/kernels/sketch_update/fleet.py": {
        # the per-row loop oracle assembles its stacked output on host
        "fleet_update_loop",
    },
    "src/repro/kernels/sketch_query/engine.py": {
        # query entry points: host params in, (K,)-sized estimates out
        "_prep_window_params",
        "fleet_window_query_device",
        "fleet_window_query_paths",
        "um_window_query_device",
        "um_gsum_device",
        # sharded twins: host params sharded in, only (K,) estimates
        # cross back (docs/sharding.md); _pad_rows pads host inputs
        "_pad_rows",
        "_sharded_window_query",
        "_sharded_um_query",
    },
}

#: np.random constructors that are fine *when seeded* (flagged only
#: when called with no arguments).
_SEEDED_CTORS = {"default_rng", "RandomState", "SeedSequence", "Generator",
                 "PCG64", "MT19937", "Philox"}

_HOST_CALLS = {("np", "asarray"), ("numpy", "asarray"),
               ("jax", "device_get")}
_HOST_METHODS = {"host", "block_until_ready"}

_PROTO_FIELDS = {"version", "seq"}


def _attr_chain(node) -> List[str]:
    """['np', 'random', 'default_rng'] for np.random.default_rng; []
    when the root is not a plain Name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return []


def _terminal_field(target) -> str:
    if isinstance(target, ast.Attribute):
        return target.attr
    if isinstance(target, ast.Name):
        return target.id
    return ""


class _FileLinter(ast.NodeVisitor):
    def __init__(self, path: str, tree: ast.Module):
        self.path = path
        self.tree = tree
        self.findings: List[Finding] = []
        self._func_stack: List[str] = []
        self._if_field_stack: List[Set[str]] = []
        self.in_kernels = path.startswith("src/repro/kernels/")
        self.in_seeded = any(path.startswith(p) for p in (
            "src/repro/net/", "src/repro/runtime/", "src/repro/core/"))
        self.in_src = path.startswith("src/")
        self.proto_file = path in ("src/repro/runtime/control.py",
                                   "src/repro/runtime/export.py")
        self._boundary = KERNEL_BOUNDARY_FUNCS.get(path, set())
        self._imports_random = any(
            isinstance(n, ast.Import)
            and any(a.name == "random" for a in n.names)
            for n in ast.walk(tree))

    def _emit(self, rule: str, node, message: str) -> None:
        self.findings.append(Finding(rule, self.path, node.lineno, message))

    # -- scope tracking ---------------------------------------------------

    def visit_FunctionDef(self, node):
        self._check_defaults(node)
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_If(self, node):
        self.visit(node.test)
        fields = {t for t in (
            _terminal_field(n) for n in ast.walk(node.test))
            if t in _PROTO_FIELDS}
        self._if_field_stack.append(fields)
        for child in node.body:
            self.visit(child)
        self._if_field_stack.pop()
        for child in node.orelse:
            self.visit(child)

    # -- mutable-default --------------------------------------------------

    def _check_defaults(self, node) -> None:
        if not self.in_src:
            return
        for d in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]:
            mutable = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call) and isinstance(d.func, ast.Name)
                and d.func.id in ("list", "dict", "set"))
            if mutable:
                self._emit("mutable-default", d,
                           f"mutable default argument in {node.name}()")

    # -- except rules -----------------------------------------------------

    def visit_ExceptHandler(self, node):
        if self.in_src:
            if node.type is None:
                self._emit("bare-except", node,
                           "bare except: name the exception type")
            elif (isinstance(node.type, ast.Name)
                  and node.type.id in ("Exception", "BaseException")
                  and len(node.body) == 1
                  and isinstance(node.body[0], ast.Pass)):
                self._emit("silent-except", node,
                           f"except {node.type.id}: pass silently "
                           "discards the failure")
        self.generic_visit(node)

    # -- host-transfer + unseeded-random ----------------------------------

    def visit_Call(self, node):
        chain = _attr_chain(node.func)
        if self.in_kernels:
            is_host = (tuple(chain) in _HOST_CALLS) or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _HOST_METHODS)
            if is_host and not any(f in self._boundary
                                   for f in self._func_stack):
                name = ".".join(chain) if chain else node.func.attr + "()"
                self._emit("host-transfer", node,
                           f"{name} materializes device data on host "
                           "outside a whitelisted boundary function")
        if self.in_seeded and len(chain) >= 2:
            if chain[0] in ("np", "numpy") and chain[1] == "random" \
                    and len(chain) == 3:
                fn = chain[2]
                if fn in _SEEDED_CTORS:
                    if not node.args and not node.keywords:
                        self._emit("unseeded-random", node,
                                   f"np.random.{fn}() without a seed "
                                   "breaks replay determinism")
                else:
                    self._emit("unseeded-random", node,
                               f"global-state np.random.{fn}(): use a "
                               "seeded np.random.default_rng/RandomState")
            elif chain[0] == "random" and self._imports_random:
                self._emit("unseeded-random", node,
                           f"stdlib random.{chain[1]}() uses hidden "
                           "global state; use a seeded RNG object")
        self.generic_visit(node)

    # -- protocol-write ---------------------------------------------------

    def _check_proto_write(self, node, targets, value, aug_add: bool):
        if not self.proto_file:
            return
        for t in targets:
            field = _terminal_field(t)
            if field not in _PROTO_FIELDS:
                continue
            if aug_add:
                continue                       # increment: always legal
            if not self._func_stack or self._func_stack[-1] == "__init__":
                continue                       # class-body / __init__ init
            if isinstance(value, ast.Call) and \
                    isinstance(value.func, ast.Name) and \
                    value.func.id == "max":
                continue                       # max-merge
            if any(field in fields for fields in self._if_field_stack):
                continue                       # guarded compare-then-set
            self._emit("protocol-write", node,
                       f"write to protocol field `{field}` is not an "
                       "increment, max-merge, guarded compare, or init")

    def visit_Assign(self, node):
        self._check_proto_write(node, node.targets, node.value, False)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        if node.value is not None:
            self._check_proto_write(node, [node.target], node.value, False)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_proto_write(node, [node.target], node.value,
                                aug_add=isinstance(node.op, ast.Add))
        self.generic_visit(node)


_NOQA_RE = re.compile(r"#\s*noqa(?::\s*(?P<codes>[A-Z0-9, ]+))?", re.I)


def _noqa_lines(source: str) -> Set[int]:
    """Lines where ruff-style ``# noqa`` (bare, or listing F401)
    suppresses the unused-import emulation — keeps one suppression
    syntax working for both ruff and this analyzer."""
    out: Set[int] = set()
    for i, text in enumerate(source.splitlines(), start=1):
        m = _NOQA_RE.search(text)
        if m and (m.group("codes") is None or "F401" in m.group("codes")):
            out.add(i)
    return out


def _unused_imports(path: str, tree: ast.Module,
                    noqa: Set[int]) -> List[Finding]:
    if os.path.basename(path) == "__init__.py":
        return []                     # re-export modules: ruff's noqa turf
    bound: List = []                  # (name, lineno, display)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = a.asname or a.name.split(".")[0]
                bound.append((name, getattr(a, "lineno", node.lineno),
                              a.name))
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for a in node.names:
                if a.name == "*":
                    continue
                name = a.asname or a.name
                bound.append((name, getattr(a, "lineno", node.lineno),
                              a.name))
    used: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
    for node in ast.walk(tree):       # names exported via __all__
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__" and \
                        isinstance(node.value, (ast.List, ast.Tuple)):
                    for elt in node.value.elts:
                        if isinstance(elt, ast.Constant) and \
                                isinstance(elt.value, str):
                            used.add(elt.value)
    seen_bindings = set()
    out = []
    for name, lineno, display in bound:
        if name in used or lineno in noqa or \
                (name, lineno) in seen_bindings:
            continue
        seen_bindings.add((name, lineno))
        out.append(Finding("unused-import", path, lineno,
                           f"`{display}` imported but unused"))
    return out


# ---------------------------------------------------------------------------

_LINT_DIRS = ("src", "tests", "benchmarks", "examples", "tools")


def iter_py_files(root: str):
    """Yield repo-relative posix paths of lint targets under ``root``."""
    for d in _LINT_DIRS:
        top = os.path.join(root, d)
        if not os.path.isdir(top):
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [x for x in dirnames if x != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    full = os.path.join(dirpath, fn)
                    yield os.path.relpath(full, root).replace(os.sep, "/")


def lint_file(root: str, relpath: str) -> List[Finding]:
    full = os.path.join(root, relpath)
    with open(full, encoding="utf-8") as fh:
        source = fh.read()
    try:
        tree = ast.parse(source, filename=relpath)
    except SyntaxError as e:
        return [Finding("syntax-error", relpath, e.lineno or 1,
                        f"syntax error: {e.msg}")]
    linter = _FileLinter(relpath, tree)
    linter.visit(tree)
    findings = linter.findings + _unused_imports(relpath, tree,
                                                 _noqa_lines(source))
    return apply_suppressions(findings, suppressions(source))


def run_lint(root: str) -> List[Finding]:
    out: List[Finding] = []
    for rel in iter_py_files(root):
        out.extend(lint_file(root, rel))
    return out
