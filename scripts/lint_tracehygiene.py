#!/usr/bin/env python
"""Trace-hygiene lint for scan-body / kernel code paths in ``codegen/``.

PR 5 hunted a class of trace-time sinks by hand: inside code that runs
*under jit tracing* (the segmented executor's scan body, kernel branches,
comm pattern switches), two idioms silently destroy the performance or
correctness contract:

* ``int(...)`` / ``float(...)`` coercions — concretize a traced value
  (crash) or freeze a build-time value into the wrong trace constant;
  trace-path code must keep indices as ``np.int32``/traced scalars.
* ``jnp`` fancy indexing — ``buf[:, cols]``, ``arr[traced_idx]`` and
  ``.at[...]`` updates lower to unfused gathers/scatters per call site;
  trace paths must go through the span-coalesced helpers
  (``_gather_cols`` / ``_scatter_cols`` / ``_take_row``) or explicit
  ``lax`` primitives so the fast paths stay the only paths.

This lint walks the AST of the files below and enforces both rules inside
the named **trace scopes** (functions that execute during tracing; their
enclosing builders run at schedule-build time and index numpy freely).
Deliberate exceptions either live in the allowlist here or carry a
``# trace-hygiene: ok`` comment on the offending line.

Exit code 0 = clean; 1 = findings (printed as file:line rule message).
Run by ``make check`` via ``scripts/ci.sh``.
"""
from __future__ import annotations

import ast
import os
import sys
from typing import Dict, List, Set, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CODEGEN = os.path.join(ROOT, "src", "repro", "codegen")

# file -> function names whose *bodies* execute under jit tracing (nested
# defs count when their own name is listed; everything else in these files
# is build-time numpy and may index freely)
TRACE_SCOPES: Dict[str, Set[str]] = {
    "executor.py": {
        "worker_fn", "worker_fn_stream", "run_segment", "body", "idle",
        "branch", "mk_pat", "_run_all", "init_buf",
        "_gather_cols", "_scatter_cols", "_take_row",
    },
    "segment.py": {"kern"},
}

# (file, enclosing trace scope, rule) triples that are deliberate (none)
ALLOW: Set[Tuple[str, str, str]] = set()

MARKER = "trace-hygiene: ok"


def _is_static_index(node: ast.expr, in_tuple: bool = False) -> bool:
    """Index expressions that cannot be a traced-array gather: literals,
    plain names as the *sole* key (python list/tuple/dict indexing), unary
    minus on literals, and slices/tuples built only from those.  A bare
    name *inside* a tuple index (``b[:, cols]``) is the classic jnp
    fancy-gather shape and counts as dynamic."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Name):
        return not in_tuple
    if isinstance(node, ast.Attribute):
        # plan.sink / self.field dict keys — build-time constants; traced
        # scalars never live behind attribute reads in these code paths
        return _is_static_index(node.value, in_tuple)
    if isinstance(node, ast.UnaryOp) and isinstance(node.operand, ast.Constant):
        return True
    if isinstance(node, ast.Slice):
        return all(
            p is None or _is_static_index(p)
            for p in (node.lower, node.upper, node.step)
        )
    if isinstance(node, ast.Tuple):
        return all(_is_static_index(e, in_tuple=True) for e in node.elts)
    if isinstance(node, ast.BinOp):
        return (
            _is_static_index(node.left, in_tuple)
            and _is_static_index(node.right, in_tuple)
        )
    return False


def _np_exempt(arg: ast.expr) -> bool:
    """``int(...)`` args that are build-time by construction: constants,
    ``len(...)``, ``np.*``/``math.*`` calls, and ``.shape``/``.size``/
    ``.ndim`` attribute reads (or subscripts of them)."""
    if isinstance(arg, (ast.Constant, ast.Num)):
        return True
    if isinstance(arg, ast.Call):
        f = arg.func
        if isinstance(f, ast.Name) and f.id == "len":
            return True
        while isinstance(f, ast.Attribute):
            f = f.value
        if isinstance(f, ast.Name) and f.id in ("np", "math"):
            return True
        return False
    if isinstance(arg, ast.Subscript):
        return _np_exempt(arg.value)
    if isinstance(arg, ast.Attribute):
        return arg.attr in ("shape", "size", "ndim", "dtype")
    if isinstance(arg, ast.BinOp):
        return _np_exempt(arg.left) and _np_exempt(arg.right)
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, fname: str, scopes: Set[str], marked: Set[int]):
        self.fname = fname
        self.scopes = scopes
        self.marked = marked
        self.stack: List[str] = []      # enclosing function names
        self.trace: List[str] = []      # enclosing *trace-scope* names
        self.findings: List[Tuple[int, str, str, str]] = []

    def _flag(self, node: ast.AST, rule: str, msg: str) -> None:
        scope = self.trace[-1]
        if (self.fname, scope, rule) in ALLOW:
            return
        if node.lineno in self.marked:
            return
        self.findings.append((node.lineno, scope, rule, msg))

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.stack.append(node.name)
        entered = node.name in self.scopes
        if entered:
            self.trace.append(node.name)
        for stmt in node.body:  # skip arg/return annotations
            self.visit(stmt)
        if entered:
            self.trace.pop()
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        # type annotations subscript typing generics — not code
        if node.value is not None:
            self.visit(node.value)

    def visit_Call(self, node: ast.Call) -> None:
        if self.trace and isinstance(node.func, ast.Name) and (
            node.func.id in ("int", "float") and len(node.args) == 1
        ):
            if not _np_exempt(node.args[0]):
                self._flag(
                    node, "int-coercion",
                    f"{node.func.id}() on a possibly-traced value "
                    "(concretizes under jit; keep np.int32/traced scalars)",
                )
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if self.trace:
            idx = node.slice
            if isinstance(idx, ast.Index):  # py<3.9 compat
                idx = idx.value
            if isinstance(node.value, ast.Attribute) and (
                node.value.attr == "at"
            ):
                self._flag(
                    node, "fancy-index",
                    ".at[...] indexed update in a trace scope (use "
                    "dynamic_update_slice / _scatter_cols)",
                )
            elif not _is_static_index(idx):
                self._flag(
                    node, "fancy-index",
                    "computed index in a trace scope lowers to an "
                    "unfused gather (use _gather_cols/_take_row or "
                    "lax primitives)",
                )
        self.generic_visit(node)


def lint_file(path: str, scopes: Set[str]) -> List[str]:
    with open(path) as f:
        src = f.read()
    marked = {
        i + 1 for i, line in enumerate(src.splitlines()) if MARKER in line
    }
    tree = ast.parse(src, filename=path)
    fname = os.path.basename(path)
    linter = _Linter(fname, scopes, marked)
    linter.visit(tree)
    rel = os.path.relpath(path, ROOT)
    return [
        f"{rel}:{line}: [{rule}] in trace scope {scope!r}: {msg}"
        for (line, scope, rule, msg) in sorted(linter.findings)
    ]


def main() -> int:
    findings: List[str] = []
    for fname, scopes in sorted(TRACE_SCOPES.items()):
        path = os.path.join(CODEGEN, fname)
        if not os.path.exists(path):
            print(f"lint_tracehygiene: missing {path}", file=sys.stderr)
            return 2
        findings += lint_file(path, scopes)
    if findings:
        print(f"trace-hygiene: {len(findings)} finding(s)")
        for f in findings:
            print("  " + f)
        return 1
    n_scopes = sum(len(s) for s in TRACE_SCOPES.values())
    print(f"trace-hygiene: clean ({n_scopes} trace scopes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
