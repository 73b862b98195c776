"""PB301 — no full-working-set elementwise math in per-step functions.

The sparse step's cost model (ISSUE/ROADMAP item 1, BENCH step_ms split)
is that per-step math scales with the BATCH (the [P] valid occurrences /
[U] unique rows it actually touches), not with the WORKING SET ([N] pass
rows, 2M at bench geometry).  A single innocuous-looking
``jnp.where(touched, ws["show"] + g, ws["show"])`` inside a jitted step
is a full-[N] sweep per step — a regression that creeps back silently
because the op is *correct*, just O(N) instead of O(U).

  PB301  a step-path function uses the full working-set array ``ws[...]``
         as an elementwise operand (math, comparison, non-gather call
         argument, or a non-structural attribute like ``.T``/``.astype``)
         instead of gathering rows first.

Scope is deliberately narrow — the two step-lowering modules
(``fast_path.py``, ``mxu_path.py``), functions that
take the working set as a ``ws`` parameter — so the rule never fires on
host-side table code, which legitimately sweeps [N].

A ``ws[...]`` use is ALLOWED (not a finding) when it is:

  * gathered: ``ws[f][rows]`` — the ws subscript is itself indexed, so
    downstream math runs on the gathered rows, not the full array;
  * structural: ``.at`` (scatter builder), ``.shape``/``.dtype``/
    ``.ndim``/``.size`` metadata;
  * a bare argument to a gather/scatter METHOD call —
    ``tab.at[...].set(ws["show"])``, ``jnp.take(ws["w"], rows)`` — a
    relayout copy, not per-element math (func attr in ``set``/``add``/
    ``max``/``min``/``mul``/``take``);
  * a bare reference: RHS of a plain assign, a return value, a dict /
    tuple / list element (aliasing, e.g. ``out[extra] = ws[extra]``).

Everything else — BinOp / UnaryOp / Compare operands, arguments to any
other call, other attributes — is a finding, anchored at the enclosing
statement's first line (one finding per statement).  The fast/mxu paths'
documented-cheap [N] scalar sweeps carry inline
``# pboxlint: disable-next=PB301 -- why`` suppressions; anything new
must either gather first or argue its own suppression in review.
"""

from __future__ import annotations

import ast
from typing import Dict, List

from paddlebox_tpu.tools.pboxlint.core import (Finding, Module,
                                               PackageContext)

_STEP_MODULES = frozenset({"fast_path.py", "mxu_path.py"})
# metadata / scatter-builder attributes on ws[...] that touch no elements
_STRUCTURAL_ATTRS = frozenset({"at", "shape", "dtype", "ndim", "size"})
# gather/scatter method calls a bare ws[...] may feed (relayout, not math)
_MOVE_METHODS = frozenset({"set", "add", "max", "min", "mul", "take"})


def _parents(fn: ast.AST) -> Dict[ast.AST, ast.AST]:
    out: Dict[ast.AST, ast.AST] = {}
    stack = [fn]
    while stack:
        node = stack.pop()
        for child in ast.iter_child_nodes(node):
            out[child] = node
            stack.append(child)
    return out


def _is_ws_subscript(node: ast.AST) -> bool:
    return (isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Name)
            and node.value.id == "ws")


def _allowed(node: ast.Subscript, parent: ast.AST) -> bool:
    """True when this ws[...] use is structurally safe (see docstring)."""
    if isinstance(parent, ast.Subscript) and parent.value is node:
        return True                     # gathered: ws[f][rows]
    if isinstance(parent, ast.Attribute) and parent.value is node:
        return parent.attr in _STRUCTURAL_ATTRS
    if isinstance(parent, ast.Call) and node in parent.args:
        func = parent.func
        tail = (func.attr if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name) else "")
        return tail in _MOVE_METHODS    # .at[..].set(ws[..]) / take(ws[..])
    if isinstance(parent, (ast.Assign, ast.AnnAssign, ast.Return,
                           ast.Dict, ast.Tuple, ast.List, ast.Starred)):
        return True                     # bare alias / collection element
    return False


def check(mod: Module, ctx: PackageContext) -> List[Finding]:
    if mod.basename not in _STEP_MODULES:
        return []
    findings: List[Finding] = []
    for fn in mod.nodes_of(ast.FunctionDef):
        args = fn.args
        names = {a.arg for a in (args.posonlyargs + args.args
                                 + args.kwonlyargs)}
        if "ws" not in names:
            continue
        parents = _parents(fn)
        seen_lines: set = set()
        for node in ast.walk(fn):
            if not _is_ws_subscript(node) or node not in parents:
                continue
            if _allowed(node, parents[node]):
                continue
            # anchor at the enclosing statement's first line so multiline
            # expressions dedupe and disable-next comments land
            stmt = node
            while stmt in parents and not isinstance(stmt, ast.stmt):
                stmt = parents[stmt]
            line = stmt.lineno if isinstance(stmt, ast.stmt) else node.lineno
            if line in seen_lines:
                continue
            seen_lines.add(line)
            key = (node.slice.value
                   if isinstance(node.slice, ast.Constant) else "...")
            findings.append(Finding(
                mod.path, line, "PB301",
                f"per-step function {fn.name}() uses full working-set "
                f"array ws[{key!r}] as an elementwise operand — a per-step "
                f"O(N) sweep over the whole pass working set; gather the "
                f"touched rows first and do the math in the [U]/[P] domain, "
                f"or document the cost with a disable-next suppression"))
    return findings
