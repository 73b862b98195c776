"""PB2xx (cont.) — key-space hygiene for observability (ps/heat.py +
utils/sketch.py discipline).

  PB208  a RAW FEATURE KEY flows into observability state:

         * package-wide — a metric/span name sink (the PB204 vocabulary:
           ``stat_*`` / ``span`` / ``start_span``) or a flight-event
           kind (``flight.record``) is built from a part whose terminal
           component is key-like (``key`` / ``keys`` / ``feasign`` /
           ``fid`` / ``slot_key`` / ``hot_key``) — a 10^11-cardinality
           key space minted into names/kinds grows the registry (or
           shreds the event vocabulary) without bound, one entry per hot
           key, or
         * in obs modules — a dict grows per key: a subscript
           store/augassign or ``setdefault`` whose index terminal is
           key-like.  Exact per-key state in the obs layer is an
           unbounded-memory bug by construction.

Key-derived observability routes through the streaming sketch types in
``utils/sketch.py`` (bounded, mergeable, decayable — count-min /
SpaceSaving / HyperLogLog via ``ps/heat.py``); sketch.py itself is the
sanctioned sink and is exempt from the dict rule.  PB204/PB206 already
flag these name sites generically as "not a bounded field"; PB208 names
the specific disease and its cure.
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional

from paddlebox_tpu.tools.pboxlint.core import (Finding, Module,
                                               PackageContext, dotted_name)
from paddlebox_tpu.tools.pboxlint.metric_names import (_NAME_SINKS,
                                                       _binop_leaves,
                                                       _terminal_field)
from paddlebox_tpu.tools.pboxlint.flight_events import _record_sinks

# terminal components that denote a raw feature key (the wire/table
# vocabulary: feasign is the reference's name for a sparse feature id)
_KEY_LIKE = frozenset({"key", "keys", "feasign", "fid", "slot_key",
                       "hot_key"})

# the obs layer, where per-key dict growth is policed (basenames —
# checker snippets lint under bare filenames); sketch.py is the
# sanctioned bounded sink and deliberately absent
_OBS_BASENAMES = frozenset({"monitor.py", "trace.py", "flight.py",
                            "timeline.py", "obs_server.py", "doctor.py",
                            "intervals.py", "heat.py"})


def _key_part(node: ast.AST) -> Optional[str]:
    """The key-like terminal of a value expression, or None."""
    field = _terminal_field(node)
    return field if field in _KEY_LIKE else None


def _name_findings(mod: Module, call: ast.Call, arg: ast.AST,
                   what: str) -> List[Finding]:
    out: List[Finding] = []

    def flag(part: str) -> None:
        out.append(Finding(
            mod.path, call.lineno, "PB208",
            f"{dotted_name(call.func) or '<call>'}(...) {what} is built "
            f"from raw feature key {part!r} — a 10^11-cardinality key "
            f"space must never be minted into observability names; "
            f"route key-derived observability through the streaming "
            f"sketches (utils/sketch.py via ps/heat.py)"))

    if isinstance(arg, ast.JoinedStr):
        for part in arg.values:
            if isinstance(part, ast.FormattedValue):
                kp = _key_part(part.value)
                if kp is not None:
                    flag(kp)
        return out
    leaves = _binop_leaves(arg)
    if isinstance(arg, ast.BinOp) and leaves is not None:
        for leaf in leaves:
            if not isinstance(leaf, ast.Constant):
                kp = _key_part(leaf)
                if kp is not None:
                    flag(kp)
    return out


def _dict_findings(mod: Module) -> List[Finding]:
    """Obs-module-only: per-key dict growth (subscript store/augassign,
    ``setdefault``)."""
    out: List[Finding] = []

    def flag(lineno: int, form: str, part: str) -> None:
        out.append(Finding(
            mod.path, lineno, "PB208",
            f"{form} keyed by raw feature key {part!r} in obs code — "
            f"exact per-key state is unbounded memory by construction; "
            f"route key-derived observability through the bounded "
            f"sketch types (utils/sketch.py)"))

    for node in mod.walk():
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                if isinstance(t, ast.Subscript):
                    kp = _key_part(t.slice)
                    if kp is not None:
                        flag(node.lineno, "dict store", kp)
        elif isinstance(node, ast.Call) and node.args:
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "setdefault"):
                kp = _key_part(node.args[0])
                if kp is not None:
                    flag(node.lineno, "dict setdefault", kp)
    return out


def check(mod: Module, ctx: PackageContext) -> List[Finding]:
    findings: List[Finding] = []
    flight_sinks = _record_sinks(mod)
    for node in mod.walk():
        if not (isinstance(node, ast.Call) and node.args):
            continue
        called = dotted_name(node.func)
        if called.rsplit(".", 1)[-1] in _NAME_SINKS:
            findings.extend(_name_findings(mod, node, node.args[0],
                                           "metric/span name"))
        elif called in flight_sinks:
            findings.extend(_name_findings(mod, node, node.args[0],
                                           "flight event kind"))
    if os.path.basename(mod.path) in _OBS_BASENAMES:
        findings.extend(_dict_findings(mod))
    return findings
