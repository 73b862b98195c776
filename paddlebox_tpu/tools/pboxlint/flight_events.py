"""PB2xx (cont.) — flight-recorder event-kind hygiene (utils/flight.py).

  PB206  an event kind passed to ``flight.record`` is either

         * built dynamically (f-string / ``+`` concatenation) from a
           part that is not a KNOWN BOUNDED FIELD — ``counts()``,
           ``events(kind=...)`` and every postmortem group by kind, so
           an unbounded kind (a rid, a path, a key) shreds the vocabulary
           into one-off buckets and defeats ring triage, or
         * a literal that is not a lowercase identifier
           (``[a-z0-9_]``) — mixed-case/dotted kinds fracture the
           closed event vocabulary that /flightz filters key on, or
         * a lowercase literal that is not in :data:`KNOWN_KINDS` — the
           vocabulary is CLOSED: a new event kind is a deliberate
           vocabulary change (postmortem tooling, /flightz dashboards
           and the ``?kind=`` filters all key on it), so it lands by
           adding the name here in the same change, not by ad-hoc
           minting at a call site.

Same bounded-field vocabulary as PB204 (``cmd / verb / site / kind /
role / phase / stage / table``); unbounded values belong in the event's
**fields**, never in its kind.  Sinks are resolved through the module's
imports — only calls that actually reach ``paddlebox_tpu.utils.flight
.record`` are checked, so unrelated ``record`` methods (bench partials,
IntervalRecorder.record) are out of scope.
"""

from __future__ import annotations

import ast
import re
from typing import List, Set

from paddlebox_tpu.tools.pboxlint.core import (Finding, Module,
                                               PackageContext, dotted_name)
from paddlebox_tpu.tools.pboxlint.metric_names import (_BOUNDED_FIELDS,
                                                       _binop_leaves,
                                                       _terminal_field)

_KIND_OK = re.compile(r"[a-z0-9_]*\Z")
_FLIGHT_MOD = "paddlebox_tpu.utils.flight"

# The closed event-kind vocabulary.  Every whole-literal kind passed to
# flight.record must be one of these; adding an event kind means adding
# it HERE in the same change (the /flightz ?kind= filters, postmortem
# groupers and dashboard queries all key on this vocabulary).
KNOWN_KINDS = frozenset({
    # pass / day lifecycle
    "pass_begin", "pass_end", "pass_feed_begin", "pass_feed_end",
    "day_end", "prefetch_pass_ready", "prefetch_pass_failed",
    # checkpoint / commit
    "checkpoint_save", "checkpoint_load", "ckpt_commit", "ckpt_gc",
    "membership_commit",
    # device row cache
    "cache_evict", "cache_invalidate", "cache_invalidate_moved",
    "cache_invalidate_shard",
    # wire / verbs / dedup
    "verb_retry", "verb_give_up", "fence_redirect", "stream_reconnect",
    "dedup_hit", "dedup_evict", "dedup_restore", "map_refresh",
    "backoff_sleep", "backoff_exhausted",
    # reshard / elastic fleet
    "reshard_begin", "reshard_drive", "reshard_cutover", "reshard_abort",
    "reshard_done", "ps_fleet_resize", "elastic_grow", "elastic_scale_in",
    "elastic_rerendezvous", "leader_elect", "fleet_cursor",
    # trainer / supervisor lifecycle
    "trainer_resume", "trainer_restart", "worker_restart",
    "resume_begin", "resume_ok", "supervisor_give_up",
    # serving tier
    "serving_load", "serving_swap", "serving_resurrect",
    "serving_failover", "serving_delta_flip", "manifest_retry",
    "manifest_giveup",
    # diagnostics
    "fault_injected", "lock_cycle", "race_suspect", "pool_saturated",
    "postmortem_written", "slo_breach", "slo_clear",
    # key-space heat telemetry (ps/heat.py)
    "heat_snapshot", "heat_imbalance",
    # out-of-package emitters sharing the ring (bench.py)
    "bench_phase",
})


def _record_sinks(mod: Module) -> Set[str]:
    """Dotted call names in this module that resolve to flight.record."""
    sinks: Set[str] = set()
    for node in mod.walk():
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == _FLIGHT_MOD:
                    sinks.add(f"{alias.asname or alias.name}.record")
        elif isinstance(node, ast.ImportFrom):
            if node.module == "paddlebox_tpu.utils":
                for alias in node.names:
                    if alias.name == "flight":
                        sinks.add(f"{alias.asname or 'flight'}.record")
            elif node.module == _FLIGHT_MOD:
                for alias in node.names:
                    if alias.name == "record":
                        sinks.add(alias.asname or "record")
    return sinks


def _findings_for_kind(mod: Module, call: ast.Call,
                       arg: ast.AST) -> List[Finding]:
    out: List[Finding] = []

    def flag(reason: str) -> None:
        out.append(Finding(
            mod.path, call.lineno, "PB206",
            f"{dotted_name(call.func) or '<call>'}(...) flight event kind "
            f"{reason} — kinds are the closed vocabulary /flightz filters "
            f"and postmortems group by; unbounded values go in event "
            f"fields, bounded dynamic parts are {sorted(_BOUNDED_FIELDS)}, "
            f"or suppress with a reason"))

    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        if not _KIND_OK.match(arg.value):
            flag(f"literal {arg.value!r} is not a lowercase identifier")
        elif arg.value not in KNOWN_KINDS:
            flag(f"literal {arg.value!r} is not in the closed KNOWN_KINDS "
                 f"vocabulary (tools/pboxlint/flight_events.py) — new event "
                 f"kinds are added there in the same change")
        return out
    if isinstance(arg, ast.JoinedStr):
        for part in arg.values:
            if isinstance(part, ast.Constant):
                if isinstance(part.value, str) \
                        and not _KIND_OK.match(part.value):
                    flag(f"literal segment {part.value!r} is not a "
                         f"lowercase identifier")
            elif isinstance(part, ast.FormattedValue):
                if _terminal_field(part.value) not in _BOUNDED_FIELDS:
                    flag("has an f-string part that is not a known "
                         "bounded field")
        return out
    leaves = _binop_leaves(arg)
    if isinstance(arg, ast.BinOp) and leaves is not None:
        for leaf in leaves:
            if isinstance(leaf, ast.Constant):
                if isinstance(leaf.value, str) \
                        and not _KIND_OK.match(leaf.value):
                    flag(f"literal segment {leaf.value!r} is not a "
                         f"lowercase identifier")
            elif _terminal_field(leaf) not in _BOUNDED_FIELDS:
                flag("is concatenated (+) from a part that is not a "
                     "known bounded field")
        return out
    # bare names/calls as the whole kind are out of static reach — the
    # f-string/+ forms are where unbounded kinds actually get minted
    return out


def check(mod: Module, ctx: PackageContext) -> List[Finding]:
    sinks = _record_sinks(mod)
    if not sinks:
        return []
    findings: List[Finding] = []
    for node in mod.walk():
        if not (isinstance(node, ast.Call) and node.args):
            continue
        if dotted_name(node.func) not in sinks:
            continue
        findings.extend(_findings_for_kind(mod, node, node.args[0]))
    return findings
