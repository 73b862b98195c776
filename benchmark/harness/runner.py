"""One run of one cell: ``python3 benchmark/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

Finds the cell's files by name, asks JAX for the chips, hands a context
to the mix's generator, reduces what it measured with the cell's metric
readers, and prints the result as the last line of standard output.  It
fails (non-zero, no result line) where the program is missing, where JAX
finds no TPU or fewer chips than the cell asks for, and where the
device's kind has no entry in ``peaks.json``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

from benchmark.harness import spec

NO_PROGRAM, NO_CHIP, BAD_SPEC = 3, 4, 5


def say(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the same code at the configuration's tiny sizes "
                         "on the CPU (virtual devices for a 4-chip cell); "
                         "every timing metric is null")
    ap.add_argument("--keep", action="store_true",
                    help="keep the run's pass files and trace")
    return ap.parse_args(argv)


def device_record(devices, n: int, traced: dict) -> dict:
    used = devices[:n]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in used]
    peaks = [p for p in peaks if p is not None]
    rec = {"platform": used[0].platform, "kind": used[0].device_kind,
           "count": n, "memory_peak_bytes": max(peaks) if peaks else None}
    rec.update(traced)
    return rec


def read_metrics(cell, group: str, kind: str, run) -> dict:
    out = {}
    for entry in cell.metrics(group):
        value = cell.module(kind, entry["name"]).read(run)
        if value is None:
            continue
        # off-chip a timing is not a device number: say so with a null
        timed = entry["unit"] not in ("count",)
        out[entry["name"]] = {
            "value": value if (run.timed or not timed) else None,
            "unit": entry["unit"]}
    return out


def setup_spans(ctx, measured) -> dict:
    """Seconds of set-up by the benchmark's span, for PERF.md's list of
    what set-up is made of (spans nest: ``build_pass_feed`` holds
    ``pack_pass_host`` and ``finish_pass_feed``)."""
    out = {}
    for s in ctx.spans.records:
        if s.t1 <= measured.t0:
            out[s.name] = out.get(s.name, 0.0) + (s.t1 - s.t0)
    return out


def traced_record(run) -> tuple:
    """``busy_s`` / ``window_s`` for ``device`` and the ``breakdown``."""
    from benchmark.harness import xplane
    trace, win = run.trace, run.trace_window
    if trace is None or win is None:
        return {}, None
    planes = xplane.device_planes(trace)[:run.chips]
    if not planes:
        return {"busy_s": 0.0, "window_s": (win[1] - win[0]) / 1e9}, None
    busy = xplane.busy_seconds(trace, win)[:run.chips]
    gaps = xplane.attribute_gaps(trace, planes[0], win)
    breakdown = {
        "device_ops": [[n, s] for n, s in
                       xplane.top_ops(trace, planes[0], win)],
        "idle_gaps": [[n, s] for n, s in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]]}
    return ({"busy_s": sum(busy) / len(busy),
             "window_s": (win[1] - win[0]) / 1e9}, breakdown)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse(argv)
    try:
        cell = spec.Cell(args.workload)
    except (spec.SpecError, OSError, KeyError, ValueError) as e:
        say(f"cannot read the cell: {e!r}")
        return BAD_SPEC
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{max(cell.chips, 1)}").strip()
    try:
        from paddlebox_tpu.utils import compile_cache
    except ImportError as e:
        say(f"the program is not here ({e}); the benchmark measures "
            "paddlebox_tpu and does not run without it")
        return NO_PROGRAM
    import jax
    cache_dir = None
    if not args.rehearse:       # a rehearsal's CPU programs are not kept
        cache_dir = compile_cache.enable()
    # every program goes to the cache, the small ones too, so that only a
    # cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        say(f"JAX found no device: {e}")
        return NO_CHIP
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        say(f"JAX found platform {platform!r} ({devices[0].device_kind} "
            f"x{len(devices)}), not a TPU: the benchmark measures on the "
            "chip only (--rehearse is the CPU-size check of the code)")
        return NO_CHIP
    if len(devices) < cell.chips:
        say(f"cell {cell.name} needs {cell.chips} chip(s), JAX reports "
            f"{len(devices)}")
        return NO_CHIP
    peaks = None
    if not args.rehearse:
        table = spec.load_json(os.path.join(spec.BENCH_DIR, "peaks.json"))
        if devices[0].device_kind not in table:
            say(f"no peaks for device kind {devices[0].device_kind!r} in "
                "benchmark/peaks.json: add its published peaks with their "
                "source")
            return NO_CHIP
        peaks = table[devices[0].device_kind]

    from benchmark.harness import checks
    from benchmark.harness.record import Context, Run
    cfg = cell.sized(args.rehearse)
    work = os.path.join(spec.BENCH_DIR, "_work", cell.name,
                        f"seed-{args.seed}"
                        + ("-rehearsal" if args.rehearse else ""))
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    say(f"cell={cell.name} chips={cell.chips} platform={platform} "
        f"kind={devices[0].device_kind!r} seed={args.seed} "
        f"seconds={args.seconds} trace={args.trace} "
        f"compile_cache={cache_dir}")
    ctx = Context(cell, cfg, args, devices, work, t_start)
    try:
        generator = cell.module("generators", cell.traffic["kind"])
        measured = generator.run(ctx)
        measured.checks["losses_finite"] = checks.losses_finite(
            measured.units)
        measured.checks["auc_floor"] = checks.auc_floor(
            measured.units, float(ctx.pair("auc_floor")))
        measured.failed += sum(
            1 for u in measured.units for x in u.losses
            if not math.isfinite(x))
        run = Run(ctx, measured, peaks)
        traced, breakdown = traced_record(run) if args.trace else ({}, None)
        run.device = device_record(devices, cell.chips, traced)
        if args.trace:
            metrics = read_metrics(cell, "per_layer", "layer_metrics", run)
        else:
            metrics = read_metrics(cell, "end_to_end", "e2e_metrics", run)
        result = {"correct": checks.verdict(measured.checks),
                  "attempted": measured.attempted,
                  "failed": measured.failed, "metrics": metrics,
                  "device": run.device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["detail"] = {
            "lowering": measured.lowering, "checks": measured.checks,
            "geometry": measured.geometry, "data": measured.data_stats,
            "units": len(measured.units), "elapsed_s": run.elapsed_s,
            "setup_s": ctx.setup_s, "setup_spans": setup_spans(ctx, measured),
            "compile": ctx.compile_log.summary(),
            "compile_requests_in_window": len(run.compiles),
            "rehearsal": bool(args.rehearse)}
    finally:
        if not args.keep:
            shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0
