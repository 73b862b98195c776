"""Traffic kind ``stream``: pass after pass through the whole pass loop,
what a retraining team runs all day.

Every pass is read, parsed, deduplicated, pulled, packed, uploaded,
trained and written back by ONE ``fleet.train_passes`` call with
``prefetch`` left at the program's default (``FLAGS_pass_prefetch``: pass
N+1 feeds while pass N trains).  The loop indexes ``passes[i]`` and
submits every load up front, so the number of passes is fixed before the
call: it is the cell's ``passes``, a plain count chosen once so that the
window lasts about the benchmark's ``run_seconds``.  The same command
then does the same work in every run, ``--seconds`` does not steer it,
and the metrics use the time the passes really took.  The warm-up passes
run in a call of their own.

The ``distinct_passes`` seeded passes are cycled.  With no more of them
than warm-up passes, every key of a measured pass is already in the host
table: the pull finds every row and no row is inserted.

Parameters (``traffic/<mix>.json``): ``files_per_pass``,
``distinct_passes``, ``warmup_passes``, ``trace_passes``; the pass's
``depth`` in batches and the window's ``passes`` are the cell's own
(``cells/<cell>.json``).
"""

from __future__ import annotations

import os

from benchmark.harness import checks, slotdata
from benchmark.harness.record import Measured, Unit


def run(ctx) -> Measured:
    from paddlebox_tpu import fleet
    cell, cfg = ctx.cell, ctx.cfg
    measured = Measured()
    prog = ctx.program()
    ds, trainer, spans = prog.dataset, prog.trainer, ctx.spans
    n_batches = int(ctx.pair("depth"))
    n = prog.batch_size * n_batches
    distinct = int(ctx.traffic("distinct_passes"))
    fields = slotdata.Fields(cfg)
    with spans.span("write_pass_files"):
        metas = slotdata.write_passes(
            os.path.join(ctx.work_dir, "data"), fields, ctx.args.seed,
            distinct, n, int(ctx.traffic("files_per_pass")))
    measured.data_stats = metas[0]["stats"]

    ref = checks.ReferenceCheck(cell, cfg, prog)
    kernels = {}

    def before_first(feed):
        measured.geometry = prog.geometry(feed)
        measured.lowering = prog.lowering()
        with spans.span("reference_steps"):
            ref.capture(feed)

    def after_first(feed):
        kernels["found"] = prog.mosaic_kernels(feed)

    prog.capture_readback(metas[0]["probe"]["keys"])
    trainer.before_first_pass = before_first
    if not ctx.rehearse:
        trainer.after_first_pass = after_first
    n_warm = int(ctx.traffic("warmup_passes"))
    trained = [k % distinct for k in range(n_warm)]
    warm = fleet.train_passes(trainer, ds,
                              [metas[p]["files"] for p in trained])
    measured.checks["reference_losses"] = ref.compare(warm[0]["losses"])
    if not ctx.rehearse:
        measured.checks["mosaic_kernels"] = {
            "ok": len(kernels.get("found", [])) == 2, **kernels}

    n_passes = int(ctx.traffic("trace_passes") if ctx.trace
                   else ctx.pair("passes"))
    window = [(n_warm + k) % distinct for k in range(n_passes)]
    measured.attempted = n_passes
    results = []
    with ctx.window(measured):
        try:
            results = fleet.train_passes(
                trainer, ds, [metas[p]["files"] for p in window])
        except (FloatingPointError, RuntimeError, ValueError,
                ConnectionError) as e:
            measured.checks["train_passes"] = {"ok": False,
                                               "error": repr(e)}
    # a pass runs from its load starting (on the prefetch worker) to its
    # end_pass returning (on the dispatching thread): k-th to k-th
    loads = spans.named("load_into_memory", measured.t0, measured.t1)
    ends = spans.named("end_pass", measured.t0, measured.t1)
    for load, end, m in zip(loads, ends, results):
        measured.units.append(Unit(load.t0, end.t1, n, int(m["batches"]),
                                   m["losses"], float(m["auc"])))
    measured.failed = n_passes - len(measured.units)
    trained += window[:len(measured.units)]
    measured.checks["write_back"] = prog.check_readback(
        slotdata.probe_counts(metas, trained))
    return measured
