"""Traffic kind ``epochs``: one pass resident on the chip, trained again
and again (PaddleRec's in-memory recipe: load once, train epochs).

Set-up loads, pulls and feeds the pass through the pass loop's own calls
(``load_into_memory`` / ``begin_pass`` / ``build_pass_feed``), checks the
first steps against the plain reference, and trains the warm-up epochs.
The window repeats ``train_pass(feed)`` until ``--seconds`` have passed;
``end_pass`` writes back after it.  Host feed work is outside the window
by construction, so the device step sets the pace.

Parameters (``traffic/<mix>.json``): ``files_per_pass``,
``warmup_epochs``, ``trace_seconds``; the pass's ``depth`` in batches is
the cell's own (``cells/<cell>.json``).
"""

from __future__ import annotations

import os
import time

from benchmark.harness import checks, slotdata
from benchmark.harness.record import Measured, Unit


def run(ctx) -> Measured:
    cell, cfg = ctx.cell, ctx.cfg
    measured = Measured()
    prog = ctx.program()
    ds, trainer = prog.dataset, prog.trainer
    n_batches = int(ctx.pair("depth"))
    n = prog.batch_size * n_batches
    fields = slotdata.Fields(cfg)
    with ctx.spans.span("write_pass_files"):
        meta = slotdata.write_passes(
            os.path.join(ctx.work_dir, "data"), fields, ctx.args.seed, 1, n,
            int(ctx.traffic("files_per_pass")))[0]
    measured.data_stats = meta["stats"]

    ds.set_filelist(meta["files"])
    ds.load_into_memory()
    ds.begin_pass()
    with ctx.spans.span("build_pass_feed"):
        feed = trainer.build_pass_feed(ds.dataset)
    measured.geometry = prog.geometry(feed)
    measured.lowering = prog.lowering()

    ref = checks.ReferenceCheck(cell, cfg, prog)
    with ctx.spans.span("reference_steps"):
        ref.capture(feed)
    warm = [trainer.train_pass(feed)
            for _ in range(int(ctx.traffic("warmup_epochs")))]
    measured.checks["reference_losses"] = ref.compare(warm[0]["losses"])
    if not ctx.rehearse:
        found = prog.mosaic_kernels(feed)
        measured.checks["mosaic_kernels"] = {"ok": len(found) == 2,
                                             "found": found}

    with ctx.window(measured):
        deadline = measured.t0 + ctx.window_seconds()
        while True:
            t0 = time.perf_counter()
            try:
                m = trainer.train_pass(feed)
            except (FloatingPointError, RuntimeError, ValueError) as e:
                measured.attempted += n_batches
                measured.failed += n_batches
                measured.checks["train_pass"] = {"ok": False,
                                                 "error": repr(e)}
                break
            t1 = time.perf_counter()
            measured.units.append(Unit(t0, t1, n, int(m["batches"]),
                                       m["losses"], float(m["auc"])))
            measured.attempted += int(m["batches"])
            if t1 >= deadline:
                break

    epochs = len(warm) + len(measured.units)
    prog.capture_readback(meta["probe"]["keys"])
    ds.end_pass()
    measured.checks["write_back"] = prog.check_readback(
        slotdata.probe_counts([meta], [0] * epochs))
    return measured
