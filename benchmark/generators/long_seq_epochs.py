"""Traffic kind ``long_seq_epochs``: ``seq_epochs`` for a tower of layers
that differ (``paddlebox_tpu/models/hybridlm.py``): linear-attention and
latent-attention mixers, a dense and routed feed-forwards.

Everything ``seq_epochs`` does is done by its own code, imported: the
pass as one file of long sequences, the generator's own layout of it
(``feed_planes``), the comparison with the plain reference after the
warm-up epoch (``LoopReferenceCheck``: two losses, and the dense
parameters, token rows and every leaf after one update; in a traced run
the bfloat16 control that has to come out refused), ``loss_falls``, the
Mosaic kernels, the write-back.  What differs:

* the operations of a step come from ``harness/flops_hybrid.py``, which
  counts the layer kinds the configuration lists and, for the routed
  layers, the assignments that held experts received **as the program
  counted them over the window** (``tower.moe.assignments_held``): a
  routed layer's work is data-dependent, so it is read, not assumed;
* ``moe_dropped``: over the whole run, warm-up included, the routed
  layers dropped no assignment (``tower.moe.dropped_assignments`` 0: the
  assignments to held experts less the rows the expert products were
  given, counted block by block as they ran) and held some.  What the
  compared epoch held is reported beside it and is no condition: with no
  balancing bias run the router may have turned away from this chip's
  experts by then (seed 2147481603 did, PERF.md section 6);
* the held experts' load (busiest and mean, ``tower.moe.expert_load_*``)
  is kept over the whole run, where the check says there is some: a
  window may route nothing to this chip, so ``tower.moe_load_imbalance``
  reads the run's.

Parameters as ``seq_epochs``'s (``traffic/<mix>.json``: ``warmup_epochs``,
``trace_seconds``; ``cells/<cell>.json``: ``depth``, ``auc_floor``,
``loss_falls_margin``; the configuration's ``correct`` limits).
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.generators.seq_epochs import (LoopReferenceCheck,
                                             check_feed_planes, feed_planes,
                                             first_and_rest,
                                             instruction_scopes, note_memory,
                                             write_pass)
from benchmark.harness import flops_hybrid, slotdata
from benchmark.harness.record import Measured, Unit


def moe_counts(since: dict = None) -> dict:
    """The routed layers' counters, less what ``since`` already held."""
    from paddlebox_tpu.utils.monitor import stat_snapshot
    now = stat_snapshot("tower.moe.")
    return {k: v - (since or {}).get(k, 0.0) for k, v in now.items()}


def run(ctx) -> Measured:
    cell, cfg = ctx.cell, ctx.cfg
    measured = Measured()
    prog = ctx.program()
    ds, trainer = prog.dataset, prog.trainer
    n_batches = int(ctx.pair("depth"))
    n = prog.batch_size * n_batches
    fields = slotdata.Fields(cfg)
    with ctx.spans.span("write_pass_files"):
        meta = write_pass(os.path.join(ctx.work_dir, "data", "pass-00"),
                          fields, ctx.args.seed, n)
    measured.data_stats = meta["stats"]

    ds.set_filelist(meta["files"])
    ds.load_into_memory()
    ds.begin_pass()
    with ctx.spans.span("build_pass_feed"):
        feed = trainer.build_pass_feed(ds.dataset)
    own = feed_planes(meta.pop("drawn"), prog.batch_size,
                      int(cfg["lengths"]["max"]))
    measured.checks["feed_planes"] = check_feed_planes(feed, own)
    measured.geometry = prog.geometry(feed)
    measured.lowering = prog.lowering()
    lengths = own["lengths"][:, 0]
    measured.geometry["tokens_valid_per_step"] = float(
        lengths.sum(axis=1).mean())
    moe_before = moe_counts()

    warm = [trainer.train_pass(feed)
            for _ in range(int(ctx.traffic("warmup_epochs")))]
    note_memory(prog.devices, "the warm-up epochs")
    ref = LoopReferenceCheck(cell, cfg, prog)
    with ctx.spans.span("reference_steps"):
        ref.capture(own, control=ctx.trace)
    note_memory(prog.devices, "the reference's update")
    # one more epoch outside the window, in two calls
    first, rest = first_and_rest(feed)
    moe_warm = moe_counts()
    epoch = [trainer.train_pass(first)]
    with ctx.spans.span("reference_steps"):
        ref.read_program()
    epoch.append(trainer.train_pass(rest))
    compared = moe_counts(moe_warm)
    note_memory(prog.devices, "the compared epoch")
    losses = epoch[0]["losses"] + epoch[1]["losses"]
    del first, rest
    measured.checks["reference_losses"] = ref.compare(losses)
    if not ctx.rehearse:
        found = prog.mosaic_kernels(feed)
        measured.checks["mosaic_kernels"] = {"ok": len(found) == 2,
                                             "found": found}
        if ctx.trace:
            measured.scopes = instruction_scopes(prog, feed)

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

    # the window's routed work, as the program counted it
    steps = sum(u.steps for u in measured.units)
    held = measured.stats.get("tower.moe.assignments_held", 0.0) \
        / max(steps, 1)
    sizes = flops_hybrid.hybrid_sizes(cfg)
    measured.geometry["moe_assignments_held_per_step"] = held
    measured.geometry["model_flops_per_step"] = float(np.mean(
        [flops_hybrid.hybrid_step(step, held, **sizes) for step in lengths]))
    counts = moe_counts(moe_before)
    for k in ("max", "mean"):
        measured.geometry["moe_run_load_" + k] = counts.get(
            "tower.moe.expert_load_" + k)
    dropped = counts.get("tower.moe.dropped_assignments")
    measured.checks["moe_dropped"] = {
        "ok": dropped == 0 and counts.get(
            "tower.moe.assignments_held", 0.0) > 0,
        "dropped_assignments": dropped,
        "assignments_held": counts.get("tower.moe.assignments_held"),
        "assignments_held_compared_epoch": compared.get(
            "tower.moe.assignments_held"),
        "assignments": counts.get("tower.moe.assignments")}

    first_epoch = float(np.mean(warm[0]["losses"]))
    last = float(np.mean(measured.units[-1].losses)) \
        if measured.units else float("nan")
    margin = float(ctx.pair("loss_falls_margin"))
    measured.checks["loss_falls"] = {
        "ok": bool(last <= first_epoch - margin), "first_epoch": first_epoch,
        "last_epoch": last, "margin": margin,
        "epoch_means": [float(np.mean(w["losses"])) for w in warm]
        + [float(np.mean(losses))]
        + [float(np.mean(u.losses)) for u in measured.units]}
    epochs = len(warm) + 1 + len(measured.units)
    prog.capture_readback(meta["probe"]["keys"])
    ds.end_pass()
    measured.checks["write_back"] = prog.check_readback(
        slotdata.probe_counts([meta], [0] * epochs))
    return measured
