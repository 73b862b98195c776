"""Traffic kind ``window_seq_epochs``: ``seq_epochs`` for a row model
whose head is the table's rows (``paddlebox_tpu/models/sambay.py``:
Mamba, window, full and cross attention, Gated Memory Units).

Everything ``seq_epochs`` does is done by its own code, imported: the
pass as one file of long sequences, the comparison with the plain
reference after the warm-up epoch (``LoopReferenceCheck``: two losses,
and the dense parameters, token rows and every leaf after one update; in
a traced run the bfloat16 control that has to come out refused),
``loss_falls``, the Mosaic kernels, the write-back.  What differs:

* **the working set holds every held vocabulary id**, whether the pass's
  data has it or not (the model names its head keys and the trainer keeps
  them in every pass), so a key's working-set row is 1 + its rank among
  the pass's keys and the head's together, and the feed carries one more
  plane, ``head_rows``: the working-set row of each head key, the same
  for every batch.  ``feed_planes`` lays both out from the generator's
  own draws and the configuration's ``loss.key_base`` / ``vocab_size``,
  and the check compares ``head_rows`` too;
* the reference is handed ``head_rows`` beside ``seq_epochs``'s planes
  (it reads the head's rows from the table it is given, and merges the
  head's gradient into the rows' before its sparse rule), and its verdict
  carries ``rows_profile``: the token rows' error row by row (the twelve
  worst: their places in batch 0, shows, error and movement), because
  that error has been seen to lie in a handful of rows: the tokens in
  front of an attention sink, where the first layer's backward multiplies
  bfloat16's rounding a few hundredfold on both sides (PERF.md section
  6).  So the rows pass two limits: all of them within
  ``rows_update_rtol``, and all but the ``rows_trimmed`` with the largest
  error within ``rows_trimmed_update_rtol`` (``trimmed_error``: the same
  ratio over the rows that are left), which a fault in more rows than
  those cannot hide under; and ``head_left_out``, a second control in
  every run: the reference's own push with the head's gradient left out,
  put through the rows' comparison in the program's place, which has to
  come out refused (the rows are the one number of the gate that sees
  the merge);
* ``tied_head``: no leaf of the model's parameter tree has the
  vocabulary for a dimension; over the run the step read
  ``vocab_size`` head rows a step (``seq.head.rows``), and the rule
  applied the head's gradient to some of them
  (``seq.head.rows_applied``); the window's share is kept as
  ``geometry.head_rows_applied_share``;
* the operations of a step come from ``harness/flops_sambay.py``.

Parameters as ``seq_epochs``'s (``traffic/<mix>.json``: ``warmup_epochs``,
``trace_seconds``; ``cells/<cell>.json``: ``depth``, ``auc_floor``,
``loss_falls_margin``; the configuration's ``correct`` limits).
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.generators import seq_epochs
from benchmark.generators.seq_epochs import (LoopReferenceCheck,
                                             first_and_rest,
                                             instruction_scopes, note_memory,
                                             write_pass)
from benchmark.harness import flops_sambay, slotdata
from benchmark.harness.record import Measured, Unit

PLANES = seq_epochs.PLANES + ("head_rows",)


def head_keys(cfg: dict) -> np.ndarray:
    return int(cfg["loss"]["key_base"]) + np.arange(
        int(cfg["vocab_size"]), dtype=np.int64)


def feed_planes(drawn: dict, batch_size: int, capacity: int,
                head: np.ndarray) -> dict:
    """``seq_epochs.feed_planes`` over a working set that also holds the
    ``head`` keys: a key's row is 1 + its rank among the pass's keys and
    the head's; ``head_rows`` [N, V] is the row of each head key."""
    held = np.unique(np.concatenate([drawn["keys"], head]))
    # the imported layout, its rows (ranked among the pass's keys alone)
    # replaced by ranks among ``held``
    own = seq_epochs.feed_planes(drawn, batch_size, capacity)
    keys = own["seq_keys"].astype(np.int64)                # [N, B, L]
    rows = np.where(keys > 0, np.searchsorted(held, keys) + 1, 0)
    own["indices"] = np.transpose(rows, (0, 2, 1))[:, None].astype(np.int32)
    own["head_rows"] = np.ascontiguousarray(np.broadcast_to(
        (np.searchsorted(held, head) + 1).astype(np.int32),
        (keys.shape[0], head.size)))
    return own


def check_feed_planes(feed, own: dict) -> dict:
    differ = []
    for k in PLANES:
        got = np.asarray(feed.data[k]) if k in feed.data else None
        if got is None or got.shape != own[k].shape \
                or not np.array_equal(got, own[k]):
            differ.append(k)
    return {"ok": not differ, "planes": list(PLANES), "differing": differ}


def trimmed_error(err: np.ndarray, moved: np.ndarray, k: int) -> float:
    """``sqrt(sum err / sum moved)`` over the rows that are left when the
    ``k`` rows with the largest ``err`` are taken out of both sums."""
    keep = np.argsort(-err)[k:]
    den = float(moved[keep].sum())
    num = float(err[keep].sum())
    return float(np.sqrt(num / den)) if den > 0 else (
        0.0 if num == 0 else float("inf"))


class KeepsFirstPush:
    """The reference's module, remembering what its first ``push_rows``
    was handed (the float32 update's: the control's comes after).
    ``LoopReferenceCheck.one_update`` makes the gradients and pushes them
    in one go and hands back neither; the ``head_left_out`` control needs
    them for a second push."""

    def __init__(self, ref):
        self._ref, self.first = ref, None

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def push_rows(self, rows, batch, d_rows, sgd):
        if self.first is None:
            self.first = (rows, batch, d_rows, sgd)
        return self._ref.push_rows(rows, batch, d_rows, sgd)


class TiedReferenceCheck(LoopReferenceCheck):
    """``LoopReferenceCheck`` whose batches carry ``head_rows`` and whose
    verdict carries ``rows_profile`` and ``head_left_out``."""

    def __init__(self, cell, cfg: dict, program, head_rows: np.ndarray):
        super().__init__(cell, cfg, program)
        self.head_rows = head_rows
        self.ref = KeepsFirstPush(self.ref)
        self.trim = int(cfg["correct"]["rows_trimmed"])
        self.trimmed_rtol = float(cfg["correct"]["rows_trimmed_update_rtol"])

    def by_row(self, got: np.ndarray):
        """The rows' error and the reference's movement, row by row."""
        want = self.want["leaves"]["rows.mf"]
        return (np.square(got - want).sum(axis=1, dtype=np.float64),
                np.square(want - self.rows_before).sum(axis=1,
                                                       dtype=np.float64))

    def one_update(self, batches, mode: str, dtype) -> dict:
        return super().one_update(
            [{**b, "head_rows": self.head_rows} for b in batches], mode,
            dtype)

    def capture(self, own: dict, control: bool) -> None:
        self.rows_before = np.asarray(self.program.engine.ws["mf"])
        self.first_rows = own["indices"][0].reshape(-1)    # batch 0, B = 1..
        self.first_length = int(own["lengths"][0].max())
        self.shows = np.bincount(
            self.first_rows, minlength=self.rows_before.shape[0])
        super().capture(own, control)
        # the same push without the head's gradient, as the program's
        # rows would read had the merge been left out
        rows, batch, d_rows, sgd = self.ref.first
        self.ref.first = None
        err, moved = self.by_row(np.asarray(self.ref.push_rows(
            rows, batch, {**d_rows, "head": np.zeros_like(d_rows["head"])},
            sgd)["mf"], np.float32))
        reading = {"rows": trimmed_error(err, moved, 0),
                   "rows_trimmed": trimmed_error(err, moved, self.trim)}
        self.head_left_out = {
            "what": "the reference's push with the head's gradient left "
                    "out, in the program's place: it has to come out "
                    "refused",
            **reading,
            "refused": bool(reading["rows"] > self.update_rtol["rows"]
                            or reading["rows_trimmed"] > self.trimmed_rtol)}

    def read_program(self) -> None:
        """Also: where the token rows' error lies, row by row."""
        err, moved = self.by_row(np.asarray(self.program.engine.ws["mf"]))
        worst = np.argsort(-err)[:12]
        self.rows_profile = {
            "error_sq": float(err.sum()), "moved_sq": float(moved.sum()),
            "rows_moved": int(np.count_nonzero(moved)),
            "trimmed": {"rows": self.trim, "limit": self.trimmed_rtol,
                        "error": trimmed_error(err, moved, self.trim)},
            "length": self.first_length,
            "worst_rows": [
                {"row": int(r), "shows_in_step": int(self.shows[r]),
                 "at": [int(i) for i in
                        np.flatnonzero(self.first_rows == r)[:3]],
                 "error_sq": float(err[r]), "moved_sq": float(moved[r])}
                for r in worst]}
        self.rows_before = None
        super().read_program()

    def compare(self, program_losses) -> dict:
        out = super().compare(program_losses)
        ok = out["ok"] and self.head_left_out["refused"] \
            and self.rows_profile["trimmed"]["error"] <= self.trimmed_rtol
        return {**out, "ok": bool(ok),
                "rows_profile": self.rows_profile,
                "head_left_out": self.head_left_out}


def head_counts(since: dict = None) -> dict:
    """The tied head's counters, less what ``since`` already held."""
    from paddlebox_tpu.utils.monitor import stat_snapshot
    now = stat_snapshot("seq.head.")
    return {k: v - (since or {}).get(k, 0.0) for k, v in now.items()}


def run(ctx) -> Measured:
    import jax
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
    head_before = head_counts()

    ds.set_filelist(meta["files"])
    ds.load_into_memory()
    ds.begin_pass()
    with ctx.spans.span("build_pass_feed"):
        feed = trainer.build_pass_feed(ds.dataset)
    own = feed_planes(meta.pop("drawn"), prog.batch_size,
                      int(cfg["lengths"]["max"]), head_keys(cfg))
    measured.checks["feed_planes"] = check_feed_planes(feed, own)
    measured.geometry = prog.geometry(feed)
    measured.lowering = prog.lowering()
    lengths = own["lengths"][:, 0]
    sizes = flops_sambay.sambay_sizes(cfg)
    measured.geometry["model_flops_per_step"] = float(np.mean(
        [flops_sambay.sambay_step(step, **sizes) for step in lengths]))
    measured.geometry["tokens_valid_per_step"] = float(
        lengths.sum(axis=1).mean())

    warm = [trainer.train_pass(feed)
            for _ in range(int(ctx.traffic("warmup_epochs")))]
    note_memory(prog.devices, "the warm-up epochs")
    ref = TiedReferenceCheck(cell, cfg, prog, own["head_rows"][0])
    with ctx.spans.span("reference_steps"):
        ref.capture(own, control=ctx.trace)
    note_memory(prog.devices, "the reference's update")
    # one more epoch outside the window, in two calls
    first, rest = first_and_rest(feed)
    epoch = [trainer.train_pass(first)]
    with ctx.spans.span("reference_steps"):
        ref.read_program()
    epoch.append(trainer.train_pass(rest))
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

    # the tied head: no [H, V] parameter, and what the push did with the
    # head's gradient over the run and over the window
    vocab = int(cfg["vocab_size"])
    steps = (len(warm) + 1 + len(measured.units)) * n_batches
    counts = head_counts(head_before)
    read, applied = (counts.get("seq.head." + k, 0.0)
                     for k in ("rows", "rows_applied"))
    dense_head = [str(jax.tree_util.keystr(path)) for path, leaf in
                  jax.tree_util.tree_leaves_with_path(trainer.params)
                  if vocab in leaf.shape]
    measured.checks["tied_head"] = {
        "ok": not dense_head and read == vocab * steps and applied > 0,
        "leaves_as_wide_as_the_vocabulary": dense_head,
        "head_rows_read": read, "head_rows_a_step": read / max(steps, 1),
        "head_rows_applied": applied, "steps": steps}
    # a window's epochs one by one, and the host's spans over it: a
    # window that ran long says in which epoch, and under which span
    measured.geometry["epoch_seconds"] = [u.t1 - u.t0
                                          for u in measured.units]
    measured.geometry["window_host_s"] = {
        k[:-len("_s.sum")]: v for k, v in measured.stats.items()
        if k.endswith("_s.sum") and v > 0}
    in_window = measured.stats.get("seq.head.rows", 0.0)
    if in_window:
        measured.geometry["head_rows_applied_share"] = \
            measured.stats.get("seq.head.rows_applied", 0.0) / in_window

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
