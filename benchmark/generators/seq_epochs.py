"""Traffic kind ``seq_epochs``: one pass of long token sequences resident
on the chip, trained again and again by a sequence model that owns its
loss (``paddlebox_tpu/models/looplm.py``).

The ``epochs`` recipe with what a next-token model needs changed:

* the pass is one file of lines that hold one sequence slot of up to
  9,999 keys (``harness/slotdata.py`` writes a two-digit count), written
  here from ``slotdata.draw_file``'s draws.  One file, because the loader
  keeps a file's lines in order: the generator then knows which sequence
  sits where in the feed and lays the pass out itself (``feed_planes``:
  keys, working-set rows, lengths, labels).  The reference is fed those
  planes, not the program's, and ``feed_planes`` in ``checks`` says that
  the program's feed holds the same;
* at the seeded state every token row is still masked (the table creates
  a row on the first push that touches it), so the model is blind to its
  tower; the comparison with the plain reference
  (``reference/<config>.py``) is taken after the warm-up epochs, from
  the state the program then holds: loss A is the reference's forward on
  batch 0, loss B its forward on batch 1 after one whole update of its
  own (the sparse rule on batch 0's rows, Adam from the program's
  moments).  The program's next epoch, still outside the window, is
  trained as its first batch alone and then the rest (two views of the
  feed, the same compiled step): between the two its parameters and rows
  are compared with the reference's after that one update, as
  ``|program - reference| / |reference - before|``: 0 for the same
  update, 1 for a state left as it was (the dense parameters as one
  vector, the token rows, and each leaf for the record: a small leaf's
  own ratio swings with how little it moved).  The reference runs where the
  program runs (a step is 10^14 operations), under ``highest`` matmul
  precision with operands rounded as ``correct.reference_matmul`` says;
* the control: in a traced run, whose set-up is not timed, the reference
  is also computed with parameters, rows and activations in bfloat16, and
  put through the same comparison in the program's place.  It has to come
  out refused (``reference_losses.control.ok`` false): the second reading
  that the limits are set from, taken again on every traced run;
* ``loss_falls``: the last measured epoch's mean loss lies under the
  first warm-up epoch's by the cell's ``loss_falls_margin``.

Parameters (``traffic/<mix>.json``): ``warmup_epochs``,
``trace_seconds``; the pass's ``depth``, ``auc_floor`` and
``loss_falls_margin`` are the cell's own (``cells/<cell>.json``); the
limits ``loss_rtol``, ``update_rtol``, ``rows_update_rtol`` and
``leaf_update_rtol`` the configuration's (``correct``).
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import re
import sys
import time

import numpy as np

from benchmark.harness import flops, slotdata
from benchmark.harness.record import Measured, Unit

PLANES = ("indices", "lengths", "valid", "labels", "seq_keys")


def write_pass(directory: str, fields, seed: int, n_examples: int) -> dict:
    """One pass as one file of ``1 <label> <D> <dense...> <n> <key...>``
    lines; what ``slotdata.write_pass`` returns, and the draws themselves
    under ``drawn``."""
    os.makedirs(directory, exist_ok=True)
    ex = slotdata.draw_file(fields, n_examples, seed, 0, 0)
    probe = slotdata.pick_probe_keys(ex["keys"], seed)
    path = os.path.join(directory, "part-000.txt")
    per_line = ex["lens"].sum(axis=1)
    starts = np.cumsum(per_line) - per_line
    with open(path, "w") as fh:
        for r in range(n_examples):
            dense = " ".join(f"{v / slotdata.DENSE_SCALE:.4f}"
                             for v in ex["dense"][r])
            line = [f"1 {ex['labels'][r]} {fields.dense_dim} {dense}"]
            at = starts[r]
            for n in ex["lens"][r]:
                line.append(f"{n} " + " ".join(
                    map(str, ex["keys"][at:at + n])))
                at += n
            fh.write(" ".join(line) + "\n")
    at = np.minimum(np.searchsorted(probe, ex["keys"]), probe.size - 1)
    counts = np.bincount(at[probe[at] == ex["keys"]], minlength=probe.size)
    return {"files": [path], "pass_id": 0, "seed": seed, "drawn": ex,
            "stats": {"examples": n_examples,
                      "occurrences": int(ex["keys"].size),
                      "occurrences_per_example": ex["keys"].size / n_examples,
                      "max_slot_len": int(ex["lens"].max()),
                      "unique_keys": int(np.unique(ex["keys"]).size)},
            "probe": {"keys": [int(k) for k in probe],
                      "counts": [int(c) for c in counts]}}


def feed_planes(drawn: dict, batch_size: int, capacity: int) -> dict:
    """The pass in the feed's layout, from the generator's own draws of
    its one sequence slot: example r of the file is place r % B of batch
    r // B; a key's working-set row is 1 + its rank among the pass's keys
    (row 0 is the reserved zero row); positions past a length hold 0."""
    lens = drawn["lens"][:, 0]
    n = lens.size
    nb = n // batch_size
    keys = np.zeros((n, capacity), np.int64)
    keys[np.repeat(np.arange(n), lens),
         np.arange(lens.sum()) - np.repeat(np.cumsum(lens) - lens, lens)] = \
        drawn["keys"]
    rows = np.where(keys > 0,
                    np.searchsorted(np.unique(drawn["keys"]), keys) + 1, 0)

    def batched(a):
        return a.reshape((nb, batch_size) + a.shape[1:])

    return {"seq_keys": batched(keys).astype(np.int32),
            "indices": np.transpose(batched(rows), (0, 2, 1)
                                    )[:, None].astype(np.int32),
            "lengths": batched(lens)[:, None].astype(np.int32),
            "labels": batched(drawn["labels"]).astype(np.float32),
            "valid": np.ones((nb, batch_size), bool)}


def check_feed_planes(feed, own: dict) -> dict:
    """The program's feed against the generator's own layout of the pass
    (the new key plane among them: a shifted, cut or wrong slot's plane
    would teach both sides the same wrong targets otherwise)."""
    differ = []
    for k in PLANES:
        got = np.asarray(feed.data[k])
        if got.shape != own[k].shape or not np.array_equal(got, own[k]):
            differ.append(k)
    return {"ok": not differ, "planes": list(PLANES), "differing": differ}


def note_memory(devices, after: str) -> None:
    """The device's peak so far, to standard error: which phase of set-up
    set ``memory_peak_bytes`` (the step's own need is 14.3 GB of 16)."""
    stats = devices[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[benchmark] after {after}: peak "
              f"{stats['peak_bytes_in_use'] / 1e9:.3f} GB of "
              f"{stats.get('bytes_limit', 0) / 1e9:.3f}", file=sys.stderr,
              flush=True)


def first_and_rest(feed):
    """Two views of a feed for one epoch in two calls: its first batch
    alone, and batches 1.. (the stacked planes rolled by one, so that
    both run the step the whole feed compiled)."""
    import jax.numpy as jnp

    def rolled(tree):
        return {k: jnp.roll(v, -1, axis=0) for k, v in tree.items()}

    return (dataclasses.replace(feed, n_batches=1),
            dataclasses.replace(feed, data=rolled(feed.data),
                                plans=rolled(feed.plans),
                                n_batches=feed.n_batches - 1))


class LoopReferenceCheck:
    """One update of the plain reference from the state the program holds
    (module docstring), against the program's own next two steps."""

    def __init__(self, cell, cfg: dict, program):
        self.ref = cell.module("reference", cell.config_name)
        self.cfg, self.program = cfg, program
        self.rtol = float(cfg["correct"]["loss_rtol"])
        # limits for the dense parameters as one vector (Adam), for the
        # token rows (the sparse rule, linear in a gradient that has
        # crossed every layer application: ten times the noise), and a
        # wide one for every leaf alone (a small leaf left as it was
        # hides in the vector)
        self.update_rtol = {"dense": float(cfg["correct"]["update_rtol"]),
                            "rows": float(cfg["correct"]["rows_update_rtol"]),
                            "leaf": float(cfg["correct"]["leaf_update_rtol"])}
        self.matmul = cfg["correct"]["reference_matmul"]
        if self.matmul == "device_default":
            on_tpu = program.devices[0].platform == "tpu"
            self.matmul = "bf16_operands" if on_tpu else "float32"
        self.want = self.control = self.program_error = None

    def one_update(self, batches, mode: str, dtype) -> dict:
        """Loss A (batch 0 from the program's state), one whole update,
        loss B (batch 1 after it), with parameters, rows and so the
        activations in ``dtype``; the updated leaves on the host, and how
        far each moved (float32 only: that is the reference's)."""
        import jax
        import jax.numpy as jnp
        ref, cfg, trainer = self.ref, self.cfg, self.program.trainer
        ws = self.program.engine.ws
        rows = {f: ws[f] for f in ref.reference.ROW_FIELDS}
        adam = trainer.opt_state[0]
        t = np.float32(int(adam.count) + 1)
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=0)

        def cast(tree):
            return jax.tree.map(lambda a: a.astype(dtype), tree)

        # the layers' weights unstacked once (a copy): a layer's gradient
        # then has a layer's size
        old = cast(ref.unstack(trainer.params))
        out = ref.batch_loss(old, cast(ref.created_mf(rows)), batches[0],
                             cfg, mode, with_grads=True, add=add)
        new_rows = ref.push_rows(rows, batches[0], out["d_rows"],
                                 cfg["table"]["sgd"])
        new = ref.adam_unstacked(old, adam.mu, adam.nu, out.pop("d_params"),
                                 t)
        moved = None
        if dtype == jnp.float32:
            moved = ref.leaf_sq_dist(new, old)
            moved["rows.mf"] = float(ref.sq_dist(new_rows["mf"], rows["mf"]))
        loss_a = float(out["loss"])
        del old, out
        new = cast(new)
        loss_b = ref.batch_loss(new, cast(ref.created_mf(new_rows)),
                                batches[1], cfg, mode)["loss"]
        leaves = ref.host_leaves(new)
        leaves["rows.mf"] = np.asarray(cast(new_rows["mf"]), np.float32)
        return {"losses": [loss_a, float(loss_b)], "leaves": leaves,
                "moved": moved}

    def capture(self, own: dict, control: bool) -> None:
        import jax
        import jax.numpy as jnp
        batches = [{k: own[k][i] for k in PLANES} for i in range(2)]
        with jax.default_matmul_precision("highest"):
            self.want = self.one_update(batches, self.matmul, jnp.float32)
            gc.collect()
            if control:
                got = self.one_update(batches, "float32", jnp.bfloat16)
                self.control = {
                    "what": "the reference with parameters, rows and "
                            "activations in bfloat16, in the program's place",
                    **self.verdict(got["losses"],
                                   self.update_error(got["leaves"]))}
        gc.collect()

    def update_error(self, leaves: dict) -> dict:
        """``|got - reference| / |reference - before|``: over the dense
        parameters as one vector, of the token rows, and leaf by leaf."""
        off = {k: float(np.sum(np.square(np.asarray(leaves[k], np.float32)
                                         - want), dtype=np.float64))
               for k, want in self.want["leaves"].items()}
        moved = self.want["moved"]

        def ratio(keys):
            num, den = sum(off[k] for k in keys), sum(moved[k] for k in keys)
            return math.sqrt(num / den) if den > 0 else (
                0.0 if num == 0 else math.inf)

        return {"dense": ratio([k for k in off if k != "rows.mf"]),
                "rows": ratio(["rows.mf"]),
                "by_leaf": {k: ratio([k]) for k in off}}

    def read_program(self) -> None:
        """After the program's one step on batch 0: its parameters and
        rows against the reference's (which are then let go)."""
        leaves = self.ref.host_leaves(self.program.trainer.params)
        leaves["rows.mf"] = np.asarray(self.program.engine.ws["mf"])
        self.program_error = self.update_error(leaves)
        self.want["leaves"] = None
        gc.collect()

    def verdict(self, losses, error: dict) -> dict:
        want = self.want["losses"]
        got = [float(x) for x in losses[:len(want)]]
        ok = len(got) == len(want) and all(
            math.isfinite(g) and abs(g - r) <= self.rtol * abs(r)
            for g, r in zip(got, want)) \
            and error["dense"] <= self.update_rtol["dense"] \
            and error["rows"] <= self.update_rtol["rows"] \
            and all(v <= self.update_rtol["leaf"]
                    for v in error["by_leaf"].values())
        return {"ok": bool(ok), "losses": got, "update_error": error}

    def compare(self, program_losses) -> dict:
        out = self.verdict(program_losses, self.program_error)
        out = {**out, "program": out.pop("losses"),
               "reference": self.want["losses"], "rtol": self.rtol,
               "update_rtol": self.update_rtol,
               "reference_moved": {k: math.sqrt(v) for k, v in
                                   self.want["moved"].items()},
               "reference_matmul": self.matmul,
               "taken": "after the warm-up epochs, from the program's "
                        "rows, parameters and Adam moments"}
        if self.control is not None:
            out["control"] = self.control
        return out


def instruction_scopes(program, feed, needle: str = "tower.") -> dict:
    """Instruction name -> ``op_name`` for the compiled step's
    instructions under a scope that holds ``needle`` (the trace names a
    device operation by its instruction; ``tower.device_share`` reads
    this where the event itself does not carry the scope)."""
    t = program.trainer
    text = t._packed_step_fn.lower(
        program.engine.ws, t.params, t.opt_state, t.auc_state, np.int32(0),
        feed.data, feed.plans or {}).compile().as_text()
    found = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"",
                       text, re.M)
    return {name: op for name, op in found if needle in op}


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
    measured.geometry["model_flops_per_step"] = float(np.mean(
        [flops.looplm_step(step, **flops.looplm_sizes(cfg))
         for step in lengths]))
    measured.geometry["tokens_valid_per_step"] = float(
        lengths.sum(axis=1).mean())

    warm = [trainer.train_pass(feed)
            for _ in range(int(ctx.traffic("warmup_epochs")))]
    note_memory(prog.devices, "the warm-up epochs")
    ref = LoopReferenceCheck(cell, cfg, prog)
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
