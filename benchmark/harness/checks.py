"""The comparisons that decide ``correct``."""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

from benchmark.harness.record import Unit
from benchmark.reference import step as reference


class ReferenceCheck:
    """The cell's first steps from seeded state against the plain
    reference.  ``capture`` runs before the program's first step: it
    copies the initial rows, parameters and the first batches to the host
    and runs the reference there; ``compare`` takes the program's own
    per-step losses once it has trained."""

    def __init__(self, cell, cfg: dict, program):
        self.logit = cell.module("reference", cell.config_name).logit
        self.sgd = cfg["table"]["sgd"]
        self.rtol = float(cfg["correct"]["loss_rtol"])
        # device_default: as a float32 matmul at default precision comes
        # out where the program runs (bfloat16 operands on a TPU)
        self.matmul = cfg["correct"]["reference_matmul"]
        if self.matmul == "device_default":
            on_tpu = program.devices[0].platform == "tpu"
            self.matmul = "bf16_operands" if on_tpu else "float32"
        self.program = program
        self.losses: List[float] = []

    def capture(self, feed) -> None:
        steps = min(reference.STEPS, feed.n_batches)
        ws = self.program.engine.ws
        rows = {f: np.asarray(ws[f]) for f in reference.ROW_FIELDS}
        batches = {k: np.asarray(feed.data[k][:steps])
                   for k in ("indices", "lengths", "dense", "labels",
                             "valid")}
        self.losses = reference.losses(
            self.logit, self.sgd, rows, self.program.trainer.params,
            batches, steps, self.matmul)

    def compare(self, program_losses: Sequence[float]) -> dict:
        got = [float(x) for x in program_losses[:len(self.losses)]]
        ok = bool(self.losses) and len(got) == len(self.losses) and all(
            math.isfinite(g)
            and abs(g - r) <= self.rtol * abs(r)
            for g, r in zip(got, self.losses))
        return {"ok": ok, "reference": self.losses, "program": got,
                "rtol": self.rtol, "reference_matmul": self.matmul}


def losses_finite(units: Sequence[Unit]) -> dict:
    bad = sum(1 for u in units for x in u.losses if not math.isfinite(x))
    return {"ok": bad == 0 and bool(units), "non_finite": bad}


def auc_floor(units: Sequence[Unit], floor: float) -> dict:
    """The last pass's AUC as the trainer reports it (its AUC state runs
    on from pass to pass) against the floor the seeded label model makes
    reachable."""
    last = units[-1].auc if units else float("nan")
    return {"ok": bool(units) and last >= floor, "auc": last,
            "floor": floor}


def verdict(checks: Dict[str, dict]) -> bool:
    return bool(checks) and all(c["ok"] for c in checks.values())
