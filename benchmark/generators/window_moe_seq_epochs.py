"""Traffic kind ``window_moe_seq_epochs``: ``long_seq_epochs`` for a routed
tower whose mixers are window and full grouped-query attention
(``paddlebox_tpu/models/afmoe.py``) and whose routing bias moves by the
balancing rule after every update.

Everything is ``long_seq_epochs.run``'s, run as it stands: the pass as
one file of long sequences, the generator's own layout of it, the
comparison with the plain reference after the warm-up epoch (the bias is
a leaf of the parameters, so the reference's one update moves it by the
same rule from its own counts), ``moe_dropped``, ``loss_falls``, the
Mosaic kernels, the write-back.  What differs:

* the operations of a step come from ``harness/flops_afmoe.py``;
* the reference's one update ends with the bias rule
  (``reference/<config>.py::balance_bias``, from the counts its own
  forward on batch 0 made), as the program's step ends with its
  after-update hook: ``LoopReferenceCheck.one_update`` hands Adam the
  gradients alone, so the reference's module is wrapped
  (``BiasAfterAdam``);
* ``long_seq_epochs.run`` takes neither its operations function nor its
  check class as an argument, but reads ``flops_hybrid`` and
  ``LoopReferenceCheck`` by module name: this kind puts its own under
  those names for the length of the run (``swapped``; a ``benchmark``
  change that lets ``run`` take them removes the swap);
* what the bias rule read over the whole run is kept beside it:
  ``geometry.moe_run_route_load_max`` / ``_mean`` (the positions that
  chose the busiest (routed layer, expert of all of them) over a pass,
  and the mean, from ``tower.moe.route_load_*``) and
  ``geometry.moe_bias_range`` (``tower.moe.bias_range``: max - min of
  the bias over the routed layers, the last pass's).  A program without
  those counters leaves them out.

Parameters as ``long_seq_epochs``'s.
"""

from __future__ import annotations

import contextlib
import types

from benchmark.generators import long_seq_epochs
from benchmark.generators.seq_epochs import LoopReferenceCheck
from benchmark.harness import flops_afmoe
from benchmark.harness.record import Measured

OPS = types.SimpleNamespace(hybrid_sizes=flops_afmoe.afmoe_sizes,
                            hybrid_step=flops_afmoe.afmoe_step)


class BiasAfterAdam:
    """The reference's module, its ``adam_unstacked`` followed by its
    ``balance_bias`` over the counts of the last forward that made
    gradients."""

    def __init__(self, ref, cfg: dict):
        self._ref, self._cfg, self._route = ref, cfg, None

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def batch_loss(self, *args, **kwargs):
        out = self._ref.batch_loss(*args, **kwargs)
        if kwargs.get("with_grads"):
            self._route = out["route"]
        return out

    def adam_unstacked(self, *args):
        return self._ref.balance_bias(self._ref.adam_unstacked(*args),
                                      self._route, self._cfg)


class BalancedReferenceCheck(LoopReferenceCheck):
    def __init__(self, cell, cfg: dict, program):
        super().__init__(cell, cfg, program)
        self.ref = BiasAfterAdam(self.ref, cfg)


@contextlib.contextmanager
def swapped(module, **names):
    """``module``'s attributes ``names`` replaced while the block runs."""
    kept = {k: getattr(module, k) for k in names}
    for k, v in names.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in kept.items():
            setattr(module, k, v)


def run(ctx) -> Measured:
    from paddlebox_tpu.utils.monitor import stat_snapshot
    before = stat_snapshot("tower.moe.route_load_")
    with swapped(long_seq_epochs, flops_hybrid=OPS,
                 LoopReferenceCheck=BalancedReferenceCheck):
        measured = long_seq_epochs.run(ctx)
    now = stat_snapshot("tower.moe.")
    for k in ("max", "mean"):
        name = "tower.moe.route_load_" + k
        if name in now:
            measured.geometry["moe_run_route_load_" + k] = \
                now[name] - before.get(name, 0.0)
    if "tower.moe.bias_range" in now:
        measured.geometry["moe_bias_range"] = now["tower.moe.bias_range"]
    return measured
