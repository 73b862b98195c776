"""The system under test, built from a configuration file through the
program's public entry points, and observed.

``fleet.init`` -> ``init_engine`` -> ``DatasetFactory().create_dataset``
-> ``SparseTrainer`` with ``sparse_path`` left at the program's default
(so one chip trains the ``mxu`` lowering and four the ``mxu_sharded``
one).  The only settings made are the ones the configuration file
states: table and optimizer sizes, and its ``flags`` pins.

Observation follows ``chip_smoke.ObservedTrainer``: a subclass and
wrapped bound methods that time the calls between layers and pass
arguments and results through untouched.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from benchmark.harness.spans import SpanLog


def feed_config(cfg: dict, batch_size: int):
    from paddlebox_tpu.config import DataFeedConfig, SlotConfig
    fields = cfg["fields"]
    lengths = cfg.get("lengths") or {}
    multi = set(lengths.get("slots", []))
    slots = [SlotConfig("label", dtype="float", is_dense=True, dim=1),
             SlotConfig("dense0", dtype="float", is_dense=True,
                        dim=fields["dense_dim"])]
    slots += [SlotConfig(f"s{i}", slot_id=100 + i,
                         capacity=int(lengths["max"]) if i in multi else 1)
              for i in range(len(fields["vocab"]))]
    return DataFeedConfig(slots=tuple(slots), batch_size=batch_size)


def make_trainer_class():
    from paddlebox_tpu.trainer.trainer import SparseTrainer

    class ObservedTrainer(SparseTrainer):
        """SparseTrainer with a span around each call the pass loop makes
        into it, and two hooks that run once: ``before_first_pass(feed)``
        before the first ``train_pass`` touches the seeded state, and
        ``after_first_pass(feed)`` when it has trained, its working set
        still on the device."""

        def __init__(self, *a, spans: SpanLog, **kw):
            super().__init__(*a, **kw)
            self.spans = spans
            self.before_first_pass: Optional[Callable] = None
            self.after_first_pass: Optional[Callable] = None

        def pack_pass_host(self, *a, **kw):
            with self.spans.span("pack_pass_host"):
                return super().pack_pass_host(*a, **kw)

        def finish_pass_feed(self, *a, **kw):
            with self.spans.span("finish_pass_feed"):
                return super().finish_pass_feed(*a, **kw)

        def train_pass(self, feed, **kw):
            hook, self.before_first_pass = self.before_first_pass, None
            if hook is not None:
                hook(feed)
            with self.spans.span("train_pass"):
                stats = super().train_pass(feed, **kw)
            hook, self.after_first_pass = self.after_first_pass, None
            if hook is not None:
                hook(feed)
            return stats

    return ObservedTrainer


class Program:
    """The objects a generator drives, and what the metrics read off
    them."""

    def __init__(self, cell, cfg: dict, seed: int, spans: SpanLog,
                 devices):
        from paddlebox_tpu import fleet, flags
        from paddlebox_tpu.config import (DistributedStrategy,
                                          EmbeddingTableConfig,
                                          SparseSGDConfig)
        self.cfg, self.spans, self.chips = cfg, spans, cell.chips
        self.devices = devices[:cell.chips]
        flags.set_flags(cfg.get("flags", {}))
        self.batch_size = int(cfg["batch_per_chip"]) * cell.chips
        self.feed_config = feed_config(cfg, self.batch_size)
        self.topology = None
        if cell.chips > 1:
            from paddlebox_tpu.config import MeshConfig
            from paddlebox_tpu.parallel.topology import HybridTopology
            self.topology = HybridTopology(MeshConfig(dp=cell.chips),
                                           devices[:cell.chips])
        table = cfg["table"]
        self.fleet = fleet.init(DistributedStrategy(
            table=EmbeddingTableConfig(
                embedding_dim=table["embedx_dim"],
                sgd=SparseSGDConfig(**table["sgd"]))),
            topology=self.topology)
        self.engine = self.fleet.init_engine(seed=seed)
        self.dataset = fleet.DatasetFactory().create_dataset(
            "BoxPSDataset", feed_config=self.feed_config)
        model = cell.module("models", cell.config_name).build(cfg)
        self.trainer = make_trainer_class()(
            self.engine, model, self.feed_config,
            batch_size=self.batch_size, topology=self.topology, seed=seed,
            spans=spans)
        # the pass loop's calls into the other layers, by bound method
        eng, ds = self.engine, self.dataset.dataset
        ds.load_into_memory = spans.wrap("load_into_memory",
                                         ds.load_into_memory)
        for name in ("end_feed_pass", "peek_next_mapper", "begin_pass",
                     "end_pass"):
            setattr(eng, name, spans.wrap(name, getattr(eng, name)))
        self.readback = None

    # -- what the step is made of -------------------------------------------
    def lowering(self) -> str:
        return self.trainer._resolve_path()

    def geometry(self, feed) -> dict:
        """Shapes the kernels' operation and byte counts are computed
        from, per device: occurrences the sorted domain keeps, table rows
        a device sweeps, and the two payload widths."""
        n, s, l, b = feed.data["indices"].shape
        rows = int(self.engine.ws["show"].shape[0])
        d = int(self.engine.ws["mf"].shape[1])
        # a device's own plan; mxu_sharded stacks the devices' plans
        chunks, _, chunk = feed.plans["rows2d"].shape[-3:]
        chunks //= self.chips
        return {"steps_per_pass": int(n), "slots": int(s), "capacity": int(l),
                "batch": int(b), "occurrences_padded": int(s * l * b),
                "occurrences_kept": int(chunks * chunk),
                "table_rows": rows, "table_rows_per_device":
                    rows // self.chips,
                "gather_width": 3 + d + 1, "scatter_width": d + 4,
                "feed_bytes": int(feed.device_bytes())}

    def mosaic_kernels(self, feed) -> list:
        """Names of the Mosaic custom calls in the step this feed trains
        (``chip_smoke.ObservedTrainer._mosaic_kernels``)."""
        from paddlebox_tpu.ops import sorted_spmm
        t = self.trainer
        text = t._packed_step_fn.lower(
            self.engine.ws, t.params, t.opt_state, t.auc_state,
            np.int32(0), feed.data, feed.plans or {}).as_text()
        return [k for k in (sorted_spmm.GATHER_KERNEL,
                            sorted_spmm.SCATTER_KERNEL)
                if "tpu_custom_call" in text
                and f'kernel_name = "{k}"' in text]

    # -- the write-back check ------------------------------------------------
    def capture_readback(self, keys) -> None:
        """From now on, before each ``end_pass`` writes back, copy what
        the device holds for the probe ``keys`` that the pass touches (a
        gather of a fixed few rows); ``check_readback`` compares the host
        table with the last copy."""
        eng = self.engine
        inner = eng.end_pass
        keys = np.asarray(keys, np.uint64)

        def end_pass(*a, **kw):
            rows = eng.mapper(keys)
            held = {f: np.asarray(eng.ws[f][rows])
                    for f in ("show", "click", "embed_w", "mf")}
            self.readback = {"keys": keys[rows > 0],
                             "device": {f: v[rows > 0]
                                        for f, v in held.items()}}
            return inner(*a, **kw)

        eng.end_pass = end_pass

    def check_readback(self, expected_show: dict) -> dict:
        """After the last ``end_pass``: the host table's rows for the
        probe keys must be the values the device held, and each ``show``
        the number of times the generator wrote the key into the passes
        trained (its own count, not the program's)."""
        if self.readback is None or not len(self.readback["keys"]):
            return {"ok": False, "why": "no probe key was captured"}
        keys = self.readback["keys"]
        host = self.engine.table.bulk_pull(keys)
        bad = [f for f, dev in self.readback["device"].items()
               if not np.array_equal(np.asarray(host[f], np.float32), dev)]
        want = np.array([expected_show[int(k)] for k in keys], np.float64)
        got = np.asarray(host["show"], np.float64)
        exact = want < 2 ** 24        # float32 counts integers up to here
        wrong = int((got[exact] != want[exact]).sum())
        return {"ok": not bad and bool((got >= 1).all()) and wrong == 0,
                "keys": int(len(keys)), "counted_exactly": int(exact.sum()),
                "fields_differing": bad, "show_mismatches": wrong}
