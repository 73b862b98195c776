#!/usr/bin/env python3
"""Proof that the DeepFM pass loop starts and trains on the TPU.

    python chip_smoke.py              # one chip, full DeepFM geometry
    python chip_smoke.py --chips 4    # same loop, dp=4 mesh -> mxu_sharded
    python chip_smoke.py --tiny       # same code at a CPU size (tier-1)

One process (it holds the chip) drives the path a user drives: slot-format
text files written from a seed, ``fleet.init`` -> ``BoxPSDataset`` ->
``fleet.train_passes`` (serial loop: load_into_memory / begin_pass /
build_pass_feed / train_pass / end_pass) for two passes of a few batches,
once on the lowering ``auto`` resolves to and once on ``reference``.  Each
stage must resolve to the lowering it names, take every batch with a
finite loss, report an AUC in [0, 1], and leave its rows written back to
the host table; the stages train identical data from identical seeds, so
their per-step losses must also agree with the plain-XLA ``reference``
stage.  On a TPU the ``mxu`` / ``mxu_sharded`` step must carry both Mosaic
kernels.

Fails (non-zero, no result line) when JAX finds no TPU, when the native
library does not build and load, or when any stage check fails: nothing
is caught and carried past.  ``--tiny`` relaxes exactly two things — the
platform check and the Mosaic assertion (Pallas runs interpreted off-TPU)
— and says so.  The numbers printed are set-up facts (seconds to compile,
bytes resident), not benchmark metrics.

Last two stdout lines on success: the set-up facts, then the result with
exactly these keys (the device as JAX reports it):
    [chip_smoke] summary: {"tiny": ..., "stages": {...}, ..., "claim": null}
    {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
"""

from __future__ import annotations

import argparse
import collections
import importlib.metadata
import json
import os
import sys
import tempfile
import time

import numpy as np

# the one model the repo benchmarks (bench.py "full" geometry), depth cut
# to a few batches a pass; the key space is covered once a pass so the
# working set is the full 2M rows
FULL = dict(batch=16384, batches=4, n_keys=2_000_000, n_slots=26, cap=3,
            dense_dim=13, mf_dim=8, hidden=(400, 400, 400))
TINY = dict(batch=128, batches=2, n_keys=3000, n_slots=6, cap=3,
            dense_dim=13, mf_dim=8, hidden=(32, 32))
N_PASSES = 2
# mxu sums through a hi/lo bf16 split (~1e-5 relative, mxu_path.py) and the
# paths order their f32 reductions differently; a few optimizer steps keep
# the per-step loss well inside this
LOSS_RTOL = 5e-3


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond, msg) -> None:
    """A gate of the smoke (not `assert`: -O must not remove it)."""
    if not cond:
        raise AssertionError(msg)


class CompileLog:
    """What JAX itself says it compiled: every XLA compile request (a
    persistent-cache hit is still a request) with its seconds, and the
    cache's hits and misses, heard by this script's own listeners.  The
    program counts the same events (`jit.compile_s`, registered in
    `fleet.init`); the smoke reports both."""
    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.events = collections.Counter()
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **kw):
        self.events[event] += 1

    def _on_duration(self, event, duration, **kw):
        if event == self.BACKEND_COMPILE:
            self.events[event] += 1
            self.compile_s += duration

    def mark(self):
        return (self.events[self.BACKEND_COMPILE], self.compile_s)

    def since(self, mark) -> dict:
        return {"xla_compiles": self.events[self.BACKEND_COMPILE] - mark[0],
                "xla_compile_s": round(self.compile_s - mark[1], 2)}


def write_pass_file(path: str, seed: int, geo: dict) -> int:
    """One pass of slot-format text: ``1 <label> <D> <dense...>`` then
    ``<n> <key...>`` per sparse slot.  The first n_keys occurrences are a
    permutation of the key space, so the pass touches every key."""
    rng = np.random.default_rng(seed)
    n = geo["batch"] * geo["batches"]
    s, d = geo["n_slots"], geo["dense_dim"]
    lens = rng.integers(1, geo["cap"] + 1, size=(n, s))
    total = int(lens.sum())
    keys = np.concatenate([
        rng.permutation(geo["n_keys"])[:total] + 1,
        rng.integers(1, geo["n_keys"] + 1,
                     size=max(0, total - geo["n_keys"]))])
    rng.shuffle(keys)
    dense = rng.normal(0, 1, size=(n, d))
    labels = (rng.random(n) < 1 / (1 + np.exp(-dense[:, 0]))).astype(int)
    ends = np.cumsum(lens.sum(axis=1))
    chunk = 4096
    with open(path, "w") as f:
        for r0 in range(0, n, chunk):
            r1 = min(r0 + chunk, n)
            k0 = int(ends[r0 - 1]) if r0 else 0
            ks = list(map(str, keys[k0:int(ends[r1 - 1])].tolist()))
            ds = ["%.4f" % x for x in dense[r0:r1].ravel().tolist()]
            ls = lens[r0:r1].tolist()
            lines, pos = [], 0
            for i, row in enumerate(ls):
                parts = ["1", str(labels[r0 + i]), str(d)]
                parts += ds[i * d:(i + 1) * d]
                for cnt in row:
                    parts.append(str(cnt))
                    parts += ks[pos:pos + cnt]
                    pos += cnt
                lines.append(" ".join(parts))
            f.write("\n".join(lines) + "\n")
    return total


def feed_config(geo: dict):
    from paddlebox_tpu.config import DataFeedConfig, SlotConfig
    return DataFeedConfig(slots=tuple(
        [SlotConfig("label", dtype="float", is_dense=True, dim=1),
         SlotConfig("dense0", dtype="float", is_dense=True,
                    dim=geo["dense_dim"])]
        + [SlotConfig(f"s{i}", slot_id=100 + i, capacity=geo["cap"])
           for i in range(geo["n_slots"])]), batch_size=geo["batch"])


def make_trainer_class():
    """SparseTrainer that also writes down what the smoke reports: it
    observes build_pass_feed / train_pass, it does not change them."""
    import jax
    from paddlebox_tpu.ops import sorted_spmm
    from paddlebox_tpu.trainer.trainer import SparseTrainer
    from paddlebox_tpu.utils.monitor import stat_snapshot

    def compile_count() -> int:
        return int(stat_snapshot("jit.compile_s").get(
            "jit.compile_s.count", 0))

    class ObservedTrainer(SparseTrainer):
        def __init__(self, *a, expect_mosaic: bool,
                     compile_log: CompileLog, **kw):
            super().__init__(*a, **kw)
            self.expect_mosaic = expect_mosaic
            self.compile_log = compile_log
            self.passes = []          # one record per train_pass

        def build_pass_feed(self, dataset, keep_host: bool = False):
            feed = super().build_pass_feed(dataset, keep_host)
            rec = {"path": self._resolve_path(),
                   "keys": int(self.engine.num_keys),
                   "ws_rows": int(self.engine.ws["show"].shape[0]),
                   "feed_mb": round(feed.device_bytes() / 1e6, 1)}
            if self.topology is not None:
                n = self.topology.world_size
                for name, tree in (("ws", self.engine.ws),
                                   ("feed.data", feed.data),
                                   ("feed.plans", feed.plans or {})):
                    for k, leaf in tree.items():
                        got = len(leaf.sharding.device_set)
                        require(got == n,
                                f"{name}[{k!r}] lives on {got} device(s), "
                                f"expected {n}: {leaf.sharding}")
                jax.block_until_ready((self.engine.ws, feed.data))
                used = [(d.memory_stats() or {}).get("bytes_in_use")
                        for d in self.topology.mesh.devices.flat]
                rec["bytes_in_use_per_device"] = used
                require(None in used or max(used) <= 2 * min(used),
                        "per-device bytes_in_use after the feed build "
                        f"differ by more than 2x: {used}")
            self.passes.append(rec)
            self._last_keys = self.engine.mapper.sorted_keys
            return feed

        def train_pass(self, feed, **kw):
            rec = self.passes[-1]
            compiles0, mark = compile_count(), self.compile_log.mark()
            t0 = time.perf_counter()

            def first_step(n):
                # FLAGS_check_nan_inf reads each loss back, so the step
                # has finished on the device when this fires
                if n == 1:
                    rec["first_step_s"] = round(time.perf_counter() - t0, 2)

            stats = super().train_pass(feed, progress=first_step, **kw)
            rec["step_compile_count"] = compile_count() - compiles0
            rec.update(self.compile_log.since(mark))
            if len(self.passes) == 1 and rec["path"].startswith("mxu"):
                rec["mosaic_kernels"] = self._mosaic_kernels()
            return stats

        def _mosaic_kernels(self):
            """Names of the Mosaic custom calls in the step this pass ran
            (a kernel that gave way to the XLA gather/scatter or to
            interpret mode leaves none)."""
            text = self.step_lowered().as_text()
            found = [k for k in (sorted_spmm.GATHER_KERNEL,
                                 sorted_spmm.SCATTER_KERNEL)
                     if "@tpu_custom_call" in text
                     and f'kernel_name = "{k}"' in text]
            require(len(found) == 2 or not self.expect_mosaic,
                    "an mxu step must carry both Mosaic kernels "
                    f"(tpu_custom_call); found {found}")
            return found

    return ObservedTrainer


def run_stage(name: str, sparse_path: str, expect_path: str, geo: dict,
              files, topology, on_tpu: bool,
              compile_log: CompileLog) -> dict:
    import jax
    from paddlebox_tpu import fleet
    from paddlebox_tpu.config import (DistributedStrategy,
                                      EmbeddingTableConfig, SparseSGDConfig)
    from paddlebox_tpu.models.deepfm import DeepFM
    from paddlebox_tpu.utils.monitor import stat_snapshot

    say(f"stage {name}: start (sparse_path={sparse_path!r})")
    t_stage = time.perf_counter()
    cfg = feed_config(geo)
    f = fleet.init(DistributedStrategy(table=EmbeddingTableConfig(
        embedding_dim=geo["mf_dim"], shard_num=8,
        sgd=SparseSGDConfig(mf_create_thresholds=0.0))), topology=topology)
    engine = f.init_engine(seed=1)
    dataset = fleet.DatasetFactory().create_dataset(
        "BoxPSDataset", feed_config=cfg, read_threads=2)
    model = DeepFM(num_slots=geo["n_slots"], emb_width=3 + geo["mf_dim"],
                   dense_dim=geo["dense_dim"], hidden=geo["hidden"])
    trainer = make_trainer_class()(
        engine, model, cfg, batch_size=geo["batch"],
        sparse_path=sparse_path, topology=topology, seed=2,
        expect_mosaic=on_tpu, compile_log=compile_log)
    tune0 = stat_snapshot("ops.crossing.autotune_s")
    metrics = fleet.train_passes(trainer, dataset, [[p] for p in files],
                                 date="20260926", prefetch=False)

    require(len(metrics) == N_PASSES == len(trainer.passes), metrics)
    for m, rec in zip(metrics, trainer.passes):
        require(rec["path"] == expect_path,
                f"stage {name} resolved to {rec['path']!r}, "
                f"not {expect_path!r}")
        require(m["batches"] == geo["batches"] == len(m["losses"]), m)
        require(all(np.isfinite(m["losses"])), m["losses"])
        require(0.0 <= m["auc"] <= 1.0, m["auc"])
        rec.update(batches=m["batches"], auc=round(m["auc"], 4),
                   losses=[round(x, 6) for x in m["losses"]])
    # write-back: end_pass put the last pass's rows in the host table,
    # each shown at least once
    last = trainer.passes[-1]
    require(engine.table.size() >= last["keys"],
            (engine.table.size(), last["keys"]))
    back = engine.table.bulk_pull(trainer._last_keys)
    require(len(back["show"]) == last["keys"]
            and (back["show"] >= 1.0).all(),
            f"write-back missing: min show {back['show'].min()}")
    tune = stat_snapshot("ops.crossing.autotune_s")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    out = {"resolved_path": expect_path,
           "crossing": (list(trainer._mxu_crossing)
                        if expect_path == "mxu" else None),
           "passes": trainer.passes,
           "compiles_in_pass_2": {
               k: trainer.passes[1][k]
               for k in ("step_compile_count", "xla_compiles",
                         "xla_compile_s")},
           "crossing_autotune_s": round(
               tune.get("ops.crossing.autotune_s.sum", 0.0)
               - tune0.get("ops.crossing.autotune_s.sum", 0.0), 2),
           "table_rows": int(engine.table.size()),
           "peak_bytes_in_use": peak,
           "stage_s": round(time.perf_counter() - t_stage, 1)}
    say(f"stage {name}: {json.dumps(out)}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU-size self-test of the same code (tier-1)")
    ap.add_argument("--chips", type=int, default=1,
                    help="devices in the dp mesh (4 -> mxu_sharded)")
    args = ap.parse_args()
    geo = TINY if args.tiny else FULL
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if args.tiny and args.chips > 1 \
            and "xla_force_host_platform_device_count" not in xla_flags:
        # virtual devices exist on the CPU platform only; no-op on a TPU
        os.environ["XLA_FLAGS"] = (
            f"{xla_flags} --xla_force_host_platform_device_count="
            f"{args.chips}").strip()

    t_all = time.perf_counter()
    from paddlebox_tpu.utils import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    compile_log = CompileLog()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    versions = {"jax": jax.__version__}
    for pkg in ("jaxlib", "libtpu"):
        try:
            versions[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            versions[pkg] = None
    if not on_tpu and not args.tiny:
        # stdout stays empty: a refusal prints no result
        print(f"chip_smoke: JAX found platform {device['platform']!r} "
              f"({device['kind']} x{device['count']}, versions "
              f"{versions}), not a TPU — this check runs on the chip only "
              "(--tiny is the CPU-size self-test)", file=sys.stderr)
        return 2
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']} versions={versions} "
        f"compile_cache={cache_dir}")
    if not on_tpu:
        say("--tiny off-TPU: platform check and Mosaic assertion RELAXED "
            "(Pallas kernels run interpreted)")
    if args.chips > len(devices):
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{len(devices)} device(s); the mesh is not shrunk",
              file=sys.stderr)
        return 2

    from paddlebox_tpu.native import (build, dump_writer, hash_map,
                                      slot_parser)
    native_ok = (build.ensure_built() and slot_parser.available()
                 and hash_map.available() and dump_writer.available())
    say(f"native library: {build.status()}")
    if not native_ok:
        print("chip_smoke: the native library did not build and load",
              file=sys.stderr)
        return 2

    from paddlebox_tpu import flags
    flags.set_flags({"check_nan_inf": True})
    topology = None
    stages = [("mxu", "auto", "mxu"),
              ("reference", "reference", "reference")]
    if args.chips > 1:
        from paddlebox_tpu.config import MeshConfig
        from paddlebox_tpu.parallel.topology import HybridTopology
        topology = HybridTopology(MeshConfig(dp=args.chips),
                                  devices[:args.chips])
        # the reference for the sharded exchange is the same data on ONE
        # device through the plain-XLA step, which shares no kernel with it
        stages = [("mxu_sharded", "auto", "mxu_sharded"),
                  ("reference", "reference", "reference")]

    results = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        t0 = time.perf_counter()
        files = []
        for p in range(N_PASSES):
            files.append(os.path.join(tmp, f"pass-{p}.txt"))
            occ = write_pass_file(files[-1], seed=100 + p, geo=geo)
        say(f"data: {N_PASSES} passes x {geo['batches']} batches x "
            f"B={geo['batch']}, {occ} key occurrences in the last, "
            f"{os.path.getsize(files[-1]) / 1e6:.0f} MB each, written in "
            f"{time.perf_counter() - t0:.1f}s")
        for name, sparse_path, expect in stages:
            results[name] = run_stage(
                name, sparse_path, expect, geo, files,
                topology if expect == "mxu_sharded" else None, on_tpu,
                compile_log)

    ref = results["reference"]
    for name, res in results.items():
        for p in range(N_PASSES):
            a, b = res["passes"][p]["losses"], ref["passes"][p]["losses"]
            require(np.allclose(a, b, rtol=LOSS_RTOL, atol=0),
                    f"stage {name} pass {p} losses {a} != reference {b}")
    say(f"per-step losses of {sorted(results)} agree (rtol {LOSS_RTOL})")

    summary = {
        "tiny": args.tiny, "chips": args.chips, "versions": versions,
        "stages": {k: {f: v[f] for f in
                       ("resolved_path", "crossing", "compiles_in_pass_2",
                        "crossing_autotune_s", "peak_bytes_in_use",
                        "stage_s")}
                   | {"first_step_s": v["passes"][0]["first_step_s"]}
                   for k, v in results.items()},
        "compile_cache": {
            "dir": cache_dir,
            "hits": compile_log.events[CompileLog.HIT],
            "misses": compile_log.events[CompileLog.MISS],
            "xla_compile_s": round(compile_log.compile_s, 1)},
        "native": build.status(),
        "total_s": round(time.perf_counter() - t_all, 1),
        "claim": None}
    say(f"summary: {json.dumps(summary)}")
    # the result line carries exactly these keys and is the last on stdout
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
