#!/usr/bin/env python3
"""Compile a cell's train step for a described TPU, without the chip.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_cell.py <cell> ...

The third rehearsal of the ``on-chip-measurement`` guide (section 2.3) for
this benchmark: the cell's shapes come from its configuration file, the
step is the program's own (``SparseTrainer._build_packed_step``), and the
TPU compiler is handed a described ``v5e:2x2`` instead of a device.  It
raises what the chip's compiler would raise (a Mosaic refusal, a program
that does not fit), and prints ``memory_analysis()`` for the step and for
the pass-plan builder: bytes a device, one program at a time, not what
else the process keeps there.  Nothing runs, so nothing here is a time.

The program picks interpret-mode Pallas when ``jax.default_backend()`` is
the CPU, which it is here; this script tells it "tpu" for the length of
the build (the guide: steer such code from the rehearsal, not through an
option of the program).
"""

from __future__ import annotations

import json
import os
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from jax.experimental import topologies                       # noqa: E402
from jax.sharding import SingleDeviceSharding                 # noqa: E402

from benchmark.harness import spec                            # noqa: E402
from benchmark.harness.program import feed_config             # noqa: E402


def analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "alias_size_in_bytes", "temp_size_in_bytes",
             "generated_code_size_in_bytes")}


def with_sharding(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def compile_cell(name: str, table_rows: int, real_per_example: float
                 ) -> dict:
    from paddlebox_tpu import flags
    from paddlebox_tpu.config import (EmbeddingTableConfig, MeshConfig,
                                      SparseSGDConfig)
    from paddlebox_tpu.data import pass_feed as pf
    from paddlebox_tpu.data.pass_feed import PackedPassFeed
    from paddlebox_tpu.ops import sorted_spmm as sp
    from paddlebox_tpu.parallel.topology import HybridTopology
    from paddlebox_tpu.ps import mxu_path
    from paddlebox_tpu.ps.embedding import size_bucket
    from paddlebox_tpu.ps.pass_manager import BoxPSEngine
    from paddlebox_tpu.trainer import trainer as trainer_mod

    cell = spec.Cell(name)
    cfg = cell.config
    flags.set_flags(cfg.get("flags", {}))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell.chips]
    batch = int(cfg["batch_per_chip"]) * cell.chips
    n = int(cell.param("depth"))
    fc = feed_config(cfg, batch)
    topology = None
    if cell.chips > 1:
        topology = HybridTopology(MeshConfig(dp=cell.chips), devices)
        repl = topology.replicated()
        table_sh = topology.table_sharding()
    else:
        repl = table_sh = SingleDeviceSharding(devices[0])
    engine = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=cfg["table"]["embedx_dim"],
        sgd=SparseSGDConfig(**cfg["table"]["sgd"])), topology=topology)
    model = cell.module("models", cell.config_name).build(cfg)
    trainer = trainer_mod.SparseTrainer(engine, model, fc, batch_size=batch,
                                        topology=topology)
    rows = size_bucket(table_rows + 1)
    d = cfg["table"]["embedx_dim"]
    f32, i32 = jnp.float32, jnp.int32
    ws = {f: jax.ShapeDtypeStruct((rows,), i32 if f in ("slot", "mf_size")
                                  else f32, sharding=table_sh)
          for f in ("show", "click", "delta_score", "slot", "embed_w",
                    "embed_g2sum", "mf_size", "mf_g2sum")}
    ws["mf"] = jax.ShapeDtypeStruct((rows, d), f32, sharding=table_sh)
    engine.ws = ws
    s = len(cfg["fields"]["vocab"])
    cap = max(c.capacity for c in fc.sparse_slots)
    shapes = {"indices": ((n, s, cap, batch), i32),
              "lengths": ((n, s, batch), i32),
              "dense": ((n, batch, cfg["fields"]["dense_dim"]), f32),
              "labels": ((n, batch), f32), "valid": ((n, batch), jnp.bool_)}

    class Arrays:                # what pass_shardings reads off the pass
        labels = np.zeros((1,))
        def extra_planes(self): return {}

    data_sh = trainer.pass_shardings(Arrays()) or {k: repl for k in shapes}
    data = {k: jax.ShapeDtypeStruct(shp, dt, sharding=data_sh[k])
            for k, (shp, dt) in shapes.items()}
    out = {"cell": name, "chips": cell.chips, "table_rows": rows,
           "steps_per_pass": n, "batch": batch, "capacity": cap}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        path = trainer._resolve_path()
        out["lowering"] = path
        if path == "mxu":
            dims = mxu_path.make_dims(s * cap * batch, rows)
            eff = sp.trimmed_dims(dims, int(real_per_example * batch))
            out["plan_positions"] = {"padded": dims.p_pad,
                                     "kept": eff.p_pad}

            def build_plans(idx, lab):
                plans = pf._build_plans(idx, dims, eff)
                return {**plans, **pf._build_static_planes(
                    plans, lab, jnp.asarray(trainer.slot_ids), dims, eff,
                    (s, cap, batch))}

            build = jax.jit(build_plans)
            built = build.lower(data["indices"], data["labels"]).compile()
        else:
            batch_axes, tbl_axes, _, rows_loc, _ = trainer._sharded_layout()
            build = trainer_mod._sharded_plan_builder(
                topology.mesh, batch_axes, tbl_axes, rows_loc)
            built = build.lower(data["indices"]).compile()
            names = ("rows2d", "perm", "inv_perm", "ch", "tl", "fg", "fs",
                     "first_occ")
        out["plan_builder"] = analysis(built)
        shapes_out = jax.eval_shape(
            build, *([data["indices"], data["labels"]] if path == "mxu"
                     else [data["indices"]]))
        shard_out = built.output_shardings
        if path == "mxu":
            plans = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                             sharding=shard_out[k])
                     for k, v in shapes_out.items()}
        else:
            plans = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh)
                     for k, v, sh in zip(names, shapes_out, shard_out)}
        out["plan_bytes_per_device"] = int(sum(
            np.prod(v.shape) * v.dtype.itemsize for v in plans.values())
            // (cell.chips if path != "mxu" else 1))
        out["data_bytes_per_device"] = int(sum(
            np.prod(v.shape) * v.dtype.itemsize for v in data.values())
            // cell.chips)
        feed = PackedPassFeed(data=data, n_batches=n, batch_size=batch,
                              num_real=n * batch, plans=plans)
        trainer._build_packed_step(feed)
        state = with_sharding((trainer.params, trainer.opt_state,
                               trainer.auc_state), repl)
        lowered = trainer._packed_step_fn.lower(
            ws, *state, jax.ShapeDtypeStruct((), i32, sharding=repl),
            data, plans)
        compiled = lowered.compile()
    text = compiled.as_text()
    out["step"] = analysis(compiled)
    out["mosaic_kernels"] = [k for k in (sp.GATHER_KERNEL, sp.SCATTER_KERNEL)
                             if k in text and "tpu_custom_call" in text]
    out["collectives"] = {k: text.count(f" {k}(") + text.count(f" {k}-start(")
                          for k in ("all-gather", "reduce-scatter",
                                    "all-reduce", "all-to-all")}
    return out


if __name__ == "__main__":
    # <cell> <unique rows of a pass> <key occurrences an example>
    args = sys.argv[1:]
    for i in range(0, len(args), 3):
        print(json.dumps(compile_cell(args[i], int(args[i + 1]),
                                      float(args[i + 2]))), flush=True)
