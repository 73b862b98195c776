"""Two-process integration worker (spawned by test_two_process.py via
paddlebox_tpu.launch).  ≙ the trainer half of test_dist_fleet_base.py:186:
read a disjoint file shard, global-shuffle it across workers over TCP,
train passes against the shared PS service with delta write-back, dump the
loss/auc trajectory as JSON.

Env: PBOX_RANK, PBOX_WORLD_SIZE (launcher-set), DW_PS_ADDR (host:port),
DW_SHUFFLE_PORTS (comma), DW_DATA (file), DW_OUT (json path),
DW_BATCH, DW_PASSES.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"   # a test worker never takes the chip

import numpy as np  # noqa: E402

from paddlebox_tpu.config import (DataFeedConfig, EmbeddingTableConfig,  # noqa: E402
                                  SlotConfig, SparseSGDConfig)
from paddlebox_tpu.data.dataset import SlotDataset  # noqa: E402
from paddlebox_tpu.data.shuffle_transport import TcpShuffleTransport  # noqa: E402
from paddlebox_tpu.models.ctr_dnn import CtrDnn  # noqa: E402
from paddlebox_tpu.ps.pass_manager import BoxPSEngine  # noqa: E402
from paddlebox_tpu.ps.service import PSClient, RemoteTableAdapter  # noqa: E402
from paddlebox_tpu.trainer.trainer import SparseTrainer  # noqa: E402

MF_DIM = 4
N_SLOTS = 3


def feed_config():
    return DataFeedConfig(slots=(
        SlotConfig("label", dtype="float", is_dense=True, dim=1),
        SlotConfig("dense0", dtype="float", is_dense=True, dim=2),
        SlotConfig("slot_a", slot_id=101, capacity=2),
        SlotConfig("slot_b", slot_id=102, capacity=2),
        SlotConfig("slot_c", slot_id=103, capacity=1),
    ))


def main():
    rank = int(os.environ["PBOX_RANK"])
    world = int(os.environ["PBOX_WORLD_SIZE"])
    ps_addr = os.environ["DW_PS_ADDR"].rsplit(":", 1)
    ports = [int(p) for p in os.environ["DW_SHUFFLE_PORTS"].split(",")]
    batch = int(os.environ["DW_BATCH"])
    passes = int(os.environ["DW_PASSES"])

    client = PSClient((ps_addr[0], int(ps_addr[1])))
    cfg = feed_config()
    transport = TcpShuffleTransport(
        rank, [("127.0.0.1", p) for p in ports]) if world > 1 else None
    ds = SlotDataset(cfg, read_threads=1, transport=transport)
    ds.set_filelist([os.environ["DW_DATA"]])

    engine = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=MF_DIM, shard_num=4,
        sgd=SparseSGDConfig(mf_create_thresholds=0.0)), seed=1)
    engine.table = RemoteTableAdapter(client, delta_mode=world > 1)

    model = CtrDnn(num_slots=N_SLOTS, emb_width=3 + MF_DIM, dense_dim=2,
                   hidden=(64, 32))
    trainer = SparseTrainer(engine, model, cfg, batch_size=batch,
                            auc_table_size=10_000, seed=2)

    # shard the records: worker w keeps rows [w::world] of its file read
    # (each worker reads the same file here; a real job reads disjoint
    # files), then the global shuffle redistributes them randomly
    results = []
    for p in range(passes):
        engine.begin_feed_pass()
        ds.load_into_memory()
        if world > 1:
            from paddlebox_tpu.data.slot_record import SlotRecordBlock
            full = SlotRecordBlock.concat(ds.get_blocks())
            ds._blocks = [full.select(np.arange(rank, full.n, world))]
            ds.global_shuffle()
        else:
            ds.local_shuffle()
        for blk in ds.get_blocks():   # key tap over the post-shuffle shard
            engine.add_keys(blk.all_keys())
        engine.end_feed_pass()
        client.barrier(world)      # all shards registered before training
        engine.begin_pass()
        trainer.reset_metrics()
        out = trainer.train_pass(ds)
        engine.end_pass()
        client.barrier(world)      # pass deltas all merged before next pull
        # EXACT global metrics: allreduce the bucket tables through the PS
        # (≙ fleet.metrics.auc) — every rank must report the same value
        if world > 1:
            from paddlebox_tpu.metrics.auc import (AucCalculator,
                                                   allreduce_auc_state)
            g = allreduce_auc_state(trainer.auc_state, client, world,
                                    key=f"auc-{p}")
            calc = AucCalculator(10_000)
            calc.merge_device_state(g)
            gauc = calc.compute()["auc"]
        else:
            gauc = out["auc"]
        results.append({"loss": out["loss"], "auc": out["auc"],
                        "gauc": gauc, "batches": out["batches"]})
        ds.release_memory()

    with open(os.environ["DW_OUT"] + f".rank{rank}", "w") as f:
        json.dump(results, f)


if __name__ == "__main__":
    main()
