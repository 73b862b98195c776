"""A tiny looped language model through the fleet API, for the tests of
``models/looplm.py`` and of what the trainer hands it (not a test file)."""

import numpy as np

from paddlebox_tpu import fleet
from paddlebox_tpu.config import (DataFeedConfig, DistributedStrategy,
                                  EmbeddingTableConfig, SlotConfig,
                                  SparseSGDConfig)
from paddlebox_tpu.models.looplm import LoopLM
from paddlebox_tpu.trainer.trainer import SparseTrainer

VOCAB, HIDDEN, CAP, BATCH = 64, 32, 8, 2
SGD = dict(optimizer="adagrad", nonclk_coeff=0.1, clk_coeff=1.0,
           learning_rate=0.05, feature_learning_rate=0.05, initial_g2sum=3.0,
           min_bound=-10.0, max_bound=10.0, mf_create_thresholds=0.0,
           mf_learning_rate=0.05, mf_initial_g2sum=3.0,
           mf_initial_range=0.04, mf_min_bound=-10.0, mf_max_bound=10.0)


def config(layers=2, steps=4, vocab=VOCAB):
    """The keys ``benchmark/reference/ouro_2p6b.py`` reads of a
    configuration file, at test sizes."""
    return {"hidden_size": HIDDEN, "num_attention_heads": 2, "head_dim": 16,
            "intermediate_size": 48, "num_hidden_layers": layers,
            "total_ut_steps": steps, "vocab_size": vocab,
            "rope_theta": 1e6, "rms_norm_eps": 1e-6,
            "loss": {"beta": 0.1, "init_std": 0.02, "key_base": 1,
                     "negative_seed": 11},
            "table": {"embedx_dim": HIDDEN, "sgd": dict(SGD)}}


def model_of(cfg):
    return LoopLM(hidden=cfg["hidden_size"],
                  heads=cfg["num_attention_heads"],
                  head_dim=cfg["head_dim"], ffn=cfg["intermediate_size"],
                  layers=cfg["num_hidden_layers"],
                  ut_steps=cfg["total_ut_steps"], vocab=cfg["vocab_size"],
                  beta=cfg["loss"]["beta"],
                  neg_seed=cfg["loss"]["negative_seed"])


def feed_config():
    return DataFeedConfig(slots=(
        SlotConfig("label", dtype="float", is_dense=True, dim=1),
        SlotConfig("dense0", dtype="float", is_dense=True, dim=1),
        SlotConfig("s0", slot_id=100, capacity=CAP)), batch_size=BATCH)


def write_sequences(path, rng, n, vocab=VOCAB):
    """``n`` lines of 2..CAP tokens; a token now and then repeats inside
    its sequence (keys are 1 + token id)."""
    with open(path, "w") as f:
        for _ in range(n):
            ln = int(rng.integers(2, CAP + 1))
            toks = rng.integers(0, vocab, ln)
            toks[-1] = toks[0]
            f.write(f"1 {rng.integers(0, 2)} 1 {rng.random():.4f} {ln} "
                    + " ".join(str(1 + t) for t in toks) + "\n")


class Snapshots(SparseTrainer):
    """SparseTrainer that copies what a pass starts from to the host
    before it trains: rows, parameters, Adam state and the feed."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.snaps = []

    def train_pass(self, feed, **kw):
        adam = self.opt_state[0]
        self.snaps.append({
            "rows": {k: np.asarray(v) for k, v in self.engine.ws.items()},
            "params": jax_copy(self.params), "m": jax_copy(adam.mu),
            "v": jax_copy(adam.nu), "t": int(adam.count),
            "keys": np.asarray(self.engine.mapper.sorted_keys),
            "batches": {k: np.asarray(v) for k, v in feed.data.items()}})
        return super().train_pass(feed, **kw)


def jax_copy(tree):
    import jax
    return jax.tree.map(lambda a: np.array(a), tree)


def fleet_run(tmp_path, cfg, passes=2, lines=4, seed=0, trainer_cls=Snapshots,
              model=None):
    """``passes`` tiny passes through fleet.init -> BoxPSDataset ->
    SparseTrainer -> fleet.train_passes; returns (trainer, metrics,
    engine).  ``model``: another row model over the same feed (its rows
    as wide as ``cfg``'s table)."""
    hidden = cfg["table"]["embedx_dim"]
    files = []
    for p in range(passes):
        path = str(tmp_path / f"seq-{p}.txt")
        write_sequences(path, np.random.default_rng(100 * seed + p), lines,
                        cfg["vocab_size"])
        files.append([path])
    fl = fleet.init(DistributedStrategy(table=EmbeddingTableConfig(
        embedding_dim=hidden, shard_num=4, sgd=SparseSGDConfig(**SGD))))
    engine = fl.init_engine(seed=seed)
    ds = fleet.DatasetFactory().create_dataset("BoxPSDataset",
                                               feed_config=feed_config())
    trainer = trainer_cls(engine, model or model_of(cfg), feed_config(),
                          batch_size=BATCH, seed=seed)
    metrics = fleet.train_passes(trainer, ds, files, date="20260930",
                                 prefetch=False)
    return trainer, metrics, engine
