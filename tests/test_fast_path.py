"""Fast path (tiling-aware) must match the reference path numerically."""

import copy

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddlebox_tpu.config import (DataFeedConfig, EmbeddingTableConfig,
                                  SlotConfig, SparseSGDConfig)
from paddlebox_tpu.data.batch_pack import PackedBatch
from paddlebox_tpu.models.ctr_dnn import CtrDnn
from paddlebox_tpu.ps import embedding, fast_path
from paddlebox_tpu.ps.pass_manager import BoxPSEngine
from paddlebox_tpu.trainer.trainer import SparseTrainer

S, MF, DD, B, L = 3, 4, 2, 16, 3
N_KEYS = 40


def make_cfg():
    slots = [SlotConfig("label", dtype="float", is_dense=True, dim=1),
             SlotConfig("d0", dtype="float", is_dense=True, dim=DD)]
    slots += [SlotConfig(f"s{i}", slot_id=10 + i, capacity=L)
              for i in range(S)]
    return DataFeedConfig(slots=tuple(slots))


def make_engine(thresh=2.0):
    eng = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=MF, shard_num=2,
        sgd=SparseSGDConfig(mf_create_thresholds=thresh)), seed=3)
    eng.begin_feed_pass()
    eng.add_keys(np.arange(1, N_KEYS, dtype=np.uint64))
    eng.end_feed_pass()
    # pre-create mf on some rows so both creation & training paths run
    eng.ws["mf_size"] = eng.ws["mf_size"].at[1:N_KEYS // 2].set(MF)
    eng.ws["show"] = eng.ws["show"].at[1:N_KEYS // 2].set(5.0)
    eng.begin_pass()
    return eng


def make_batch(seed=0):
    rng = np.random.default_rng(seed)
    return PackedBatch(
        indices=rng.integers(1, N_KEYS, (S, B, L)).astype(np.int32),
        lengths=rng.integers(0, L + 1, (S, B)).astype(np.int32),
        dense=rng.normal(0, 1, (B, DD)).astype(np.float32),
        labels=rng.integers(0, 2, (B,)).astype(np.float32),
        valid=np.ones((B,), bool), num_real=B)


def run_one(fast: bool, steps=3):
    cfg = make_cfg()
    eng = make_engine()
    model = CtrDnn(num_slots=S, emb_width=3 + MF, dense_dim=DD,
                   hidden=(16,))
    tr = SparseTrainer(eng, model, cfg, batch_size=B,
                       sparse_path="fast" if fast else "reference",
                       auc_table_size=1000, seed=11)
    tr._build_step()
    ws, params = eng.ws, tr.params
    opt, auc = tr.opt_state, tr.auc_state
    losses = []
    for i in range(steps):
        b = make_batch(i)
        dev = tr._put_batch(b)
        ws, params, opt, auc, loss, preds = tr._step_fn(
            ws, params, opt, auc, *dev)
        losses.append(float(loss))
    return ws, params, losses


def test_fast_matches_reference():
    ws_f, p_f, loss_f = run_one(True)
    ws_r, p_r, loss_r = run_one(False)
    np.testing.assert_allclose(loss_f, loss_r, rtol=1e-5)
    for k in ws_r:
        np.testing.assert_allclose(
            np.asarray(ws_f[k]), np.asarray(ws_r[k]), rtol=1e-4, atol=1e-5,
            err_msg=f"ws field {k} diverged")
    a = jax.tree.leaves(p_f)
    b = jax.tree.leaves(p_r)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-6)


def test_pull_pool_cvm_matches_composed():
    """fast pull_pool_cvm == pull_sparse + fused_seqpool_cvm."""
    from paddlebox_tpu.ops.seqpool_cvm import fused_seqpool_cvm
    eng = make_engine()
    rng = np.random.default_rng(5)
    idx_sbl = jnp.asarray(rng.integers(1, N_KEYS, (S, B, L)), jnp.int32)
    lengths = jnp.asarray(rng.integers(0, L + 1, (S, B)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 2, (B,)), jnp.float32)
    ins_cvm = jnp.stack([jnp.ones_like(labels), labels], 1)

    emb = embedding.pull_sparse(eng.ws, idx_sbl)
    want = fused_seqpool_cvm(emb, lengths, ins_cvm, True)  # [B, S*E]
    got = fast_path.pull_pool_cvm(
        eng.ws, jnp.transpose(idx_sbl, (0, 2, 1)), lengths, True)
    got = got.reshape(B, -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_fast_path_respects_row0():
    eng = make_engine()
    ws0 = {k: np.asarray(v).copy() for k, v in eng.ws.items()}
    cfg = SparseSGDConfig(mf_create_thresholds=0.0)
    idx = jnp.zeros((S, L, B), jnp.int32)  # everything padded to row 0
    lengths = jnp.zeros((S, B), jnp.int32)
    d_pooled = jnp.ones((B, S, 3 + MF))
    ins = jnp.ones((B, 2))
    out = fast_path.push_and_update(eng.ws, idx, lengths, d_pooled, ins,
                                    jnp.arange(S, dtype=jnp.int32), cfg)
    for k, v in out.items():
        np.testing.assert_allclose(np.asarray(v), ws0[k], atol=1e-7,
                                   err_msg=f"{k} changed by pure-padding push")
