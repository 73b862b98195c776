"""mxu_path (sorted-SpMM step) vs fast_path / reference path equivalence.

Same working set + batch through all three sparse pipelines must produce
matching pooled outputs and matching post-push working sets (up to the
kernels' hi/lo bf16 summation error, ~1e-5 relative).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddlebox_tpu.config import SparseSGDConfig
from paddlebox_tpu.ps import embedding, fast_path, feature_value as fv
from paddlebox_tpu.ps import mxu_path
from paddlebox_tpu.ps import optimizer as sparse_opt


def _make_ws(n_rows, mf_dim, seed=0, created_frac=0.7, adam=False):
    rng = np.random.default_rng(seed)
    host = fv.default_rows(n_rows - 1, mf_dim, rng, 1e-2, adam=adam)
    host["show"][:] = rng.integers(1, 50, n_rows - 1).astype(np.float32)
    host["click"][:] = rng.integers(0, 5, n_rows - 1).astype(np.float32)
    host["mf_size"][:] = np.where(rng.random(n_rows - 1) < created_frac,
                                  mf_dim, 0)
    host["embed_g2sum"][:] = rng.random(n_rows - 1).astype(np.float32)
    host["mf_g2sum"][:] = rng.random(n_rows - 1).astype(np.float32)
    return embedding.build_working_set(host, mf_dim, pad_to=n_rows)


def _batch(n_rows, S, L, B, seed=1):
    rng = np.random.default_rng(seed)
    # slot-disjoint key ranges (matches real data: a feasign embeds its
    # slot id) — the per-row slot accumulator is scatter-max in the v1
    # path but count-normalized mean in the mxu path; they agree exactly
    # when a row is touched by one slot only
    per = (n_rows - 1) // S
    idx = np.zeros((S, L, B), np.int32)
    for s_ in range(S):
        idx[s_] = 1 + s_ * per + rng.integers(0, per, (L, B))
    idx[rng.random((S, L, B)) < 0.1] = 0  # sprinkle unseen keys
    lengths = rng.integers(0, L + 1, (S, B)).astype(np.int32)
    # enforce the packer convention: positions >= length carry row 0
    for s in range(S):
        for b in range(B):
            idx[s, lengths[s, b]:, b] = 0
    d_pooled = rng.normal(0, 1, (B, S, 3 + 4)).astype(np.float32)
    ins_cvm = np.stack([np.ones(B), rng.integers(0, 2, B)], 1).astype(
        np.float32)
    slot_ids = (100 + np.arange(S)).astype(np.int32)
    return (jnp.asarray(idx), jnp.asarray(lengths), jnp.asarray(d_pooled),
            jnp.asarray(ins_cvm), jnp.asarray(slot_ids))


@pytest.mark.parametrize("use_cvm", [True, False])
def test_pull_matches_fast_path(use_cvm):
    n, D, S, L, B = 300, 4, 5, 3, 16
    ws = _make_ws(n, D)
    idx, lengths, d_pooled, ins_cvm, slot_ids = _batch(n, S, L, B)
    dims = mxu_path.make_dims(S * L * B, n)
    plan = mxu_path.build_plan(idx, dims)
    got = mxu_path.pull_pool_cvm(ws, plan, dims, (S, L, B), use_cvm,
                                 interpret=True)
    want = fast_path.pull_pool_cvm(ws, idx, lengths, use_cvm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-4)


def test_push_matches_fast_path_adagrad():
    n, D, S, L, B = 300, 4, 5, 3, 16
    cfg = SparseSGDConfig(mf_create_thresholds=5.0)
    ws = _make_ws(n, D)
    idx, lengths, d_pooled, ins_cvm, slot_ids = _batch(n, S, L, B)
    dims = mxu_path.make_dims(S * L * B, n)
    plan = mxu_path.build_plan(idx, dims)
    got = mxu_path.push_and_update(ws, plan, dims, idx, d_pooled, ins_cvm,
                                   slot_ids, cfg, interpret=True)
    want = fast_path.push_and_update(ws, idx, lengths, d_pooled, ins_cvm,
                                     slot_ids, cfg)
    for k in want:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), atol=2e-3, rtol=2e-4,
            err_msg=f"field {k}")


@pytest.mark.parametrize("crossing", ["take", "sort"])
def test_trimmed_plan_matches_fast_path(crossing):
    """A trimmed plan (padding occurrences dropped from the worklist) must
    produce the same pooled pull and the same post-push working set as the
    dense fast path — under both crossing lowerings (ops/crossing.py)."""
    from paddlebox_tpu.ops import sorted_spmm as sp
    n, D, S, L, B = 300, 4, 5, 3, 16
    cfg = SparseSGDConfig(mf_create_thresholds=5.0)
    ws = _make_ws(n, D)
    idx, lengths, d_pooled, ins_cvm, slot_ids = _batch(n, S, L, B)
    dims = sp.spmm_dims(S * L * B, n, chunk=8, tile=32)
    n_real = int((np.asarray(idx) != 0).sum())
    eff = sp.trimmed_dims(dims, n_real)
    assert eff.p_pad < dims.p_pad, "batch must actually trim"
    plan = mxu_path.build_plan(idx, dims, eff)

    got = mxu_path.pull_pool_cvm(ws, plan, dims, (S, L, B), True,
                                 interpret=True, crossing=crossing)
    want = fast_path.pull_pool_cvm(ws, idx, lengths, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-4)

    got_ws = mxu_path.push_and_update(ws, plan, dims, idx, d_pooled,
                                      ins_cvm, slot_ids, cfg, interpret=True,
                                      crossing=crossing)
    want_ws = fast_path.push_and_update(ws, idx, lengths, d_pooled, ins_cvm,
                                        slot_ids, cfg)
    for k in want_ws:
        np.testing.assert_allclose(
            np.asarray(got_ws[k]), np.asarray(want_ws[k]), atol=2e-3,
            rtol=2e-4, err_msg=f"field {k}")


def test_sort_crossing_matches_take_untrimmed():
    """Untrimmed plans must also agree across crossing lowerings (the
    per-batch step path builds plans in-step, always untrimmed)."""
    n, D, S, L, B = 300, 4, 5, 3, 16
    cfg = SparseSGDConfig(mf_create_thresholds=5.0)
    ws = _make_ws(n, D)
    idx, lengths, d_pooled, ins_cvm, slot_ids = _batch(n, S, L, B)
    dims = mxu_path.make_dims(S * L * B, n)
    plan = mxu_path.build_plan(idx, dims)
    for fn, args in (
            (mxu_path.pull_pool_cvm, (ws, plan, dims, (S, L, B), True)),
            (mxu_path.push_and_update, (ws, plan, dims, idx, d_pooled,
                                        ins_cvm, slot_ids, cfg))):
        a = fn(*args, interpret=True, crossing="take")
        b = fn(*args, interpret=True, crossing="sort")
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-5, rtol=1e-5)


def test_push_matches_reference_path_all_optimizers():
    # the mxu accumulators must equal embedding.push_sparse_grads's, so any
    # optimizer rule (not just adagrad) composes with them
    n, D, S, L, B = 200, 4, 4, 2, 8
    for opt in ("adagrad", "naive", "shared_adam"):
        cfg = SparseSGDConfig(optimizer=opt, mf_create_thresholds=5.0)
        ws = _make_ws(n, D, seed=3, adam=opt == "shared_adam")
        idx, lengths, d_pooled, ins_cvm, slot_ids = _batch(n, S, L, B, seed=4)
        dims = mxu_path.make_dims(S * L * B, n)
        plan = mxu_path.build_plan(idx, dims)
        got = mxu_path.push_and_update(ws, plan, dims, idx, d_pooled,
                                       ins_cvm, slot_ids, cfg,
                                       interpret=True)
        # reference accumulators expect grads [S,B,L,3+D] with the cvm cols
        # replaced by the instance cvm and key-masked
        m = (np.arange(L)[None, :, None] <
             np.asarray(lengths)[:, None, :]).astype(np.float32)  # [S,L,B]
        g = np.zeros((S, B, L, 3 + D), np.float32)
        g[..., 0] = (np.asarray(ins_cvm)[None, :, 0][..., None] *
                     m.transpose(0, 2, 1))
        g[..., 1] = (np.asarray(ins_cvm)[None, :, 1][..., None] *
                     m.transpose(0, 2, 1))
        g[..., 2] = (np.asarray(d_pooled)[:, :, 2].T[:, :, None] *
                     m.transpose(0, 2, 1))
        g[..., 3:] = (np.asarray(d_pooled)[:, :, 3:].transpose(1, 0, 2)
                      [:, :, None, :] * m.transpose(0, 2, 1)[..., None])
        idx_sbl = jnp.transpose(idx, (0, 2, 1))  # [S,B,L]
        acc = embedding.push_sparse_grads(ws, idx_sbl, jnp.asarray(g),
                                          jnp.asarray(slot_ids))
        want = sparse_opt.apply_push(ws, acc, cfg)
        for k in want:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]), atol=2e-3,
                rtol=2e-4, err_msg=f"{opt}/{k}")


@pytest.mark.parametrize("crossing", ["take", "sort"])
def test_extended_table_pull_push_matches_reference(crossing):
    """Extended (mf_ex / NNCross) tables on the mxu path: the ex columns
    ride the feature-major table and payload, pulled values match
    pull_sparse_extended's pooling and the post-push working set matches
    the v1 accumulators (push_sparse_grads_extended) + apply_push."""
    from paddlebox_tpu.ps import feature_value as fv

    n, D, DX, S, L, B = 200, 4, 3, 4, 2, 8
    cfg = SparseSGDConfig(mf_create_thresholds=5.0)
    rng = np.random.default_rng(5)
    host = fv.default_rows(n - 1, D, rng, 1e-2, expand_dim=DX)
    host["show"][:] = rng.integers(1, 50, n - 1).astype(np.float32)
    host["click"][:] = rng.integers(0, 5, n - 1).astype(np.float32)
    host["mf_size"][:] = np.where(rng.random(n - 1) < 0.7, D, 0)
    host["mf_ex"][:] = rng.normal(0, 0.3, (n - 1, DX)).astype(np.float32)
    ws = embedding.build_working_set(host, D, pad_to=n)
    assert "mf_ex" in ws

    idx, lengths, d_pooled_, ins_cvm, slot_ids = _batch(n, S, L, B, seed=6)
    d_pooled = jnp.asarray(
        np.random.default_rng(7).normal(0, 1, (B, S, 3 + D + DX)).astype(
            np.float32))
    dims = mxu_path.make_dims(S * L * B, n)
    plan = mxu_path.build_plan(idx, dims)

    # pull: pooled [B, S, 3+D+DX] vs manual pooling of the v1 extended pull
    got = mxu_path.pull_pool_cvm(ws, plan, dims, (S, L, B), True,
                                 interpret=True, crossing=crossing)
    idx_sbl = jnp.transpose(idx, (0, 2, 1))
    emb, emb_ex = embedding.pull_sparse_extended(ws, idx_sbl)  # [S,B,L,*]
    show = np.asarray(emb)[..., 0].sum(2)                      # [S, B]
    click = np.asarray(emb)[..., 1].sum(2)
    w_ = np.asarray(emb)[..., 2].sum(2)
    mf = np.asarray(emb)[..., 3:].sum(2)                       # [S, B, D]
    mfx = np.asarray(emb_ex).sum(2)                            # [S, B, DX]
    want = np.concatenate(
        [np.stack([np.log(show + 1), np.log(click + 1) - np.log(show + 1),
                   w_], -1), mf, mfx], axis=-1).transpose(1, 0, 2)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-3, rtol=1e-4)

    # push: vs v1 extended accumulators through the same optimizer
    got_ws = mxu_path.push_and_update(ws, plan, dims, idx, d_pooled,
                                      ins_cvm, slot_ids, cfg,
                                      interpret=True, crossing=crossing)
    m = (np.arange(L)[None, :, None]
         < np.asarray(lengths)[:, None, :]).astype(np.float32)   # [S,L,B]
    g = np.zeros((S, B, L, 3 + D), np.float32)
    g[..., 0] = (np.asarray(ins_cvm)[None, :, 0][..., None]
                 * m.transpose(0, 2, 1))
    g[..., 1] = (np.asarray(ins_cvm)[None, :, 1][..., None]
                 * m.transpose(0, 2, 1))
    g[..., 2] = (np.asarray(d_pooled)[:, :, 2].T[:, :, None]
                 * m.transpose(0, 2, 1))
    g[..., 3:] = (np.asarray(d_pooled)[:, :, 3:3 + D].transpose(1, 0, 2)
                  [:, :, None, :] * m.transpose(0, 2, 1)[..., None])
    gx = (np.asarray(d_pooled)[:, :, 3 + D:].transpose(1, 0, 2)
          [:, :, None, :] * m.transpose(0, 2, 1)[..., None])
    acc = embedding.push_sparse_grads_extended(
        ws, idx_sbl, jnp.asarray(g), jnp.asarray(gx), jnp.asarray(slot_ids))
    want_ws = sparse_opt.apply_push(ws, acc, cfg)
    for k in want_ws:
        np.testing.assert_allclose(
            np.asarray(got_ws[k]), np.asarray(want_ws[k]), atol=2e-3,
            rtol=2e-4, err_msg=f"field {k}")


def _slot_dataset(caps, n, seed=8):
    """A feed config and a one-block dataset of ``n`` records over sparse
    slots of capacities ``caps`` (keys 1..299), a label and two dense
    features."""
    from paddlebox_tpu.config import DataFeedConfig, SlotConfig
    from paddlebox_tpu.data.dataset import SlotDataset
    from paddlebox_tpu.data.slot_record import SlotRecordBlock
    cfg = DataFeedConfig(slots=tuple(
        [SlotConfig("label", dtype="float", is_dense=True, dim=1),
         SlotConfig("dense0", dtype="float", is_dense=True, dim=2)]
        + [SlotConfig(f"s{i}", slot_id=100 + i, capacity=c)
           for i, c in enumerate(caps)]))
    rng = np.random.default_rng(seed)
    blk = SlotRecordBlock(n=n)
    for i, c in enumerate(caps):
        lens = rng.integers(1, c + 1, size=n)
        off = np.zeros((n + 1,), np.int64)
        np.cumsum(lens, out=off[1:])
        blk.uint64_slots[f"s{i}"] = (
            rng.integers(1, 300, size=int(off[-1])).astype(np.uint64), off)
    blk.float_slots["label"] = (rng.integers(0, 2, n).astype(np.float32),
                                np.arange(n + 1, dtype=np.int64))
    blk.float_slots["dense0"] = (
        rng.normal(0, 1, n * 2).astype(np.float32),
        np.arange(n + 1, dtype=np.int64) * 2)
    ds = SlotDataset(cfg)
    ds._blocks = [blk]
    return cfg, ds


def test_extended_table_trains_through_trainer():
    """An expand-embedding engine auto-resolves to the mxu path and trains
    a pass end-to-end (previously extended tables fell back to the slower
    reference path)."""
    from paddlebox_tpu.config import EmbeddingTableConfig
    from paddlebox_tpu.models.ctr_dnn import CtrDnn
    from paddlebox_tpu.ps.pass_manager import BoxPSEngine
    from paddlebox_tpu.trainer.trainer import SparseTrainer

    D, DX, S, CAP, B = 4, 3, 3, 2, 64
    cfg, ds = _slot_dataset((CAP,) * S, 4 * B)

    eng = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=D, expand_dim=DX, shard_num=4,
        sgd=SparseSGDConfig(mf_create_thresholds=0.0)))
    eng.begin_feed_pass()
    for b in ds.get_blocks():
        eng.add_keys(b.all_keys())
    eng.end_feed_pass()
    eng.begin_pass()
    assert "mf_ex" in eng.ws
    eng.ws["mf_size"] = jnp.full_like(eng.ws["mf_size"], D)

    model = CtrDnn(num_slots=S, emb_width=3 + D + DX, dense_dim=2,
                   hidden=(16,))
    tr = SparseTrainer(eng, model, cfg, batch_size=B)
    assert tr._resolve_path() == "mxu"
    ws_ex_before = np.asarray(eng.ws["mf_ex"]).copy()
    feed = tr.build_pass_feed(ds)
    stats = tr.train_pass(feed)
    assert np.isfinite(stats["loss"]) and stats["batches"] == 4
    # the expand embedding actually TRAINS on this path
    assert not np.allclose(np.asarray(eng.ws["mf_ex"]), ws_ex_before)


def _static_planes(plan, dims, eff, labels, slot_ids, S, L, B):
    """Host-side twin of pass_feed._build_static_planes for one batch."""
    kd = eff or dims
    p0 = dims.p_pad - kd.p_pad
    perm_full = np.concatenate([np.asarray(plan[1]),
                                np.zeros(dims.p_pad - dims.p, np.int32)])
    perm_k = perm_full[p0:]
    s_of = perm_k // (L * B)
    b_of = perm_k % B
    bs = (b_of * S + s_of).astype(np.int32)
    labelcol = np.asarray(labels)[b_of].astype(np.float32)
    slotcol = (np.asarray(slot_ids)[s_of].astype(np.float32)
               * np.asarray(plan[7]))
    return plan + (jnp.asarray(bs), jnp.asarray(labelcol),
                   jnp.asarray(slotcol))


@pytest.mark.parametrize("trim", [False, True])
@pytest.mark.parametrize("crossing", ["take", "sort"])
def test_push_static_planes_matches_legacy(trim, crossing):
    """The narrow-crossing push (static bs/labelcol/slotcol planes, only
    1+D dynamic columns cross) must produce the IDENTICAL post-push
    working set as the legacy full-payload crossing."""
    from paddlebox_tpu.ops import sorted_spmm as sp
    n, D, S, L, B = 300, 4, 5, 3, 16
    cfg = SparseSGDConfig(mf_create_thresholds=5.0)
    ws = _make_ws(n, D)
    idx, lengths, d_pooled, ins_cvm, slot_ids = _batch(n, S, L, B)
    dims = sp.spmm_dims(S * L * B, n, chunk=8, tile=32)
    eff = None
    if trim:
        eff = sp.trimmed_dims(dims, int((np.asarray(idx) != 0).sum()))
        assert eff.p_pad < dims.p_pad
    plan = mxu_path.build_plan(idx, dims, eff)
    labels = np.asarray(ins_cvm)[:, 1]
    plan11 = _static_planes(plan, dims, eff, labels, slot_ids, S, L, B)

    legacy = mxu_path.push_and_update(ws, plan, dims, idx, d_pooled,
                                      ins_cvm, slot_ids, cfg,
                                      interpret=True, crossing=crossing)
    got = mxu_path.push_and_update(ws, plan11, dims, idx, d_pooled,
                                   ins_cvm, slot_ids, cfg,
                                   interpret=True, crossing=crossing)
    for k in legacy:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(legacy[k]), atol=1e-6,
            rtol=1e-6, err_msg=f"field {k}")


def test_crossing_bf16_close_to_f32():
    """FLAGS_mxu_crossing_bf16 moves the crossings in bfloat16: pooled pull
    and post-push state stay within bf16 tolerance of the f32 path.  The
    push lever applies on the PLANES path (the legacy payload carries the
    exact slot column and ignores the flag); slot ids must survive exactly
    — including ones beyond bf16's 8 mantissa bits."""
    from paddlebox_tpu import flags
    n, D, S, L, B = 300, 4, 5, 3, 16
    cfg = SparseSGDConfig(mf_create_thresholds=5.0)
    ws = _make_ws(n, D)
    idx, lengths, d_pooled, ins_cvm, slot_ids = _batch(n, S, L, B)
    # slot ids that round in bf16 (1234 -> 1232): exactness must hold
    slot_ids = jnp.asarray(1233 + np.arange(S, dtype=np.int32))
    dims = mxu_path.make_dims(S * L * B, n)
    plan = mxu_path.build_plan(idx, dims)
    labels = np.asarray(ins_cvm)[:, 1]
    plan11 = _static_planes(plan, dims, None, labels, slot_ids, S, L, B)
    f32_pull = mxu_path.pull_pool_cvm(ws, plan, dims, (S, L, B), True,
                                      interpret=True)
    f32_ws = mxu_path.push_and_update(ws, plan11, dims, idx, d_pooled,
                                      ins_cvm, slot_ids, cfg, interpret=True)
    flags.set_flags({"mxu_crossing_bf16": True})
    try:
        bf_pull = mxu_path.pull_pool_cvm(ws, plan, dims, (S, L, B), True,
                                         interpret=True)
        bf_ws = mxu_path.push_and_update(ws, plan11, dims, idx, d_pooled,
                                         ins_cvm, slot_ids, cfg,
                                         interpret=True)
        legacy_bf_ws = mxu_path.push_and_update(ws, plan, dims, idx,
                                                d_pooled, ins_cvm, slot_ids,
                                                cfg, interpret=True)
    finally:
        flags.set_flags({"mxu_crossing_bf16": False})
    np.testing.assert_allclose(np.asarray(bf_pull), np.asarray(f32_pull),
                               atol=0.3, rtol=2e-2)
    for k in f32_ws:
        np.testing.assert_allclose(
            np.asarray(bf_ws[k]), np.asarray(f32_ws[k]), atol=0.3,
            rtol=3e-2, err_msg=f"field {k}")
    # slot ids exact on BOTH paths under the flag
    touched = np.asarray(f32_ws["slot"]) != np.asarray(ws["slot"])
    assert touched.any()
    np.testing.assert_array_equal(np.asarray(bf_ws["slot"])[touched],
                                  np.asarray(f32_ws["slot"])[touched])
    np.testing.assert_array_equal(np.asarray(legacy_bf_ws["slot"])[touched],
                                  np.asarray(f32_ws["slot"])[touched])


# -- rows pulled and pushed per position (a sequence model's slot) ----------

def _sequence_batch(n, L, B, seed=3):
    """One sequence slot: [1, L, B] rows with a padded tail, row 0 beyond a
    sequence's length, and a token repeated inside one sequence."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(2, L + 1, (1, B)).astype(np.int32)
    idx = rng.integers(1, n, (1, L, B)).astype(np.int32)
    idx[0, 1, :] = idx[0, 0, :]                  # the repeat
    idx[0] = np.where(np.arange(L)[:, None] < lengths, idx[0], 0)
    return idx, lengths


def test_unpooled_pull_is_the_masked_row_of_every_position():
    n, D, L, B = 300, 160, 6, 8                  # 164 rows: two W blocks
    ws = _make_ws(n, D)
    idx, _ = _sequence_batch(n, L, B)
    dims = mxu_path.make_dims(L * B, n)
    plan = mxu_path.build_plan(jnp.asarray(idx), dims)
    v = mxu_path.pull_rows(ws, plan, dims, (1, L, B), interpret=True)
    assert v.shape == (1, L, B, 3 + D)
    created = (np.asarray(ws["mf_size"]) > 0)[idx]
    want = np.asarray(ws["mf"])[idx] * created[..., None]
    np.testing.assert_allclose(np.asarray(v[..., 3:]), want, atol=1e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(v[..., 2]),
                               np.asarray(ws["embed_w"])[idx], atol=1e-5)
    assert (np.asarray(v)[idx == 0] == 0).all()


@pytest.mark.parametrize("planes", [False, True])
@pytest.mark.parametrize("trim", [False, True])
def test_unpooled_push_adds_every_occurrences_own_gradient(planes, trim):
    """``d_occ``: the merged accumulators are ``.at[].add`` of the
    per-occurrence gradients (a token repeated inside a sequence adds
    twice), then the same sparse rule."""
    from paddlebox_tpu.ops import sorted_spmm as sp
    n, D, L, B = 300, 160, 6, 8
    cfg = SparseSGDConfig(mf_create_thresholds=0.0)
    ws = _make_ws(n, D)
    idx, lengths = _sequence_batch(n, L, B)
    rng = np.random.default_rng(5)
    d_occ = rng.normal(0, 1, (1, L, B, 1 + D)).astype(np.float32)
    d_occ[..., 0] = 0.0                          # embed_w: no gradient
    labels = rng.integers(0, 2, B).astype(np.float32)
    ins_cvm = jnp.asarray(np.stack([np.ones(B), labels], 1), jnp.float32)
    slot_ids = jnp.asarray([100], jnp.int32)
    dims = sp.spmm_dims(L * B, n, chunk=8, tile=32)
    eff = sp.trimmed_dims(dims, int((idx != 0).sum())) if trim else None
    plan = mxu_path.build_plan(jnp.asarray(idx), dims, eff)
    if planes:
        plan = _static_planes(plan, dims, eff, labels, slot_ids, 1, L, B)
    got = mxu_path.push_and_update(ws, plan, dims, jnp.asarray(idx), None,
                                   ins_cvm, slot_ids, cfg, interpret=True,
                                   d_occ=jnp.asarray(d_occ))
    flat = idx.reshape(-1)
    real = (flat != 0).astype(np.float32)
    acc = {"g_show": jnp.zeros(n).at[flat].add(real),
           "g_click": jnp.zeros(n).at[flat].add(real * np.tile(labels, L)),
           "g_embed": jnp.zeros(n),
           "g_embedx": jnp.zeros((n, D)).at[flat].add(
               d_occ.reshape(-1, 1 + D)[:, 1:] * real[:, None]),
           "slot": jnp.where(jnp.zeros(n).at[flat].add(real) > 0, 100, 0)}
    want = sparse_opt.apply_push(ws, acc, cfg)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k])[1:],
                                   np.asarray(want[k])[1:], atol=2e-4,
                                   rtol=1e-4, err_msg=k)
    with pytest.raises(ValueError):
        mxu_path.push_and_update(ws, plan, dims, jnp.asarray(idx), None,
                                 ins_cvm, slot_ids, cfg, interpret=True,
                                 crossing="sort", d_occ=jnp.asarray(d_occ))


# -- the pooled pull crossing takes only the positions a slot can hold ------

def _capacity_batch(n_rows, caps, B, seed=1):
    """``_batch`` over slots that hold at most their declared capacity:
    [S, max(caps), B] rows, padding at and beyond a slot's own capacity
    (what the packers guarantee: BatchPacker.pad_sparse)."""
    S, L = len(caps), max(caps)
    idx, lengths, _, ins_cvm, slot_ids = _batch(n_rows, S, L, B, seed)
    idx = np.array(idx)
    for s_, c in enumerate(caps):
        idx[s_, c:] = 0
    return jnp.asarray(idx), ins_cvm, slot_ids


def _pull_tower_push(pull, ws, plan, dims, idx, ins_cvm, slot_ids, cfg):
    """One step: ``pull`` -> a small tower's gradient -> push_and_update;
    returns (pooled, the working set after it)."""
    pooled = pull(ws, plan, dims, idx.shape)
    b = pooled.shape[0]
    tower = jnp.asarray(np.random.default_rng(9).normal(
        0, 0.3, (pooled.shape[1] * pooled.shape[2], 5)).astype(np.float32))
    d_pooled = jax.grad(
        lambda x: jnp.sum(jnp.tanh(x.reshape(b, -1) @ tower)))(pooled)
    return pooled, mxu_path.push_and_update(
        ws, plan, dims, idx, d_pooled, ins_cvm, slot_ids, cfg,
        interpret=True)


@pytest.mark.parametrize("use_cvm", [True, False])
@pytest.mark.parametrize("trim", [False, True])
@pytest.mark.parametrize("caps", [(1, 1, 1), (1, 16, 1, 16), (3, 1, 16)])
def test_grouped_pull_equals_full_rectangle(caps, trim, use_cvm):
    """The crossing run once a capacity group (only the positions a slot
    can hold) gives the pooled values of the full [S, L, B] crossing bit
    for bit — the positions left out held exact zeros — and the step
    behind it leaves the same working set."""
    from paddlebox_tpu.ops import sorted_spmm as sp
    n, D, B = 300, 4, 16
    S, L = len(caps), max(caps)
    cfg = SparseSGDConfig(mf_create_thresholds=5.0)
    ws = _make_ws(n, D)
    idx, ins_cvm, slot_ids = _capacity_batch(n, caps, B)
    dims = sp.spmm_dims(S * L * B, n, chunk=8, tile=32)
    eff = None
    if trim:
        eff = sp.trimmed_dims(dims, int((np.asarray(idx) != 0).sum()))
        assert L == 1 or eff.p_pad < dims.p_pad, "batch must actually trim"
    plan = mxu_path.build_plan(idx, dims, eff)

    def pull(capacities):
        return lambda ws, plan, dims, shape: mxu_path.pull_pool_cvm(
            ws, plan, dims, shape, use_cvm, interpret=True,
            capacities=capacities)

    got = _pull_tower_push(pull(caps), ws, plan, dims, idx, ins_cvm,
                           slot_ids, cfg)
    want = _pull_tower_push(pull(None), ws, plan, dims, idx, ins_cvm,
                            slot_ids, cfg)
    assert got[0].shape == (B, S, 3 + D)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert mxu_path.pull_cross_rows(caps, (S, L, B)) == sum(caps) * B
    assert mxu_path.pull_cross_rows(caps, (S, L, B), "sort") == S * L * B


def test_equal_capacities_lower_to_the_full_rectangle_step():
    """One capacity group (DeepFM: every slot holds one key; any model
    with one capacity) takes the full-rectangle path unchanged: the step's
    StableHLO text is that of ``pull_rows`` + ``pool_cvm_values``."""
    n, D, S, L, B = 300, 4, 5, 3, 16
    cfg = SparseSGDConfig(mf_create_thresholds=5.0)
    ws = _make_ws(n, D)
    idx, _, _, ins_cvm, slot_ids = _batch(n, S, L, B)
    dims = mxu_path.make_dims(S * L * B, n)
    plan = mxu_path.build_plan(idx, dims)

    def grouped(ws, plan, dims, shape):
        return mxu_path.pull_pool_cvm(ws, plan, dims, shape, True,
                                      interpret=True, capacities=(L,) * S)

    def full(ws, plan, dims, shape):
        return mxu_path.pool_cvm_values(
            mxu_path.pull_rows(ws, plan, dims, shape, interpret=True),
            True, premasked=True)

    texts = [jax.jit(lambda ws, plan, pull=pull: _pull_tower_push(
        pull, ws, plan, dims, idx, ins_cvm, slot_ids, cfg)
    ).lower(ws, plan).as_text() for pull in (grouped, full)]
    assert texts[0] == texts[1]
    # and a model with two capacities does not
    mixed = jax.jit(lambda ws, plan: mxu_path.pull_pool_cvm(
        ws, plan, dims, (S, L, B), True, interpret=True,
        capacities=(1, L, 1, L, L))).lower(ws, plan).as_text()
    assert mixed.count("gather") > jax.jit(lambda ws, plan: full(
        ws, plan, dims, (S, L, B))).lower(ws, plan).as_text().count("gather")


@pytest.mark.parametrize("caps", [(1, 2), (1, 2, 4, 3), (0, 3, 3)])
def test_capacities_must_fit_the_rectangle(caps):
    with pytest.raises(ValueError, match="capacities"):
        mxu_path.capacity_groups(caps, 3, 3)


# -- a wide row's "take" crossing gathers row-major at lane width -----------

def _parent_take(g, inv_perm, dims, trimmed, src=None):
    """``_take_canonical`` as it was before the layout rule (PR 30)."""
    if not trimmed:
        return jnp.take(g.T[:dims.p], inv_perm, axis=0)
    v = jnp.take(g.T, jnp.maximum(inv_perm, 0), axis=0)
    return v * (inv_perm >= 0).astype(v.dtype)[:, None]


def _row_major(monkeypatch):
    """The other side of the rule at a test's size: no source is small
    enough for fast memory."""
    monkeypatch.setattr(mxu_path, "CROSS_FAST_SOURCE_BYTES", 0)


def _wide_case(caps, trim, D=32, n=300, B=16):
    from paddlebox_tpu.ops import sorted_spmm as sp
    S, L = len(caps), max(caps)
    ws = _make_ws(n, D)
    idx, ins_cvm, slot_ids = _capacity_batch(n, caps, B)
    dims = sp.spmm_dims(S * L * B, n, chunk=8, tile=32)
    eff = None
    if trim:
        eff = sp.trimmed_dims(dims, int((np.asarray(idx) != 0).sum()))
        assert eff.p_pad < dims.p_pad, "batch must actually trim"
    return ws, idx, ins_cvm, slot_ids, dims, mxu_path.build_plan(idx, dims,
                                                                 eff)


@pytest.mark.parametrize("use_cvm", [True, False])
@pytest.mark.parametrize("trim", [False, True])
@pytest.mark.parametrize("caps", [(1, 16, 1, 16), (3, 3, 3)])
def test_lane_wide_pull_equals_feature_major(caps, trim, use_cvm,
                                             monkeypatch):
    """A 35-wide row gathered row-major at lane width: the pooled pull,
    grouped and in one group, and the step behind it are bit for bit what
    the feature-major gather gives: the same rows, the same sums in the
    same order."""
    cfg = SparseSGDConfig(mf_create_thresholds=5.0)
    ws, idx, ins_cvm, slot_ids, dims, plan = _wide_case(caps, trim)

    def step():
        return _pull_tower_push(
            lambda ws, plan, dims, shape: mxu_path.pull_pool_cvm(
                ws, plan, dims, shape, use_cvm, interpret=True,
                capacities=caps),
            ws, plan, dims, idx, ins_cvm, slot_ids, cfg)

    assert mxu_path.pull_cross_lane_width(ws, plan, dims) == 0
    want = step()
    _row_major(monkeypatch)
    assert mxu_path.pull_cross_lane_width(ws, plan, dims) == 128
    got = step()
    assert got[0].shape == (16, len(caps), 35)
    assert float(jnp.abs(got[0][..., 3:]).sum()) > 0
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _lowered_pull(fn, ws, plan):
    return jax.jit(fn).lower(ws, plan).as_text()


@pytest.mark.parametrize("shape", ["pooled-11-groups", "pooled-11-one-group",
                                   "pooled-11-trimmed", "pooled-35-fits",
                                   "rows-2051"])
def test_rows_the_rule_leaves_alone_lower_to_the_parent_text(shape,
                                                             monkeypatch):
    """A row of two sublane tiles or fewer (DeepFM's 11 wide), a row past
    the band (Ouro's 2,051-wide ``pull_rows``), both whatever the source's
    size, and a wide row whose source fits fast memory: the lowered
    StableHLO text is the parent's, op for op."""
    if shape != "pooled-35-fits":
        _row_major(monkeypatch)
    if shape == "rows-2051":
        n, D, L, B = 300, 2048, 6, 8
        ws = _make_ws(n, D)
        idx, _ = _sequence_batch(n, L, B)
        dims = mxu_path.make_dims(L * B, n)
        plan = mxu_path.build_plan(jnp.asarray(idx), dims)

        def pull(ws, plan):
            return mxu_path.pull_rows(ws, plan, dims, (1, L, B),
                                      interpret=True)
    else:
        caps = (3, 3, 3) if shape == "pooled-11-one-group" else (1, 16, 1, 16)
        ws, _, _, _, dims, plan = _wide_case(
            caps, shape == "pooled-11-trimmed",
            D=32 if shape == "pooled-35-fits" else 8)

        def pull(ws, plan):
            return mxu_path.pull_pool_cvm(
                ws, plan, dims, (len(caps), max(caps), 16), True,
                interpret=True, capacities=caps)
    text = _lowered_pull(lambda ws, plan: pull(ws, plan), ws, plan)
    assert "optimization_barrier" not in text
    monkeypatch.setattr(mxu_path, "_take_canonical", _parent_take)
    assert text == _lowered_pull(lambda ws, plan: pull(ws, plan), ws, plan)


@pytest.mark.parametrize("caps", [(1, 16, 1, 16), (1, 3, 16, 3), (4, 4)])
def test_one_relayout_serves_every_capacity_group(caps, monkeypatch):
    """The sorted columns are laid out row-major once a step, whatever the
    number of capacity groups, and every group gathers its rows from that
    one source at lane width."""
    import re
    _row_major(monkeypatch)
    ws, _, _, _, dims, plan = _wide_case(caps, True)
    text = _lowered_pull(lambda ws, plan: mxu_path.pull_pool_cvm(
        ws, plan, dims, (len(caps), max(caps), 16), True, interpret=True,
        capacities=caps), ws, plan)
    lanes = mxu_path.CROSS_LANES
    assert text.count("optimization_barrier") == 1
    assert len(re.findall(rf"stablehlo\.pad.*-> tensor<\d+x{lanes}xf32>",
                          text)) == 1
    assert len(re.findall(
        rf'"stablehlo\.gather".*\n?.*-> tensor<\d+x{lanes}xf32>',
        text)) == len(set(caps))


def _wide_trainer():
    """A 35-wide pooled model with two capacities, one trained pass."""
    from paddlebox_tpu.config import EmbeddingTableConfig
    from paddlebox_tpu.models.ctr_dnn import CtrDnn
    from paddlebox_tpu.ps.pass_manager import BoxPSEngine
    from paddlebox_tpu.trainer.trainer import SparseTrainer

    D, caps, B = 32, (1, 3, 1, 3), 64
    cfg, ds = _slot_dataset(caps, 3 * B)
    eng = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=D, shard_num=4,
        sgd=SparseSGDConfig(mf_create_thresholds=0.0)))
    eng.begin_feed_pass()
    for b in ds.get_blocks():
        eng.add_keys(b.all_keys())
    eng.end_feed_pass()
    eng.begin_pass()
    eng.ws["mf_size"] = jnp.full_like(eng.ws["mf_size"], D)
    tr = SparseTrainer(eng, CtrDnn(num_slots=len(caps), emb_width=3 + D,
                                   dense_dim=2, hidden=(16,)),
                       cfg, batch_size=B, seed=0)
    stats = tr.train_pass(tr.build_pass_feed(ds))
    return stats, {k: np.asarray(v) for k, v in eng.ws.items()}


@pytest.mark.parametrize("row_major", [True, False])
def test_gauge_says_which_layout_the_pull_crossing_took(row_major,
                                                        monkeypatch):
    """``ps.mxu.pull_cross_lane_width`` is set when the step is built:
    128 where the row-major crossing is taken, 0 where the feature-major
    one is; and the trained pass is the same on both sides of the rule."""
    from paddlebox_tpu.utils.monitor import stat_get
    if row_major:
        _row_major(monkeypatch)
    stats, ws = _wide_trainer()
    assert stat_get("ps.mxu.pull_cross_lane_width") == (
        mxu_path.CROSS_LANES if row_major else 0)
    assert stats["batches"] == 3 and np.isfinite(stats["loss"])
    monkeypatch.undo()
    if not row_major:
        _row_major(monkeypatch)
    other, ws_other = _wide_trainer()
    assert stat_get("ps.mxu.pull_cross_lane_width") == (
        0 if row_major else mxu_path.CROSS_LANES)
    assert stats["loss"] == other["loss"]
    for k in ws:
        np.testing.assert_array_equal(ws[k], ws_other[k], err_msg=k)


@pytest.mark.parametrize("w,p_pad,itemsize,lanes", [
    (11, 425_984, 4, 0),        # deepfm_criteo, both cells: 26 MiB
    (35, 851_968, 4, 128),      # widedeep_seq.epochs: 130 MiB
    (35, 851_968, 2, 0),        # the same under mxu_crossing_bf16: 78 MiB
    (2051, 4096, 4, 0),         # ouro_2p6b.seq_epochs: past the band
    (35, 638_976, 4, 0),        # 97.5 MiB fits (12.9 against 19.0 ms)
    (43, 638_976, 4, 128),      # 117 MiB does not (61.3 against 19.0 ms)
    (27, 851_968, 4, 0),        # 104 MiB fits (10.7 against 19.3 ms)
    (19, 1_277_952, 4, 128),    # 117 MiB does not (34.9 against 20.2 ms)
    (11, 3_407_872, 4, 0),      # two tiles: left alone whatever the size
    (56, 851_968, 4, 128), (59, 851_968, 4, 0), (131, 851_968, 4, 0)])
def test_lane_rule_reads_static_shapes_alone(w, p_pad, itemsize, lanes):
    assert mxu_path.cross_lane_width(w, p_pad, itemsize) == lanes
    assert mxu_path.cross_lane_width(w, p_pad, itemsize, "sort") == 0


@pytest.mark.parametrize("row_major", [False, True])
def test_bf16_crossing_takes_either_layout(row_major, monkeypatch):
    """FLAGS_mxu_crossing_bf16 under the layout rule: the 35-wide pooled
    pull in bfloat16 stays within bf16 tolerance of float32 whichever
    layout the rule gives it, and is the same bits in both."""
    from paddlebox_tpu import flags
    caps = (1, 16, 1, 16)
    ws, _, _, _, dims, plan = _wide_case(caps, True)

    def pull():
        return np.asarray(mxu_path.pull_pool_cvm(
            ws, plan, dims, (4, 16, 16), True, interpret=True,
            capacities=caps))

    f32 = pull()
    flags.set_flags({"mxu_crossing_bf16": True})
    try:
        fm = pull()
        if row_major:
            _row_major(monkeypatch)
            assert mxu_path.pull_cross_lane_width(ws, plan, dims) == 128
        got = pull()
    finally:
        flags.set_flags({"mxu_crossing_bf16": False})
    np.testing.assert_allclose(got, f32, atol=0.3, rtol=2e-2)
    np.testing.assert_array_equal(got, fm)
