"""models/sambay.py against the plain reference
(benchmark/reference/phi4_mini_flash.py), at small sizes on the CPU with
seeded weights: the chunked selective scan against the token-by-token
recurrence, window and full attention in blocks against the masked full
softmax, the loss and every gradient leaf (the head's and the shared
memory's and KV's among them), the vocabulary's shares against the uncut
tied head.  ``test_tied_head.py`` has the push and the whole step."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.models import sambay
from sambay_fixture import (BLOCKS, CAP, HIDDEN, VOCAB, config, model_of,
                            module, seeded)

ref = module("reference")


@pytest.fixture(autouse=True)
def several_blocks(monkeypatch):
    """Chunks of 4 tokens in segments of 8, 4 queries a block in 3 groups,
    16 feed-forward and 8 head tokens a block: the tests' two dozen
    positions then take several of each (the constants are sized for
    8,192)."""
    for name, value in BLOCKS:
        monkeypatch.setattr(sambay, name, value)
    monkeypatch.setattr(ref, "SCAN_BLOCK", 8)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 8)
    monkeypatch.setattr(ref, "MLP_BLOCK", 8)


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))


def scan_inputs(n, seed=0, d=12, ns=4):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    dt = jnp.asarray(rng.uniform(1e-3, 0.5, (n, d)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 16.0, (ns, d)), jnp.float32)
    return normal(n, d), dt, a, normal(n, ns), normal(n, ns)


@pytest.mark.parametrize("n,chunk", [(24, 8), (19, 8), (5, 8), (33, 4),
                                     (7, 1)])
def test_chunked_selective_scan_is_the_token_recurrence(monkeypatch, n,
                                                        chunk):
    """Lengths that are no whole number of chunks among them; the value,
    the state handed on and every gradient."""
    monkeypatch.setattr(sambay, "MAMBA_CHUNK", chunk)
    args = scan_inputs(n)
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[0].shape), jnp.float32)
    state = jnp.asarray(np.random.default_rng(2).normal(
        size=args[2].shape), jnp.float32)

    def token_by_token(x, dt, a, bm, cm):
        s, out = jnp.zeros_like(a), []
        for t in range(n):
            s = jnp.exp(dt[t][None, :] * a) * s \
                + (dt[t] * x[t])[None, :] * bm[t][:, None]
            out.append(jnp.sum(s * cm[t][:, None], axis=0))
        return jnp.stack(out), s

    got, got_state = sambay.selective_scan(*args)
    want, want_state = token_by_token(*args)
    assert rel(got, want) <= 2e-5 and rel(got_state, want_state) <= 2e-5
    np.testing.assert_allclose(ref.scan_tokens(*args), want, atol=1e-5)
    # from a state handed on: two runs are one
    first, mid = sambay.selective_scan(*(t[:n // 2] if t.shape[0] == n
                                         else t for t in args))
    rest, _ = sambay.selective_scan(*(t[n // 2:] if t.shape[0] == n else t
                                      for t in args), state=mid)
    assert rel(jnp.concatenate([first, rest]), want) <= 2e-5
    g_got = jax.grad(lambda *a: jnp.sum(
        sambay.selective_scan(*a)[0] * weight)
        + jnp.sum(sambay.selective_scan(*a)[1] * state),
        argnums=range(5))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(token_by_token(*a)[0] * weight)
                      + jnp.sum(token_by_token(*a)[1] * state),
                      argnums=range(5))(*args)
    assert max(rel(a, b) for a, b in zip(g_got, g_want)) <= 5e-5


def attention_inputs(n, seed=0, groups=2, d=8):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return normal(groups, 2, 2, n, d), normal(groups, 2, n, d), \
        normal(groups, n, 2 * d)


def full_softmax(q, k, v, length, lam, window=0):
    """The masked full softmax, both maps, one differential head at a
    time."""
    n, d = q.shape[3], q.shape[-1]
    i = np.arange(n)
    allowed = (i[None, :] <= i[:, None]) & (i[None, :] < length)
    if window:
        allowed &= i[None, :] > i[:, None] - window
    out = []
    for g in range(q.shape[0]):
        for h in range(2):
            maps = [jax.nn.softmax(jnp.where(
                allowed, q[g, h, side] @ k[g, side].T / math.sqrt(d),
                -1e30), axis=-1) for side in range(2)]
            out.append((maps[0] - lam * maps[1]) @ v[g])
    return jnp.stack(out).reshape(q.shape[:2] + (n, 2 * d))


@pytest.mark.parametrize("n,length,window,block", [
    (5, 5, 8, 4), (8, 8, 8, 4), (24, 24, 8, 4), (19, 13, 8, 4),
    (24, 24, 8, 16), (24, 20, 3, 8)])
def test_window_attention_in_blocks_is_the_masked_full_softmax(
        monkeypatch, n, length, window, block):
    """Lengths below, at and above the window; a block wider than the
    window and narrower; the value and every gradient.  No product of a
    block has a key extent beyond window + block."""
    monkeypatch.setattr(sambay, "SWA_QBLOCK", block)
    q, k, v = attention_inputs(n)
    lam = jnp.float32(0.37)
    weight = jnp.asarray(np.random.default_rng(3).normal(
        size=q.shape[:2] + (n, v.shape[-1])), jnp.float32)
    valid = (np.arange(n) < length)[:, None]

    def blocked(q, k, v):
        return sambay.window_attention(q, k, v, length, lam, window)

    got = blocked(q, k, v)
    want = full_softmax(q, k, v, length, lam, window)
    assert rel(jnp.where(valid, got, 0), jnp.where(valid, want, 0)) <= 2e-5
    g_got = jax.grad(lambda *a: jnp.sum(jnp.where(
        valid, blocked(*a), 0) * weight), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: jnp.sum(jnp.where(
        valid, full_softmax(*a, length, lam, window), 0) * weight),
        argnums=(0, 1, 2))(q, k, v)
    assert max(rel(a, b) for a, b in zip(g_got, g_want)) <= 5e-5
    text = jax.jit(blocked).lower(q, k, v).as_text()
    extents = {int(m) for line in text.splitlines() if "dot_general" in line
               for m in __import__("re").findall(r"tensor<(?:\d+x)*?(\d+)x",
                                                 line)}
    assert n <= window + min(block, n) or n not in extents


@pytest.mark.parametrize("n,length,block,groups", [(24, 24, 4, 3),
                                                   (19, 13, 4, 8),
                                                   (8, 8, 256, 8)])
def test_full_attention_in_blocks_is_the_masked_full_softmax(
        monkeypatch, n, length, block, groups):
    monkeypatch.setattr(sambay, "ATTN_QBLOCK", block)
    monkeypatch.setattr(sambay, "ATTN_GROUPS", groups)
    q, k, v = attention_inputs(n, seed=1)
    lam = jnp.float32(0.61)
    valid = (np.arange(n) < length)[:, None]
    weight = jnp.asarray(np.random.default_rng(4).normal(
        size=q.shape[:2] + (n, v.shape[-1])), jnp.float32)
    got = sambay.diff_attention(q, k, v, length, lam)
    want = full_softmax(q, k, v, length, lam)
    assert rel(jnp.where(valid, got, 0), jnp.where(valid, want, 0)) <= 2e-5
    g_got = jax.grad(lambda *a: jnp.sum(jnp.where(
        valid, sambay.diff_attention(*a, length, lam), 0) * weight),
        argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: jnp.sum(jnp.where(
        valid, full_softmax(*a, length, lam), 0) * weight),
        argnums=(0, 1, 2))(q, k, v)
    assert max(rel(a, b) for a, b in zip(g_got, g_want)) <= 5e-5


@pytest.mark.parametrize("seed,lengths,layers", [
    (0, (CAP, 13, 1), 6), (1, (9, CAP), 6), (2, (2, 17, 24, 5), 6),
    (3, (CAP, 11), 8)])
def test_loss_and_every_gradient_leaf_agree_with_the_reference(seed, lengths,
                                                               layers):
    """``layers`` 8: two Gated Memory Units and two cross layers read the
    one memory and the one KV, whose gradients sum over the readers."""
    cfg = config(layers=layers)
    model, params, mf, batch, args = seeded(cfg, seed, lengths)
    (loss, aux), (g_p, g_x, g_e) = jax.jit(jax.value_and_grad(
        lambda p, x, e: model.loss(p, x, *args[1:4], head=e),
        argnums=(0, 1, 2), has_aux=True))(params, args[0], args[4])
    out = ref.batch_loss(params, jnp.asarray(mf), batch, cfg, "float32",
                         with_grads=True)
    assert abs(float(loss) - out["loss"]) <= 1e-5 * out["loss"]
    assert float(aux["stats"][0]) == out["targets"]
    assert float(aux["stats"][1]) == sum(lengths)
    assert float(aux["stats"][2]) == CAP * len(lengths) - sum(lengths)
    worst = ref.named_leaves(jax.tree.map(rel, g_p, out["d_params"]))
    assert len(worst) == len(ref.named_leaves(params))
    assert max(worst.values()) <= 2e-4, max(worst, key=worst.get)
    assert all(float(jnp.abs(g).max()) > 0
               for g in ref.named_leaves(g_p).values())
    # the loss's own gradients: the trainer's push negates them
    got = np.transpose(np.asarray(g_x)[:, 0], (1, 0, 2))       # [L, B, D]
    assert rel(got, out["d_rows"]["occ"]) <= 2e-5
    assert g_e.shape == (VOCAB, HIDDEN)
    assert rel(g_e, out["d_rows"]["head"]) <= 2e-5
    # no dense head: nothing in the tree is as wide as the vocabulary
    assert not any(VOCAB in leaf.shape for leaf in jax.tree.leaves(params)
                   if leaf.shape != (HIDDEN, 2 * model.d_inner))
    # the AUC's pairs: the same scores, positives and negatives
    n = len(aux["auc_mask"]) // 2
    mask, pred = np.asarray(aux["auc_mask"]), np.asarray(aux["auc_pred"])
    for half, name in ((slice(0, n), "pos"), (slice(n, None), "neg")):
        want = np.concatenate([np.asarray(a[name])[np.asarray(
            a["has_target"])] for a in out["aux"]])
        np.testing.assert_allclose(pred[half][mask[half]], want, atol=1e-6)


def test_the_layers_held_are_published_layers_14_to_19():
    cfg = config()
    model = model_of(cfg)
    assert model.layers == ref.sizes(cfg)["layers"] == (
        (14, "mamba"), (15, "swa"), (16, "mamba"), (17, "attn_full"),
        (18, "gmu"), (19, "attn_cross"))
    assert (model.memory_from, model.kv_from) == (2, 3)
    whole = model_of(config(layers=32, layer_first=0))
    kinds = [k for _, k in whole.layers]
    assert [kinds.count(k) for k in sambay.KINDS] == [9, 8, 1, 7, 7]
    assert (whole.memory_from, whole.kv_from) == (16, 17)
    assert abs(sambay.lambda_init(17) - (0.8 - 0.6 * math.exp(-5.1))) < 1e-12
    with pytest.raises(ValueError):       # a GMU with no memory ahead of it
        model_of(config(layers=2, layer_first=18))


def test_the_vocabulary_shares_side_by_side_are_the_uncut_tied_head():
    """The guide's share test: the table and the head cut over 8 chips by
    vocabulary.  Every chip computes the tower alike (its parameters do
    not know the slice); a chip's logits are LNf(h) E_slice^T over the
    ids it holds, and the 8 slices side by side are the uncut head's
    logits LNf(h) E^T, id for id; a chip's model names the keys of its
    own slice."""
    rng = np.random.default_rng(0)
    whole = 8 * 32
    e = jnp.asarray(rng.normal(0, 0.05, (whole, HIDDEN)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(20, HIDDEN)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 32, 20), jnp.int32)
    uncut = h @ e.T
    sides, keys = [], []
    for share in range(8):
        cfg = config(vocab=32, vocab_first=32 * share)
        model = model_of(cfg)
        keys.append(model.head_keys())
        rows = e[32 * share:32 * (share + 1)]
        # what the chip's head block computes from the rows it pulled:
        # the block's logits reduced to log-sum-exp and the target's
        ce, lp_pos, _ = model.head_terms(rows, h, targets, targets)
        z = h @ rows.T
        np.testing.assert_allclose(
            -np.asarray(lp_pos), np.asarray(ce), atol=1e-6)
        np.testing.assert_allclose(np.asarray(ce), np.asarray(
            jax.nn.logsumexp(z, axis=-1)
            - jnp.take_along_axis(z, targets[:, None], axis=1)[:, 0]),
            atol=1e-5)
        sides.append(z)
    np.testing.assert_allclose(np.concatenate(sides, axis=1), uncut,
                               atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(keys),
                                  np.arange(1, whole + 1, dtype=np.uint64))
