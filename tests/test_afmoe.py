"""models/afmoe.py against the plain reference
(benchmark/reference/trinity_mini.py), at small sizes on the CPU with
seeded weights: grouped-query attention in blocks against the masked full
softmax, rotation on the sliding layers only, a chip's share of a routed
layer, the loss and every gradient leaf, the bias rule, and the whole
step through fleet.train_passes."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear_48b as kimi_ref
from benchmark.reference import step as reference
from benchmark.reference import trinity_mini as ref
from paddlebox_tpu.models import afmoe, hybridlm
from paddlebox_tpu.parallel import moe
from paddlebox_tpu.utils.monitor import stat_get
from afmoe_fixture import CAP, HIDDEN, config, model_of, seeded
import looplm_fixture

MM = reference.matmul("float32")


@pytest.fixture(autouse=True)
def several_blocks(monkeypatch):
    """4 queries a window block, 4 a full block in 3 groups, 8 head
    tokens a block, 16 assignments an expert block: the tests' two dozen
    positions then take several of each (the constants are sized for
    8,192)."""
    for name, value in (("SWA_QBLOCK", 4), ("ATTN_QBLOCK", 4),
                        ("ATTN_GROUPS", 3)):
        monkeypatch.setattr(afmoe, name, value)
    monkeypatch.setattr(hybridlm, "HEAD_BLOCK", 8)
    monkeypatch.setattr(moe, "EXPERT_BLOCK", 16)
    monkeypatch.setattr(kimi_ref, "HEAD_BLOCK", 8)   # the reference's head


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))


def dense_attention(q, k, v, length, window):
    """softmax(q k^T / sqrt(d) + M) v head by head over one dense mask;
    q [kv, heads / kv, n, d], k, v [kv, n, d]."""
    n, d = q.shape[-2], q.shape[-1]
    i = np.arange(n)
    keep = (i[None, :] <= i[:, None]) & (i[None, :] < length)
    if window:
        keep &= i[:, None] - i[None, :] < window
    s = jnp.einsum("ghqd,gkd->ghqk", q, k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("ghqk,gkd->ghqd", p, v)


@pytest.mark.parametrize("n,length,window", [
    (5, 5, 0), (13, 11, 0), (24, 24, 0), (24, 9, 0),
    (5, 5, 6), (13, 13, 6), (24, 20, 6), (23, 23, 4), (9, 9, 16)])
def test_grouped_attention_in_blocks_is_the_masked_full_softmax(
        n, length, window):
    """Lengths that cross the window and the block edges (blocks of 4,
    three groups of them for the full layer), padded tails, a window wider
    than the sequence; and the gradients."""
    rng = np.random.default_rng(n + window)
    q, k, v = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in
               ((2, 3, n, 8), (2, n, 8), (2, n, 8)))
    got = afmoe.grouped_attention(q, k, v, length, window)
    want = dense_attention(q, k, v, length, window)
    valid = np.arange(n) < length
    assert rel(got[:, :, valid], want[:, :, valid]) <= 1e-5
    weight = jnp.asarray(rng.normal(size=want.shape), jnp.float32) \
        * valid[:, None]
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * weight), (0, 1, 2))(q, k, v)
             for f in (lambda *a: afmoe.grouped_attention(*a, length, window),
                       lambda *a: dense_attention(*a, length, window))]
    for g, w in zip(*grads):
        assert rel(g, w) <= 1e-4


def test_rotation_is_on_the_sliding_layers_only():
    """The full layer takes no rotation: run as a sliding layer whose
    window covers the sequence (the same mask, the rotation switched on)
    its output changes; each kind is the reference's own."""
    cfg = config(window=CAP)
    model, params, _, _, args = seeded(cfg, lengths=(CAP,))
    w = params["layers"][3]["mixer"]                  # published layer 4
    assert model.layers[2][0] == "attn_full"
    a = jnp.asarray(np.random.default_rng(0).normal(size=(CAP, HIDDEN)),
                    jnp.float32)
    sz = ref.sizes(cfg)
    full = model.attention("attn_full", w, a, CAP)
    turned = model.attention("swa", w, a, CAP)
    assert rel(full, ref.attention(w, a, CAP, 0, sz, MM)) <= 1e-5
    assert rel(turned, ref.attention(w, a, CAP, CAP, sz, MM)) <= 1e-5
    assert rel(turned, full) > 1e-2


def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """The guide's share test: a 16-expert layer cut over 8 chips, 2
    experts each.  The parts the eight shares compute, with the shared
    expert counted once, are what the uncut reference gives; every share
    counts the same choices over all experts."""
    full = config(experts=16, held=16)
    model, params, _, _, _ = seeded(full)
    w = params["layers"][2]["ffn"]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(24, HIDDEN)),
                    jnp.float32)
    want, route = ref.routed(w, x, len(x), ref.sizes(full), MM)
    total = hybridlm.swiglu(x, w["sg"], w["su"], w["sd"])
    held_all = 0.0
    for share in range(8):
        ids = (2 * share, 2 * share + 1)
        part, counts = moe.routed_experts(
            x, jnp.ones(len(x), bool), w["router"], w["router_bias"],
            tuple(w[k][2 * share:2 * share + 2] for k in ("wg", "wu", "wd")),
            ids, 2, 2.826)
        assert float(counts["dropped"]) == 0
        np.testing.assert_array_equal(counts["route"], route)
        total = total + part
        held_all += float(counts["held"])
    assert rel(total, want) <= 1e-5
    assert held_all == 2 * len(x) == float(jnp.sum(route))


@pytest.mark.parametrize("seed,lengths", [(0, (CAP, 13, 1)), (1, (9, CAP)),
                                          (2, (2, 17, 24, 5))])
def test_loss_and_every_gradient_leaf_agree_with_the_reference(seed, lengths):
    cfg = config()
    model, params, mf, batch, args = seeded(cfg, seed, lengths)
    (loss, aux), (g_p, g_x) = jax.jit(jax.value_and_grad(
        lambda p, x: model.loss(p, x, *args[1:]), argnums=(0, 1),
        has_aux=True))(params, args[0])
    out = ref.batch_loss(params, jnp.asarray(mf), batch, cfg, "float32",
                         with_grads=True)
    assert abs(float(loss) - out["loss"]) <= 1e-5 * out["loss"]
    assert float(aux["stats"][0]) == out["targets"]
    assert float(aux["stats"][1]) == sum(lengths)
    assert float(aux["stats"][4]) == 0                       # none dropped
    # the choices over all 8 experts, of every position inside its
    # sequence: the reference's count, and k of them a position
    np.testing.assert_array_equal(aux["route"], out["route"])
    assert (np.asarray(aux["route"]).sum(axis=1) == 2 * sum(lengths)).all()
    worst = ref.named_leaves(jax.tree.map(rel, g_p, out["d_params"]))
    assert len(worst) == len(ref.named_leaves(params))
    assert max(worst.values()) <= 2e-5, max(worst, key=worst.get)
    for name, g in ref.named_leaves(g_p).items():
        moves = float(jnp.abs(g).max()) > 0
        assert moves != name.endswith("router_bias"), name
    got = np.transpose(np.asarray(g_x)[:, 0], (1, 0, 2))       # [L, B, D]
    assert rel(got, out["d_rows"]) <= 2e-5


def test_the_layers_held_are_published_layers_1_to_5():
    cfg = config()
    model = model_of(cfg)
    assert model.layers == (("swa", "dense"), ("swa", "moe"),
                            ("attn_full", "moe"), ("swa", "moe"),
                            ("swa", "moe"))
    assert ref.sizes(cfg)["layers"] == model.layers
    assert model.input_scale == math.sqrt(HIDDEN)
    params = model.init(jax.random.PRNGKey(0))
    w = params["layers"][1]
    assert w["ffn"]["router"].shape == (HIDDEN, 8)
    assert w["ffn"]["wg"].shape == (2, HIDDEN, 32)
    assert w["mixer"]["wgate"].shape == (HIDDEN, 64)
    assert w["mixer"]["wk"].shape == (HIDDEN, 32)
    assert {"g1", "g1_post", "g2", "g2_post"} <= set(w)


def test_the_bias_rule():
    """d_e = rate * sign(mean(c) - c_e), centred: an expert chosen more
    than the mean goes down, one chosen less goes up, one at the mean
    moves by the centring alone; the bias's mean does not move; a dense
    layer has none; the program's rule is the reference's."""
    cfg = config()
    model = model_of(cfg)
    params = model.init(jax.random.PRNGKey(0))
    c = jnp.asarray([[9, 1, 4, 4, 6, 0, 3, 5]] * 4, jnp.float32)  # mean 4
    new = model.balance_bias(params, {"route": c})
    plain = ref.balance_bias(params, np.asarray(c), cfg)
    d = 1e-3 * np.array([-1, 1, 0, 0, -1, 1, 1, -1], np.float32)
    for l, w in enumerate(new["layers"]):
        if l == 0:
            assert "router_bias" not in w["ffn"]
            continue
        bias = np.asarray(w["ffn"]["router_bias"])
        np.testing.assert_allclose(bias, d - d.mean(), atol=1e-9)
        assert abs(bias.mean()) < 1e-9
        np.testing.assert_array_equal(
            bias, np.asarray(plain["layers"][l]["ffn"]["router_bias"]))
    # everything else is left as it was
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                        {**params, "layers": [
                            {**w, "ffn": {k: v for k, v in w["ffn"].items()
                                          if k != "router_bias"}}
                            for w in params["layers"]]},
                        {**new, "layers": [
                            {**w, "ffn": {k: v for k, v in w["ffn"].items()
                                          if k != "router_bias"}}
                            for w in new["layers"]]})
    assert all(jax.tree.leaves(same))


def test_a_model_without_a_rate_keeps_its_bias_and_its_stats():
    """Kimi's configuration states no rate: no hook, and the stats carry
    no counts over all experts (their layout is the one it had)."""
    cfg = config()
    cfg["load_balance_coeff"] = 0.0
    model, params, _, _, args = seeded(cfg)
    assert model.after_update is None
    _, aux = model.loss(params, *args)
    assert "route" not in aux
    assert aux["stats"].shape == (5 + 4 * 2,)


@pytest.fixture(scope="module")
def two_passes(tmp_path_factory):
    cfg = config(window=4)
    cfg["vocab_size"] = looplm_fixture.VOCAB
    before = {k: stat_get(k) for k in (
        "tower.tokens_valid", "tower.moe.assignments_held",
        "tower.moe.dropped_assignments", "tower.moe.route_load_max",
        "tower.moe.route_load_mean")}
    with pytest.MonkeyPatch.context() as patch:
        for name, value in (("SWA_QBLOCK", 4), ("ATTN_QBLOCK", 4)):
            patch.setattr(afmoe, name, value)
        patch.setattr(hybridlm, "HEAD_BLOCK", 8)
        patch.setattr(moe, "EXPERT_BLOCK", 16)
        trainer, metrics, engine = looplm_fixture.fleet_run(
            tmp_path_factory.mktemp("afmoe"), cfg, model=model_of(cfg))
    counted = {k: stat_get(k) - v for k, v in before.items()}
    return cfg, trainer, metrics, engine, counted


def test_steps_through_the_trainer_move_the_bias_and_count(two_passes):
    cfg, trainer, metrics, _, counted = two_passes
    assert trainer.sparse_path == "auto" and trainer._row_model
    assert trainer._packed_sig[0] == "mxu"
    assert len(metrics) == 2 and all(m["batches"] == 2 for m in metrics)
    assert all(np.isfinite(m["losses"]).all() for m in metrics)
    # blind first step: every row masked, every logit 0
    assert abs(metrics[0]["losses"][0] - math.log(looplm_fixture.VOCAB)) \
        < 1e-5
    tokens = sum(int(s["batches"]["lengths"].sum()) for s in trainer.snaps)
    assert counted["tower.tokens_valid"] == tokens
    assert counted["tower.moe.dropped_assignments"] == 0
    assert counted["tower.moe.route_load_max"] \
        >= counted["tower.moe.route_load_mean"] > 0
    biases = np.stack([np.asarray(w["ffn"]["router_bias"])
                       for w in trainer.params["layers"][1:]])
    assert np.abs(biases).max() > 0                 # the rule moved them
    assert np.abs(biases.mean(axis=1)).max() < 1e-7  # centred
    # at most 1e-3 a step, 4 steps
    assert np.abs(biases).max() <= 4 * 2e-3


def test_steps_through_the_trainer_are_the_references(two_passes):
    """From each pass's seeded state the reference's whole steps (the
    sparse rule, Adam, then the bias rule) give the program's losses, and
    after the last one the program's bias; the bias's spread of a pass is
    the mean over its steps of the spread each routed with."""
    cfg, trainer, metrics, _, _ = two_passes

    def spread(params):
        bias = np.stack([np.asarray(w["ffn"]["router_bias"])
                         for w in params["layers"][1:]])
        return float(bias.max() - bias.min())

    for snap, got in zip(trainer.snaps, metrics):
        rows, params = snap["rows"], snap["params"]
        m, v, t = snap["m"], snap["v"], snap["t"]
        spreads = []
        for i, loss in enumerate(got["losses"]):
            spreads.append(spread(params))
            batch = {k: a[i] for k, a in snap["batches"].items()}
            rows, params, m, v, out = ref.step(rows, params, m, v, t + 1,
                                               batch, cfg)
            t += 1
            assert abs(out["loss"] - loss) <= 1e-4 * abs(loss), (i, loss)
    for l in range(1, 5):
        np.testing.assert_allclose(
            np.asarray(trainer.params["layers"][l]["ffn"]["router_bias"]),
            np.asarray(params["layers"][l]["ffn"]["router_bias"]),
            atol=1e-7)
    assert spreads[-1] > 0
    assert stat_get("tower.moe.bias_range") == pytest.approx(
        float(np.mean(spreads)), abs=1e-7)
