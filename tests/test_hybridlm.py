"""models/hybridlm.py and parallel/moe.py::routed_experts against the plain
reference (benchmark/reference/kimi_linear_48b.py), at small sizes on the
CPU with seeded weights: the chunked delta rule against the token-by-token
recurrence, latent attention in query blocks against the full softmax, a
chip's share of a routed layer, the loss and every gradient leaf, and the
whole step through fleet.train_passes."""

import hashlib
import math
import pathlib
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear_48b as ref
from benchmark.reference import step as reference
from paddlebox_tpu.models import hybridlm, looplm
from paddlebox_tpu.parallel import moe
from paddlebox_tpu.trainer.trainer import SparseTrainer
from paddlebox_tpu.utils.monitor import stat_get
from hybridlm_fixture import CAP, HIDDEN, config, model_of, seeded
import looplm_fixture

MM = reference.matmul("float32")


@pytest.fixture(autouse=True)
def several_blocks(monkeypatch):
    """Chunks of 8 tokens in sub-chunks of 4, blocks of 2 chunks, 8
    queries and 8 head tokens a block, 16 assignments an expert block:
    the tests' two dozen positions then take several of each (the
    constants are sized for 4,096)."""
    for name, value in (("KDA_CHUNK", 8), ("KDA_SUB", 4), ("KDA_BLOCK", 2),
                        ("MLA_QBLOCK", 8), ("HEAD_BLOCK", 8)):
        monkeypatch.setattr(hybridlm, name, value)
    monkeypatch.setattr(moe, "EXPERT_BLOCK", 16)
    monkeypatch.setattr(ref, "SCAN_BLOCK", 8)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 8)


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))


def kda_inputs(n, seed=0, b=2, heads=2, d=16, decay=1.0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q = hybridlm.l2_normalise(normal(b, n, heads, d)) * d ** -0.5
    k = hybridlm.l2_normalise(normal(b, n, heads, d))
    g = -decay * jnp.asarray(rng.uniform(1e-3, 1.6, (b, n, heads, d)),
                             jnp.float32)
    beta = jax.nn.sigmoid(normal(b, n, heads))
    return q, k, normal(b, n, heads, d), g, beta


@pytest.mark.parametrize("n,chunk,sub", [(24, 8, 4), (19, 8, 4), (5, 8, 4),
                                         (21, 16, 4), (13, 4, 4),
                                         (40, 16, 16)])
def test_chunked_delta_rule_is_the_token_recurrence(monkeypatch, n, chunk,
                                                    sub):
    """Lengths that are no whole number of chunks, a chunk of one
    sub-chunk, a sequence shorter than a chunk: outputs and the gradient
    of every input agree with the recurrence."""
    monkeypatch.setattr(hybridlm, "KDA_CHUNK", chunk)
    monkeypatch.setattr(hybridlm, "KDA_SUB", sub)
    args = kda_inputs(n, seed=n)
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[2].shape), jnp.float32)

    def recurrent(*a):
        return jnp.stack([ref.delta_rule(*(t[i] for t in a))
                          for i in range(a[0].shape[0])])

    def both(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *b: jnp.sum(fn(*b) * weight), argnums=range(5))(*a)))

    got, g_got = both(hybridlm.kda_chunked)(*args)
    want, g_want = both(recurrent)(*args)
    assert rel(got, want) <= 2e-5
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        assert rel(a, b) <= 1e-4, name


def test_a_decay_that_empties_the_state_in_a_step_stays_finite():
    """No exponent is positive: a per-token log decay of -40 (e^40 a
    token, e^320 a chunk, far beyond float32) gives the recurrence's
    output, finite, with finite gradients."""
    args = kda_inputs(24, seed=3, decay=25.0)
    got = jax.jit(hybridlm.kda_chunked)(*args)
    want = jnp.stack([ref.delta_rule(*(t[i] for t in args))
                      for i in range(2)])
    assert bool(jnp.all(jnp.isfinite(got))) and rel(got, want) <= 2e-5
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(hybridlm.kda_chunked(*a) ** 2),
        argnums=range(5)))(*args)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_unit_lower_inverse_and_its_gradient():
    rng = np.random.default_rng(0)
    low = jnp.asarray(np.tril(rng.normal(size=(3, 2, 16, 16)), -1),
                      jnp.float32)
    eye = np.eye(16, dtype=np.float32)
    got = hybridlm.unit_lower_inverse(low)
    np.testing.assert_allclose(got, np.linalg.inv(eye + np.asarray(low)),
                               rtol=1e-4, atol=1e-4)
    weight = jnp.asarray(rng.normal(size=low.shape), jnp.float32)
    g = jax.grad(lambda x: jnp.sum(hybridlm.unit_lower_inverse(x)
                                   * weight))(low)
    want = jax.grad(lambda x: jnp.sum(jnp.linalg.inv(eye + x) * weight))(low)
    np.testing.assert_allclose(g, np.tril(np.asarray(want), -1), rtol=1e-3,
                               atol=1e-3)


@pytest.mark.parametrize("n,block", [(24, 8), (19, 8), (8, 256)])
def test_latent_attention_in_query_blocks_is_the_full_softmax(
        monkeypatch, n, block):
    monkeypatch.setattr(hybridlm, "MLA_QBLOCK", block)
    cfg = config()
    model = model_of(cfg)
    sz = ref.sizes(cfg)
    w = model.init(jax.random.PRNGKey(2))["layers"][3]["mixer"]
    rng = np.random.default_rng(n)
    a = jnp.asarray(rng.normal(size=(2, n, HIDDEN)), jnp.float32)
    lengths = np.asarray([n, max(n // 2, 1)])
    weight = jnp.asarray(rng.normal(size=a.shape), jnp.float32)
    keep = (np.arange(n)[None, :] < lengths[:, None])[..., None]

    def program(w, a):
        return jnp.sum(jnp.where(
            keep, model.mla(w, a, jnp.asarray(lengths)) * weight, 0))

    def plain(w, a):
        out = jnp.stack([ref.mla(w, a[i], int(lengths[i]), sz, MM)
                         for i in range(2)])
        return jnp.sum(jnp.where(keep, out * weight, 0))

    got, g_got = jax.jit(jax.value_and_grad(program, (0, 1)))(w, a)
    want, g_want = jax.jit(jax.value_and_grad(plain, (0, 1)))(w, a)
    assert abs(got - want) <= 1e-4 * abs(want)
    got, want = g_got, g_want
    assert max(jax.tree.leaves(jax.tree.map(rel, got, want))) <= 1e-4


def routed_layer(cfg, seed=4, tokens=40):
    model = model_of(cfg)
    w = model.init(jax.random.PRNGKey(seed))["layers"][1]["ffn"]
    w["router"] = 2.5 * w["router"]
    x = jnp.asarray(np.random.default_rng(seed).normal(
        size=(tokens, HIDDEN)), jnp.float32)
    return model, w, x


def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """The guide's share test: an 8-expert layer cut over 4 chips, 2
    experts each.  The parts the four shares compute, with the shared
    expert counted once, are what the uncut reference gives."""
    full = config(experts=8, held=8)
    model, w, x = routed_layer(full)
    want = ref.routed(w, x, ref.sizes(full), MM)
    total = hybridlm.swiglu(x, w["sg"], w["su"], w["sd"])
    held_all = 0.0
    for share in range(4):
        ids = (2 * share, 2 * share + 1)
        part, counts = moe.routed_experts(
            x, jnp.ones(len(x), bool), w["router"],
            w["router_bias"],
            tuple(w[k][2 * share:2 * share + 2] for k in ("wg", "wu", "wd")),
            ids, 2, 2.446)
        # the share alone is the reference given the same share
        cut = config(experts=8, held=2, first=2 * share)
        w_cut = {**w, **{k: w[k][2 * share:2 * share + 2]
                         for k in ("wg", "wu", "wd")}}
        alone = ref.routed(w_cut, x, ref.sizes(cut), MM) \
            - ref.swiglu(x, w["sg"], w["su"], w["sd"], MM)
        assert rel(part, alone) <= 1e-5
        assert float(counts["dropped"]) == 0
        total = total + part
        held_all += float(counts["held"])
    assert rel(total, want) <= 1e-5
    assert held_all == 2 * len(x)          # every choice lies on one chip
    # and through the model's own layer, every expert held
    out, _ = model.routed(w, x[None], jnp.ones((1, len(x)), bool))
    assert rel(out[0], want) <= 1e-5


@pytest.mark.parametrize("bias,first_column,held", [
    ({1: 1.0, 5: 0.5}, 0.0, 40),     # one expert: 2 blocks of 16 and one of 8
    ({0: 1.0, 1: 0.9}, 0.0, 80),     # both choices held, the worst case
    ({1: 1.0, 5: 0.3}, 10.0, None),  # some tokens' second choice too
    ({4: 1.0, 5: 0.5}, 0.0, 0)])     # every choice lies elsewhere: no block
def test_a_router_that_herds_the_tokens(bias, first_column, held):
    """A router that sends every token to held expert 1, to both held
    experts (the worst case: every position's every choice), to expert 1
    and some also to expert 0 (each expert's last block partly filled),
    or to none held (no block runs): nothing is dropped and the layer is
    the reference's, gradients too."""
    cfg = config(experts=8, held=2)
    _, w, x = routed_layer(cfg)
    w["router"] = (0.01 * w["router"]).at[:, 0].set(
        jnp.zeros(HIDDEN).at[0].set(first_column))
    w["router_bias"] = jnp.zeros(8).at[jnp.asarray(list(bias))].set(
        jnp.asarray(list(bias.values())))

    def program(w, x):
        return moe.routed_experts(
            x, jnp.ones(len(x), bool), w["router"], w["router_bias"],
            (w["wg"], w["wu"], w["wd"]), (0, 1), 2, 2.446)

    def plain(w, x):
        return ref.routed(w, x, ref.sizes(cfg), MM) \
            - ref.swiglu(x, w["sg"], w["su"], w["sd"], MM)

    out, counts = jax.jit(program)(w, x)
    if held is None:        # expert 0 where sigmoid(10 x_0) + 0 > 0.8
        also = int((np.asarray(x[:, 0]) > math.log(4) / 10).sum())
        assert 0 < also < len(x) - 1
        held = len(x) + also
        assert counts["load"].tolist() == [float(also), float(len(x))]
    assert float(counts["held"]) == held == float(jnp.sum(counts["load"]))
    assert float(counts["dropped"]) == 0
    # nothing held: the reference's two terms cancel to 1e-10, not to 0
    err = rel if held else (
        lambda a, b: float(jnp.max(jnp.abs(a - b))) / 1e-3)
    assert err(out, plain(w, x)) <= 1e-5
    weight = jnp.asarray(np.random.default_rng(2).normal(size=x.shape),
                         jnp.float32)
    got = jax.jit(jax.grad(lambda w, x: jnp.sum(program(w, x)[0] * weight),
                           (0, 1)))(w, x)
    want = jax.grad(lambda w, x: jnp.sum(plain(w, x) * weight), (0, 1))(w, x)
    for k in ("router", "wg", "wu", "wd"):
        assert err(got[0][k], want[0][k]) <= 1e-4, k
    assert err(got[1], want[1]) <= 1e-4


@pytest.mark.parametrize("chose,sliced", [
    (range(40), [True, True, False]),   # the last block would pass the end
    (range(0, 40, 2), [False, False]),  # every other token
    (range(3, 19), [True])])            # one run, started anywhere
def test_a_block_of_consecutive_tokens_moves_as_one_slice(chose, sliced):
    """The tokens ``chose`` picked held expert 1 first, every other choice
    lies elsewhere: a block (16 rows) of consecutive tokens that fits
    inside the 40 positions from its first on is moved as one slice, any
    other row by row (both paths against the reference:
    ``test_a_router_that_herds_the_tokens``)."""
    tokens, size, chose = 40, 16, list(chose)
    key = np.full((tokens, 2), 2)
    key[chose, 0] = 1
    key = jnp.asarray(key.reshape(-1))
    load = jnp.asarray([0, len(chose)], jnp.int32)
    order = jnp.concatenate([jnp.argsort(key, stable=True),
                             jnp.zeros((size,), jnp.int32)])
    plan = (key, order, load, jnp.concatenate([
        jnp.zeros((1,), jnp.int32), jnp.cumsum(-(-load // size))]))
    x = jnp.ones((tokens, HIDDEN), jnp.float32)
    experts = tuple(jnp.zeros((2, HIDDEN, 3)) for _ in range(3))
    got = [bool(moe._block_inputs(x, jnp.ones(2 * tokens + size), experts,
                                  plan, i, size, 2)[-1])
           for i in range(int(plan[3][-1]))]
    assert got == sliced


def test_positions_that_are_padding_or_all_zero_are_not_dispatched():
    """A zero input ties every score, and a top-k of ties names the
    lowest ids, the held experts: such positions are left out of the
    buffer (every expert returns 0 for them)."""
    _, w, x = routed_layer(config())
    x = x.at[3].set(0.0)
    live = jnp.ones(len(x), bool).at[7].set(False)
    out, counts = moe.routed_experts(
        x, live, w["router"], w["router_bias"],
        (w["wg"], w["wu"], w["wd"]), (0, 1), 2, 2.446)
    idx, _ = moe.route_top_k(x, w["router"], w["router_bias"], 2,
                             2.446)
    keep = np.ones(len(x), bool)
    keep[[3, 7]] = False
    assert float(counts["held"]) == int((np.asarray(idx)[keep] < 2).sum())
    assert float(jnp.abs(out[3]).max()) == 0 == float(jnp.abs(out[7]).max())


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
    assert float(aux["stats"][2]) == CAP * len(lengths) - sum(lengths)
    assert float(aux["stats"][4]) == 0                       # none dropped
    assert float(aux["stats"][3]) == float(jnp.sum(aux["stats"][5:]))
    worst = ref.named_leaves(jax.tree.map(rel, g_p, out["d_params"]))
    assert len(worst) == len(ref.named_leaves(params))
    assert max(worst.values()) <= 2e-5, max(worst, key=worst.get)
    for name, g in ref.named_leaves(g_p).items():
        moves = float(jnp.abs(g).max()) > 0
        assert moves != name.endswith("router_bias"), name
    got = np.transpose(np.asarray(g_x)[:, 0], (1, 0, 2))       # [L, B, D]
    assert rel(got, out["d_rows"]) <= 2e-5
    # the AUC's pairs: the same scores, positives and negatives
    n = len(aux["auc_mask"]) // 2
    mask, pred = np.asarray(aux["auc_mask"]), np.asarray(aux["auc_pred"])
    for half, name in ((slice(0, n), "pos"), (slice(n, None), "neg")):
        want = np.concatenate([np.asarray(a[name])[np.asarray(
            a["has_target"])] for a in out["aux"]])
        np.testing.assert_allclose(pred[half][mask[half]], want, atol=1e-6)


def test_the_period_is_one_mla_layer_in_four_behind_a_dense_layer():
    cfg = config(layers=9)
    model = model_of(cfg)
    assert [m for m, _ in model.layers] == ["kda"] * 3 + ["mla"] \
        + ["kda"] * 3 + ["mla", "kda"]
    assert [f for _, f in model.layers] == ["dense"] + ["moe"] * 8
    assert ref.sizes(cfg)["layers"] == model.layers
    params = model.init(jax.random.PRNGKey(0))
    assert params["layers"][1]["ffn"]["router"].shape == (HIDDEN, 8)
    assert params["layers"][1]["ffn"]["wg"].shape == (2, HIDDEN, 32)


def test_ouro_step_text_is_the_parents():
    """``looplm.py`` now takes its norm, negatives, head block, AUC pairs
    and padding counters from ``rowlm.py``: the fixture-size train step
    lowers to the text it had before (sha256 of the StableHLO taken on
    1389141, the parent of the PR that moved them, and taken again once
    the step cut batch i's slices of every pass plane first, behind one
    barrier: test_step_slices.py)."""
    class Keep(SparseTrainer):
        def train_pass(self, feed, **kw):
            out = super().train_pass(feed, **kw)
            self.text = self._packed_step_fn.lower(
                self.engine.ws, self.params, self.opt_state, self.auc_state,
                np.int32(0), feed.data, feed.plans or {}).as_text()
            return out

    with tempfile.TemporaryDirectory() as tmp:
        trainer, _, _ = looplm_fixture.fleet_run(
            pathlib.Path(tmp), looplm_fixture.config(), passes=1,
            trainer_cls=Keep)
    assert looplm.rms_norm is hybridlm.rms_norm
    assert hashlib.sha256(trainer.text.encode()).hexdigest() == \
        "7bec2b2e97ec5ec36c91124b5b12a155e31687a1570892dc85eac7deb8019a52"


@pytest.fixture(scope="module")
def two_passes(tmp_path_factory):
    cfg = config()
    cfg["vocab_size"] = looplm_fixture.VOCAB
    before = {k: stat_get(k) for k in (
        "tower.tokens_valid", "tower.moe.assignments_held",
        "tower.moe.dropped_assignments", "tower.moe.expert_load_max",
        "tower.moe.expert_load_mean")}
    # module scope: the function-scoped ``monkeypatch`` is not to be had
    with pytest.MonkeyPatch.context() as patch:
        for name, value in (("KDA_CHUNK", 4), ("KDA_SUB", 2),
                            ("MLA_QBLOCK", 4), ("HEAD_BLOCK", 8)):
            patch.setattr(hybridlm, name, value)
        patch.setattr(moe, "EXPERT_BLOCK", 16)
        trainer, metrics, engine = looplm_fixture.fleet_run(
            tmp_path_factory.mktemp("hybridlm"), cfg, model=model_of(cfg))
    counted = {k: stat_get(k) - v for k, v in before.items()}
    return cfg, trainer, metrics, engine, counted


def test_fleet_path_resolves_to_mxu_trains_and_counts(two_passes):
    _, trainer, metrics, _, counted = two_passes
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
    # 2 of 8 experts are here: about a quarter of the 2 choices a token
    # a routed layer, none from the blind step
    assert 0 < counted["tower.moe.assignments_held"] < 2 * 4 * tokens
    assert counted["tower.moe.expert_load_max"] \
        >= counted["tower.moe.expert_load_mean"] > 0


def test_two_passes_equal_the_references_whole_step(two_passes):
    """From each pass's seeded state (rows masked until a push creates
    them, Adam's moments carried over) the reference's steps give the
    program's losses, and its rows are what was written back."""
    cfg, trainer, metrics, engine, _ = two_passes
    for snap, got in zip(trainer.snaps, metrics):
        rows, params = snap["rows"], snap["params"]
        m, v, t = snap["m"], snap["v"], snap["t"]
        for i, loss in enumerate(got["losses"]):
            batch = {k: a[i] for k, a in snap["batches"].items()}
            rows, params, m, v, out = ref.step(rows, params, m, v, t + 1,
                                               batch, cfg)
            t += 1
            assert abs(out["loss"] - loss) <= 1e-4 * abs(loss), (i, loss)
    keys = snap["keys"]
    host = engine.table.bulk_pull(keys)
    np.testing.assert_allclose(
        host["mf"], np.asarray(rows["mf"])[1:len(keys) + 1], rtol=1e-4,
        atol=1e-6)
    assert (np.asarray(host["mf_size"]) == HIDDEN).all()
