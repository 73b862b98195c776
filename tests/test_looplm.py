"""models/looplm.py against the plain reference
(benchmark/reference/ouro_2p6b.py), at small sizes on the CPU with seeded
weights: the tower's arithmetic, the loss it owns, the gradients to
parameters and rows, the sampled-negative AUC, and the whole step through
fleet.train_passes from the seeded state (row creation included)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro_2p6b as ref
from paddlebox_tpu.metrics.auc import (AucCalculator, accumulate_auc,
                                       make_auc_state)
from paddlebox_tpu.models import looplm
from looplm_fixture import (CAP, HIDDEN, VOCAB, config, fleet_run,
                            model_of)


@pytest.fixture(autouse=True)
def head_in_several_blocks(monkeypatch):
    """Eight tokens a block of the head, so that the tests' few dozen
    positions take several (the constant is sized for 49,152 columns)."""
    monkeypatch.setattr(looplm, "HEAD_BLOCK", 8)


def seeded(cfg, seed=0, b=3, rows=40, gate=True):
    """Model, parameters (the gate moved off its zero start), a table of
    created rows, and one batch with a padded tail and a length-1
    sequence, as the model and as the reference read it."""
    model = model_of(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    if gate:
        params["gate_w"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed + 1), (HIDDEN,))
        params["gate_b"] = jnp.float32(0.3)
    rng = np.random.default_rng(seed)
    lengths = np.array([[CAP, max(CAP // 2, 2), 1][:b]], np.int32)
    mask = np.arange(CAP)[None, :, None] < lengths[:, None, :]
    idx = np.where(mask, rng.integers(1, rows, (1, CAP, b)), 0
                   ).astype(np.int32)
    mf = rng.normal(0, 0.05, (rows, HIDDEN)).astype(np.float32)
    mf[0] = 0
    key_of_row = rng.integers(1, cfg["vocab_size"] + 1, rows)
    seq_keys = np.where(mask[0].T, key_of_row[idx[0].T], 0).astype(np.int32)
    batch = {"indices": idx, "lengths": lengths,
             "valid": np.ones(b, bool), "seq_keys": seq_keys,
             "labels": np.zeros(b, np.float32)}
    args = (jnp.asarray(mf)[idx[0].T][:, None], jnp.asarray(lengths.T),
            jnp.ones(b, bool), jnp.asarray(seq_keys))
    return model, params, mf, batch, args


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))


def test_per_step_terms_and_exit_distribution_agree_with_the_reference():
    cfg = config()
    model, params, mf, batch, (rows, lengths, valid, keys) = seeded(cfg)
    b = rows.shape[0]
    tokens = np.clip(batch["seq_keys"] - 1, 0, VOCAB - 1)
    targets = np.concatenate([tokens[:, 1:], np.zeros((b, 1), np.int32)], 1)
    neg = looplm.sampled_negatives(11, keys[:, 0], lengths[:, 0], CAP, VOCAB)
    ce, gate, lp = model.tower_terms(
        params, rows[:, 0], lengths[:, 0], jnp.asarray(targets).reshape(-1),
        neg.reshape(-1))
    p = jnp.exp(model.exit_log_probs(gate))
    np.testing.assert_allclose(np.asarray(p.sum(axis=0)), 1.0, atol=1e-6)
    out = ref.batch_loss(params, jnp.asarray(mf), batch, cfg, "float32")
    for i, aux in enumerate(out["aux"]):
        n = int(batch["lengths"][0, i]) - 1      # target positions
        sl = slice(i * CAP, i * CAP + n)
        # per-step logits, read through the target's cross-entropy, the
        # gate's logit and the exit distribution
        np.testing.assert_allclose(ce[:, sl], aux["ce"][:, :n], atol=1e-5)
        np.testing.assert_allclose(gate[:, sl], aux["gate"][:, :n],
                                   atol=1e-5)
        np.testing.assert_allclose(p[:, sl], aux["p"][:, :n], atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(neg)[i], ref.negatives_of(
                11, i, int(batch["seq_keys"][i, 0]),
                int(batch["lengths"][0, i]), CAP, VOCAB))
    # the hidden states give the same logits through the head
    hs = ref.hidden_states(params, rows[0, 0], CAP, ref.sizes(cfg),
                           "float32")
    z_ref = hs[-1] @ params["head"]
    lse = jax.nn.logsumexp(z_ref, axis=-1)
    np.testing.assert_allclose(
        lse[:CAP - 1] - z_ref[np.arange(CAP - 1), targets[0, :CAP - 1]],
        ce[-1, :CAP - 1], atol=1e-5)


def test_loss_and_gradients_agree_with_the_reference():
    cfg = config()
    model, params, mf, batch, args = seeded(cfg)
    (loss, aux), (g_p, g_x) = jax.value_and_grad(
        lambda p, x: model.loss(p, x, *args[1:]), argnums=(0, 1),
        has_aux=True)(params, args[0])
    out = ref.batch_loss(params, jnp.asarray(mf), batch, cfg, "float32",
                         with_grads=True)
    assert abs(float(loss) - out["loss"]) <= 1e-5 * out["loss"]
    assert float(aux["stats"][0]) == out["targets"]
    worst = max(jax.tree.leaves(jax.tree.map(
        rel, g_p, ref.restack(out["d_params"]))))
    assert worst <= 1e-5, worst
    got = np.transpose(np.asarray(g_x)[:, 0], (1, 0, 2))       # [L, B, D]
    assert rel(got, out["d_rows"]) <= 1e-5
    # the AUC's pairs: the same scores, positives and negatives
    n = len(aux["auc_mask"]) // 2
    mask, pred = np.asarray(aux["auc_mask"]), np.asarray(aux["auc_pred"])
    for half, name in ((slice(0, n), "pos"), (slice(n, None), "neg")):
        want = np.concatenate([np.asarray(a[name])[np.asarray(
            a["has_target"])] for a in out["aux"]])
        np.testing.assert_allclose(pred[half][mask[half]], want, atol=1e-6)


def test_a_looped_layers_gradient_is_the_sum_over_its_applications():
    """Weight sharing: the gradient of a layer run T times equals the sum
    of the gradients of T untied copies, each run once in its place."""
    cfg = config(layers=1, steps=4)
    model, params, _, _, args = seeded(cfg)
    tied = jax.grad(lambda p: model.loss(p, *args)[0])(params)["layers"]

    sz = ref.sizes(cfg)

    def unrolled(stack):
        """4 untied layers; the final norm, head and gate after each, as
        the looped model's four recurrent steps have them."""
        total, count = 0.0, 0
        for b in range(args[0].shape[0]):
            ln = int(args[1][b, 0])
            if ln < 2:
                continue
            x = args[0][b, 0]
            h, ce, gate = x, [], []
            for l in range(4):
                w = {k: v[l] for k, v in stack.items()}
                h = ref.layer.__wrapped__(
                    w, h, ln, heads=sz["heads"], head_dim=sz["head_dim"],
                    theta=sz["theta"], eps=sz["eps"], mode="float32")
                h = ref.rms(h, params["gf"], sz["eps"])
                z = h @ params["head"]
                tok = np.clip(np.asarray(args[3][b]) - 1, 0, VOCAB - 1)
                tgt = np.concatenate([tok[1:], [0]])
                ce.append(jax.nn.logsumexp(z, -1) - z[np.arange(CAP), tgt])
                gate.append(h @ params["gate_w"] + params["gate_b"])
            p = ref.exit_distribution(jnp.stack(gate))
            per = jnp.sum(p * jnp.stack(ce), 0) + 0.1 * jnp.sum(
                jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-38)), 0.0), 0)
            total = total + jnp.sum(per[:ln - 1])
            count += ln - 1
        return total / count

    stack = jax.tree.map(lambda a: jnp.concatenate([a] * 4, axis=0),
                         params["layers"])
    per_copy = jax.grad(unrolled)(stack)
    for name, g in tied.items():
        want = per_copy[name].sum(axis=0, keepdims=True)
        assert rel(g, want) <= 1e-5, name


def test_one_recurrent_step_is_plain_cross_entropy():
    cfg = config(steps=1)
    model, params, mf, batch, args = seeded(cfg)
    loss, _ = model.loss(params, *args)
    sz = ref.sizes(cfg)
    total, count = 0.0, 0
    for b in range(args[0].shape[0]):
        ln = int(batch["lengths"][0, b])
        h = ref.hidden_states(params, args[0][b, 0], ln, sz, "float32")[0]
        logp = jax.nn.log_softmax(h @ params["head"], axis=-1)
        tok = np.clip(batch["seq_keys"][b] - 1, 0, VOCAB - 1)
        total += -float(sum(logp[i, tok[i + 1]] for i in range(ln - 1)))
        count += ln - 1
    assert abs(float(loss) - total / count) <= 1e-5 * total / count


def auc_of(log_p, targets, negatives, vocab):
    rows = np.arange(len(targets))
    pred = jax.nn.sigmoid(jnp.concatenate(
        [log_p[rows, targets], log_p[rows, negatives]]) + math.log(vocab))
    label = jnp.concatenate([jnp.ones(len(rows)), jnp.zeros(len(rows))])
    calc = AucCalculator(100_000)
    calc.merge_device_state(jax.device_get(accumulate_auc(
        make_auc_state(100_000), pred, label)))
    return calc.compute()["auc"]


def test_sampled_negative_auc_reads_half_untrained_and_high_for_an_oracle():
    vocab, n = 512, 4000
    rng = np.random.default_rng(3)
    freq = 1.0 / np.arange(1, vocab + 1) ** 1.1
    freq /= freq.sum()
    targets = rng.choice(vocab, n, p=freq)
    negatives = np.asarray(looplm.sampled_negatives(
        5, jnp.arange(n // 8) + 1, jnp.full(n // 8, 8), 8, vocab)
    ).reshape(-1)
    # uniform over the vocabulary (a counter hash, not a generator)
    assert abs(negatives.mean() - (vocab - 1) / 2) < 0.03 * vocab
    assert len(np.unique(negatives)) > 0.9 * vocab
    random_logits = jax.nn.log_softmax(
        jnp.asarray(rng.normal(0, 1, (n, vocab)), jnp.float32), axis=-1)
    assert abs(auc_of(random_logits, targets, negatives, vocab) - 0.5) < 0.02
    oracle = jnp.broadcast_to(jnp.log(jnp.asarray(freq, jnp.float32)),
                              (n, vocab))
    assert auc_of(oracle, targets, negatives, vocab) > 0.8


@pytest.fixture(scope="module")
def two_passes(tmp_path_factory):
    cfg = config(layers=2, steps=4)
    trainer, metrics, engine = fleet_run(
        tmp_path_factory.mktemp("looplm"), cfg)
    return cfg, trainer, metrics, engine


def test_fleet_path_resolves_to_mxu_and_trains(two_passes):
    _, trainer, metrics, _ = two_passes
    assert trainer.sparse_path == "auto" and trainer._row_model
    assert trainer._packed_sig[0] == "mxu"
    assert len(metrics) == 2 and all(m["batches"] == 2 for m in metrics)
    assert all(np.isfinite(m["losses"]).all() for m in metrics)
    # blind first step: every row masked, every logit 0
    blind = math.log(VOCAB) - 0.1 * 1.75 * math.log(2)
    assert abs(metrics[0]["losses"][0] - blind) < 1e-5


def test_two_passes_equal_the_references_whole_step(two_passes):
    """From each pass's seeded state (rows masked until a push creates
    them, Adam's moments carried over) the reference's steps give the
    program's losses, and its rows are what was written back."""
    cfg, trainer, metrics, engine = two_passes
    for snap, got in zip(trainer.snaps, metrics):
        rows, params = snap["rows"], snap["params"]
        m, v, t = snap["m"], snap["v"], snap["t"]
        for i, loss in enumerate(got["losses"]):
            batch = {k: a[i] for k, a in snap["batches"].items()}
            rows, params, m, v, out = ref.step(rows, params, m, v, t + 1,
                                               batch, cfg)
            t += 1
            assert abs(out["loss"] - loss) <= 1e-4 * abs(loss), (i, loss)
    # the last pass's rows, as the host table now holds them
    keys = snap["keys"]
    host = engine.table.bulk_pull(keys)
    np.testing.assert_allclose(host["mf"], np.asarray(rows["mf"])[1:len(keys) + 1],
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(host["show"],
                                  np.asarray(rows["show"])[1:len(keys) + 1])
    assert (np.asarray(host["mf_size"]) == HIDDEN).all()


def test_a_creation_push_takes_no_gradient_step(two_passes):
    """The rule as it stands: the push that creates a row's mf leaves the
    candidate values as they were; the row moves from its next push on."""
    _, trainer, _, engine = two_passes
    first = trainer.snaps[0]
    assert (first["rows"]["mf_size"] == 0).all()
    keys = first["keys"]
    after = engine.table.bulk_pull(keys)
    assert (np.asarray(after["mf_size"]) == HIDDEN).all()
    idx = first["batches"]["indices"]            # [N, 1, L, B]
    seen_once = [r for r in np.unique(idx) if r and (idx == r).sum() == 1]
    shared = set(np.unique(trainer.snaps[1]["batches"]["seq_keys"])) - {0}
    rows = [r for r in seen_once if int(keys[r - 1]) not in shared]
    assert rows, "no row was pushed exactly once"
    np.testing.assert_array_equal(
        np.asarray(after["mf"])[np.asarray(rows) - 1],
        first["rows"]["mf"][rows])


def test_depth_is_the_only_cut():
    """The 8-layer model is the 48-layer model cut in depth and nothing
    else: built with num_hidden_layers 48 at test widths, the same code
    and the same reference agree."""
    cfg = config(layers=48, steps=4)
    model, params, mf, batch, args = seeded(cfg, b=2)
    assert params["layers"]["wq"].shape[0] == 48
    (loss, _), g_x = jax.value_and_grad(
        lambda x: model.loss(params, x, *args[1:]), has_aux=True)(args[0])
    out = ref.batch_loss(params, jnp.asarray(mf), batch, cfg, "float32",
                         with_grads=True)
    assert abs(float(loss) - out["loss"]) <= 1e-5 * out["loss"]
    got = np.transpose(np.asarray(g_x)[:, 0], (1, 0, 2))
    assert rel(got, out["d_rows"]) <= 1e-4
    assert rel(jax.grad(lambda p: model.loss(p, *args)[0])(params)["head"],
               out["d_params"]["head"]) <= 1e-4
