"""The tied head through the parameter server (``models/sambay.py``'s
``head_keys``): the push that merges the head's gradient with the
occurrences', the pull of the head's rows, every head key in the working
set of a pass whose data has few of them, the whole step through
fleet.train_passes against the plain reference
(benchmark/reference/phi4_mini_flash.py), where a head-keys model is
refused; Ouro's and Kimi's steps lower to the text they had before the
push took a head."""

import hashlib
import math
import pathlib
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.config import SparseSGDConfig
from paddlebox_tpu.models import sambay
from paddlebox_tpu.ops import sorted_spmm as sp
from paddlebox_tpu.ps import mxu_path
from paddlebox_tpu.ps import optimizer as sparse_opt
from paddlebox_tpu.trainer.trainer import SparseTrainer
from paddlebox_tpu.utils.monitor import stat_get
from sambay_fixture import BLOCKS, config, model_of, module
import hybridlm_fixture
import looplm_fixture
from test_mxu_path import _make_ws, _sequence_batch, _static_planes

ref = module("reference")


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    monkeypatch.setattr(ref, "SCAN_BLOCK", 8)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 8)
    monkeypatch.setattr(ref, "MLP_BLOCK", 8)


@pytest.mark.parametrize("planes", [False, True])
def test_push_merges_the_heads_gradient_with_the_occurrences(planes):
    """Merged per-row gradient = occurrences + head; the rule is the
    same: a head row that no occurrence touched is left as it was, and
    shows count occurrences only."""
    n, D, L, B = 300, 160, 6, 8
    cfg = SparseSGDConfig(mf_create_thresholds=0.0)
    ws = _make_ws(n, D)
    idx, _ = _sequence_batch(n, L, B)
    rng = np.random.default_rng(5)
    d_occ = rng.normal(0, 1, (1, L, B, 1 + D)).astype(np.float32)
    d_occ[..., 0] = 0.0
    head_rows = rng.permutation(np.arange(1, n))[:200].astype(np.int32)
    d_head = rng.normal(0, 1, (200, D)).astype(np.float32)
    labels = rng.integers(0, 2, B).astype(np.float32)
    ins_cvm = jnp.asarray(np.stack([np.ones(B), labels], 1), jnp.float32)
    slot_ids = jnp.asarray([100], jnp.int32)
    dims = sp.spmm_dims(L * B, n, chunk=8, tile=32)
    plan = mxu_path.build_plan(jnp.asarray(idx), dims)
    if planes:
        plan = _static_planes(plan, dims, None, labels, slot_ids, 1, L, B)
    args = (ws, plan, dims, jnp.asarray(idx), None, ins_cvm, slot_ids, cfg)
    got = mxu_path.push_and_update(
        *args, interpret=True, d_occ=jnp.asarray(d_occ),
        head=(jnp.asarray(head_rows), jnp.asarray(d_head)))
    untied = mxu_path.push_and_update(*args, interpret=True,
                                      d_occ=jnp.asarray(d_occ))
    flat = idx.reshape(-1)
    real = (flat != 0).astype(np.float32)
    shows = jnp.zeros(n).at[flat].add(real)
    acc = {"g_show": shows,
           "g_click": jnp.zeros(n).at[flat].add(real * np.tile(labels, L)),
           "g_embed": jnp.zeros(n),
           "g_embedx": jnp.zeros((n, D)).at[flat].add(
               d_occ.reshape(-1, 1 + D)[:, 1:] * real[:, None]
           ).at[head_rows].add(d_head),
           "slot": jnp.where(shows > 0, 100, 0)}
    want = sparse_opt.apply_push(ws, acc, cfg)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k])[1:],
                                   np.asarray(want[k])[1:], atol=2e-4,
                                   rtol=1e-4, err_msg=k)
    touched = np.asarray(shows) > 0
    alone = np.setdiff1d(head_rows, np.flatnonzero(touched))
    both = np.intersect1d(head_rows, np.flatnonzero(touched))
    assert len(alone) > 50 and len(both) > 10
    for k in want:          # a row without an occurrence: unmoved
        np.testing.assert_array_equal(np.asarray(got[k])[alone],
                                      np.asarray(ws[k])[alone], err_msg=k)
    # shows and clicks count occurrences only; the head moved the rows
    # that an occurrence touched and that were created
    for k in ("show", "click"):
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(untied[k]))
    moved = np.abs(np.asarray(got["mf"]) - np.asarray(untied["mf"])).max(1)
    created = np.asarray(ws["mf_size"]) > 0
    assert (moved[both][created[both]] > 0).all()
    assert (moved[np.setdiff1d(np.arange(n), both)] == 0).all()


def test_pull_head_is_what_a_pull_of_those_keys_returns():
    n, D = 300, 160
    ws = _make_ws(n, D)
    ws["mf_size"] = ws["mf_size"].at[7].set(0)       # not created yet
    head_rows = np.array([7, 3, 0, 250, 3], np.int32)
    got = np.asarray(mxu_path.pull_head(ws, jnp.asarray(head_rows)))
    dims = mxu_path.make_dims(8, n)
    idx = np.zeros((1, 8, 1), np.int32)
    idx[0, :5, 0] = head_rows
    plan = mxu_path.build_plan(jnp.asarray(idx), dims)
    pulled = np.asarray(mxu_path.pull_rows(ws, plan, dims, (1, 8, 1),
                                           interpret=True))[0, :5, 0, 3:]
    np.testing.assert_allclose(got, pulled, atol=1e-5, rtol=1e-4)
    assert (got[0] == 0).all() and (got[2] == 0).all()


class Keep(SparseTrainer):
    def train_pass(self, feed, **kw):
        out = super().train_pass(feed, **kw)
        self.text = self._packed_step_fn.lower(
            self.engine.ws, self.params, self.opt_state, self.auc_state,
            np.int32(0), feed.data, feed.plans or {}).as_text()
        return out


@pytest.mark.parametrize("name,sha", [
    ("ouro",
     "7bec2b2e97ec5ec36c91124b5b12a155e31687a1570892dc85eac7deb8019a52"),
    ("kimi",
     "daea361e3249fa522d56678b4e1346be16d4b19957c50489e7efff9fed39627b")])
def test_untied_row_models_step_text_is_the_parents(monkeypatch, name, sha):
    """The push took an input (``head=``) and the feed a plane
    (``head_rows``) that exist only where a model names head keys: the
    fixture-size train steps of the two row models that name none lower
    to the text they had before (sha256 of the StableHLO taken on
    40f9bdb, the parent of the change that added the tied head; Kimi's
    taken again once its routed layers moved a block of consecutive
    tokens, and a block's routing weights, as one slice; both taken
    again once the step cut batch i's slices of every pass plane first,
    behind one barrier: test_step_slices.py)."""
    cfg, model = looplm_fixture.config(), None
    if name == "kimi":
        cfg = hybridlm_fixture.config()
        cfg["vocab_size"] = looplm_fixture.VOCAB
        model = hybridlm_fixture.model_of(cfg)
    with tempfile.TemporaryDirectory() as tmp:
        trainer, _, _ = looplm_fixture.fleet_run(
            pathlib.Path(tmp), cfg, passes=1, trainer_cls=Keep, model=model)
    assert trainer._head_keys is None
    assert hashlib.sha256(trainer.text.encode()).hexdigest() == sha


@pytest.fixture(scope="module")
def two_passes(tmp_path_factory):
    cfg = config(vocab=looplm_fixture.VOCAB)
    names = ("tower.tokens_valid", "tower.tokens_padded", "seq.head.rows",
             "seq.head.rows_applied")
    before = {k: stat_get(k) for k in names}
    with pytest.MonkeyPatch.context() as patch:
        for name, value in BLOCKS:
            patch.setattr(sambay, name, value)
        trainer, metrics, engine = looplm_fixture.fleet_run(
            tmp_path_factory.mktemp("sambay"), cfg, model=model_of(cfg))
    counted = {k: stat_get(k) - v for k, v in before.items()}
    return cfg, trainer, metrics, engine, counted


@pytest.mark.parametrize("path", [
    "seq.head_pull", "seq.push/ps.push.rule/seq.head_push"])
def test_the_heads_pull_and_merge_are_scoped_in_the_step(two_passes, path):
    """The head's merge lies inside the sparse rule's scope, so
    ``step.sparse_rule_ms`` reads it with the rule."""
    trainer = two_passes[1]
    assert any(path + "/" in op for op in trainer.step_scopes().values())


def test_fleet_path_keeps_every_head_key_in_the_working_set(two_passes):
    cfg, trainer, metrics, _, counted = two_passes
    assert trainer.sparse_path == "auto" and trainer._row_model
    assert trainer._packed_sig[0] == "mxu"
    assert len(metrics) == 2 and all(m["batches"] == 2 for m in metrics)
    assert all(np.isfinite(m["losses"]).all() for m in metrics)
    vocab = cfg["vocab_size"]
    head = np.arange(1, vocab + 1, dtype=np.uint64)
    for snap in trainer.snaps:
        data = np.unique(snap["batches"]["seq_keys"])
        data = data[data > 0]
        # the pass's data has few of the ids, its working set all of them
        assert len(data) < vocab // 2
        np.testing.assert_array_equal(snap["keys"], head)
        plane = snap["batches"]["head_rows"]
        assert plane.shape == (2, vocab)
        np.testing.assert_array_equal(plane[0], np.arange(1, vocab + 1))
        np.testing.assert_array_equal(plane[0], plane[1])
    # blind first step: every row masked, the head's rows too
    assert abs(metrics[0]["losses"][0] - math.log(vocab)) < 1e-5
    tokens = sum(int(s["batches"]["lengths"].sum()) for s in trainer.snaps)
    assert counted["tower.tokens_valid"] == tokens
    assert counted["seq.head.rows"] == 4 * vocab
    assert 0 < counted["seq.head.rows_applied"] < 4 * vocab // 2


def test_two_passes_equal_the_references_whole_step(two_passes):
    """From each pass's seeded state the reference's steps (its head read
    from the rows, the head's gradient merged into their push) give the
    program's losses, and its rows are what was written back: the rows
    the passes never held as an input among them, unmoved."""
    cfg, trainer, metrics, engine, _ = two_passes
    seen = set()
    for snap, got in zip(trainer.snaps, metrics):
        rows, params = snap["rows"], snap["params"]
        m, v, t = snap["m"], snap["v"], snap["t"]
        for i, loss in enumerate(got["losses"]):
            batch = {k: a[i] for k, a in snap["batches"].items()}
            rows, params, m, v, out = ref.step(rows, params, m, v, t + 1,
                                               batch, cfg)
            t += 1
            assert abs(out["loss"] - loss) <= 1e-4 * abs(loss), (i, loss)
        seen |= set(np.unique(snap["batches"]["seq_keys"]).tolist())
    keys = snap["keys"]
    host = engine.table.bulk_pull(keys)
    np.testing.assert_allclose(
        host["mf"], np.asarray(rows["mf"])[1:len(keys) + 1], rtol=1e-4,
        atol=1e-6)
    never = np.array([k for k in keys.tolist() if k not in seen], np.uint64)
    assert len(never) > 10
    idle = engine.table.bulk_pull(never)
    assert (np.asarray(idle["show"]) == 0).all()
    assert (np.asarray(idle["mf_size"]) == 0).all()


def test_a_head_keys_model_is_refused_where_row_inputs_are(tmp_path):
    cfg = config(vocab=looplm_fixture.VOCAB)
    model = model_of(cfg)
    with pytest.raises(ValueError, match="head_keys"):
        looplm_fixture.fleet_run(
            tmp_path, cfg, passes=1, model=model,
            trainer_cls=lambda *a, **kw: SparseTrainer(
                *a, sparse_path="reference", **kw))

    class Pooled:                       # names head keys, owns no loss
        head_keys = model.head_keys
        init = model.init

    with pytest.raises(ValueError, match="row_inputs"):
        looplm_fixture.fleet_run(tmp_path, cfg, passes=1, model=Pooled())
