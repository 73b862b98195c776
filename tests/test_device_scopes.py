"""The device side of the tracing (``utils/trace.DEVICE_SCOPES``): every
instruction of the compiled step lies under a name the program gave it,
the names come from one closed table, and the step can be printed again
from the abstract arguments of its first dispatch.  Nothing here asserts a
duration; the readers are ``benchmark/harness/step_scopes.py``.
"""

import hashlib
import os
import re

import jax
import numpy as np
import pytest

from paddlebox_tpu.config import (DataFeedConfig, EmbeddingTableConfig,
                                  SlotConfig, SparseSGDConfig)
from paddlebox_tpu.data import pass_feed
from paddlebox_tpu.data.dataset import SlotDataset
from paddlebox_tpu.data.slot_record import SlotRecordBlock
from paddlebox_tpu.models.deepfm import DeepFM
from paddlebox_tpu.ps.pass_manager import BoxPSEngine
from paddlebox_tpu.trainer.trainer import SparseTrainer
from paddlebox_tpu.utils import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "paddlebox_tpu")
MF, B = 4, 32
# slot capacities: one key a slot (DeepFM's shape: pooling is a no-op) and
# two capacity groups (Wide&Deep's: a take a group, then the pooling sum)
GEOMETRIES = {"deepfm": (1, 1, 1, 1), "sequence": (1, 3, 1, 3)}
# the shared path's scopes, in the order the step runs them
STEP_SCOPES = trace.DEVICE_SCOPES[:11]
# of those, the ones a row model's step has (its rows are not pooled and
# its loss is the tower's), and what its own core and tower add
ROW_SCOPES = tuple(s for s in STEP_SCOPES
                   if s not in ("ps.pull.pool", "dense.tower")) + (
    "seq.pull", "seq.push", "tower.ut", "tower.head_loss")
# sha256 of the packed step's StableHLO at these two geometries: a scope
# changes an instruction's op_name and nothing the compile cache keys, so
# this is the text of 6336e5a, the parent of the PR that added the scopes,
# with batch i's slices since cut first behind one barrier
# (test_step_slices.py; on 6336e5a: ac895e16... and 3b187b7a...)
PARENT_TEXT = {
    "deepfm": "3e68499699cfb4f77feee0d606b966b46024f14633ac09ad7edb4eef8d27631e",
    "sequence": "0c92faa29b581c9b7b5ffe37499809766c964a01d2f0b9d5b5a35527d713707d"}


def feed_config(caps):
    return DataFeedConfig(slots=tuple(
        [SlotConfig("label", dtype="float", is_dense=True, dim=1),
         SlotConfig("dense0", dtype="float", is_dense=True, dim=3)]
        + [SlotConfig(f"s{i}", slot_id=100 + i, capacity=c)
           for i, c in enumerate(caps)]))


def block(caps, n, seed=0):
    rng = np.random.default_rng(seed)
    blk = SlotRecordBlock(n=n)
    for i, c in enumerate(caps):
        lens = rng.integers(1 if c == 1 else 0, c + 1, size=n)
        off = np.zeros((n + 1,), np.int64)
        np.cumsum(lens, out=off[1:])
        keys = rng.integers(1, 300, size=int(off[-1])) + 1000 * (i + 1)
        blk.uint64_slots[f"s{i}"] = (keys.astype(np.uint64), off)
    blk.float_slots["label"] = (rng.integers(0, 2, n).astype(np.float32),
                                np.arange(n + 1, dtype=np.int64))
    blk.float_slots["dense0"] = (rng.normal(0, 1, n * 3).astype(np.float32),
                                 np.arange(n + 1, dtype=np.int64) * 3)
    return blk


def pooled_trainer(caps):
    engine = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=MF, shard_num=4,
        sgd=SparseSGDConfig(mf_create_thresholds=0.0)), seed=0)
    model = DeepFM(num_slots=len(caps), emb_width=3 + MF, dense_dim=3,
                   hidden=(8,))
    return SparseTrainer(engine, model, feed_config(caps), batch_size=B,
                         seed=0)


def train_one_pass(trainer, caps, batches):
    """One pass of ``batches`` batches through the pass-resident feed; the
    step's StableHLO as the parent's tests took it (the live arrays)."""
    engine = trainer.engine
    ds = SlotDataset(feed_config(caps))
    ds._blocks = [block(caps, batches * B)]
    engine.begin_feed_pass()
    engine.add_keys(ds._blocks[0].all_keys())
    engine.end_feed_pass()
    engine.begin_pass()
    feed = trainer.build_pass_feed(ds)
    trainer.train_pass(feed)
    text = trainer._packed_step_fn.lower(
        engine.ws, trainer.params, trainer.opt_state, trainer.auc_state,
        np.int32(0), feed.data, feed.plans or {}).as_text()
    engine.end_pass()
    return text


@pytest.fixture(scope="module")
def pooled_steps():
    """geometry -> (trainer, its step's text, instruction -> op_name)."""
    out = {}
    for name, caps in GEOMETRIES.items():
        trainer = pooled_trainer(caps)
        text = train_one_pass(trainer, caps, 2)
        out[name] = (trainer, text, trainer.step_scopes())
    return out


@pytest.fixture(scope="module")
def row_model_scopes(tmp_path_factory):
    from looplm_fixture import config, fleet_run
    trainer, _, _ = fleet_run(tmp_path_factory.mktemp("looplm"),
                              config(layers=1, steps=2), passes=1,
                              trainer_cls=SparseTrainer)
    return trainer.step_scopes()


def under(scopes: dict, name: str):
    """The op_names in which ``name`` is a path element, bare or inside a
    transform's brackets (the readers' rule)."""
    element = re.compile(r"(^|[/(])" + re.escape(name) + r"([/)]|$)")
    return [op for op in scopes.values() if element.search(op)]


# -- the names are in the compiled step --------------------------------------

@pytest.mark.parametrize("scope", STEP_SCOPES)
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_pooled_step_names_every_scope(pooled_steps, geometry, scope):
    assert under(pooled_steps[geometry][2], scope), scope


@pytest.mark.parametrize("half,marks", [
    ("forward", ("dense.tower/jvp(",)),
    ("backward", ("dense.tower/transpose(jvp(",))])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_dense_tower_holds_the_forward_and_the_backward(pooled_steps,
                                                       geometry, half,
                                                       marks):
    """``value_and_grad`` runs inside the scope, so the name is a bare
    path element before the transform's own."""
    ops = under(pooled_steps[geometry][2], "dense.tower")
    assert any(all(m in op for m in marks) for op in ops), (half, ops[:5])


@pytest.mark.parametrize("scope", ROW_SCOPES)
def test_row_model_step_names_every_scope(row_model_scopes, scope):
    assert under(row_model_scopes, scope), scope


def test_row_model_pull_and_push_hold_the_shared_paths_scopes(
        row_model_scopes):
    """``seq.pull`` / ``seq.push`` stay and hold the shared path's names
    as inner path elements."""
    assert any("seq.pull/ps.pull.table/" in op
               for op in row_model_scopes.values())
    assert any("seq.push/ps.push.rule/" in op
               for op in row_model_scopes.values())


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_pooled_step_text_is_the_parents(pooled_steps, geometry):
    text = pooled_steps[geometry][1]
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[geometry]


# -- printing the step again ---------------------------------------------------

def test_step_is_printed_from_abstract_arguments(pooled_steps):
    trainer, text, scopes = pooled_steps["deepfm"]
    leaves = jax.tree.leaves(trainer._packed_step_args)
    assert leaves and all(isinstance(a, jax.ShapeDtypeStruct)
                          for a in leaves)
    # the same program as the one lowered from the live arrays
    assert trainer.step_lowered().as_text() == text
    assert scopes and all(isinstance(k, str) and isinstance(v, str)
                          for k, v in scopes.items())


def test_step_arguments_follow_a_rebuild():
    """A feed of another geometry rebuilds the step; what is printed is
    the step that ran last."""
    caps = GEOMETRIES["deepfm"]
    trainer = pooled_trainer(caps)
    with pytest.raises(ValueError, match="no packed step"):
        trainer.step_lowered()
    train_one_pass(trainer, caps, 2)
    first = trainer._packed_step_args
    assert first[5]["indices"].shape[0] == 2
    train_one_pass(trainer, caps, 3)
    assert trainer._packed_step_args is not first
    assert trainer._packed_step_args[5]["indices"].shape[0] == 3
    assert "3x" in trainer.step_lowered().as_text()


def test_instruction_scopes_reads_compiled_text():
    text = (
        'HloModule jit_step\n\n'
        '%fused (p: f32[8]) -> f32[8] {\n'
        '  ROOT %add.1 = f32[8]{0} add(%p, %p), '
        'metadata={op_name="jit(step)/dense.adam/add"}\n}\n\n'
        'ENTRY %main {\n'
        '  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused, '
        'metadata={op_name="jit(step)/ps.pull.table/scatter" '
        'source_file="x.py" source_line=3}\n'
        '  %copy.3 = f32[8]{0} copy(%fusion.2)\n'
        '  ROOT %while.4 = (s32[]) while(%t), condition=%c, body=%b, '
        'metadata={op_name="jit(step)/jvp(tower.ut)/while"}\n}\n')
    assert trace.instruction_scopes(text) == {
        "add.1": "jit(step)/dense.adam/add",
        "fusion.2": "jit(step)/ps.pull.table/scatter",
        "while.4": "jit(step)/jvp(tower.ut)/while"}


# -- one closed table ---------------------------------------------------------

def package_sources():
    for base, _, names in os.walk(PKG):
        for n in names:
            if n.endswith(".py"):
                path = os.path.join(base, n)
                with open(path) as f:
                    yield os.path.relpath(path, PKG), f.read()


def test_no_second_scope_site():
    """``jax.named_scope`` is entered in utils/trace.py and nowhere else
    in the package, and every literal handed to ``device_scope`` is in
    the table."""
    hits, literals = [], set()
    for rel, text in package_sources():
        if "named_scope" in text:
            hits.append(rel)
        literals.update(re.findall(r'device_scope\("([^"]+)"\)', text))
    assert hits == [os.path.join("utils", "trace.py")]
    assert literals and literals <= set(trace.DEVICE_SCOPES)


def test_a_name_outside_the_table_is_refused():
    with pytest.raises(ValueError, match="DEVICE_SCOPES"):
        trace.device_scope("ps.pull.everything")


@pytest.mark.parametrize("scope", trace.DEVICE_SCOPES)
def test_every_name_of_the_table_is_used(scope):
    """As a literal, or as ``"tower." + kind`` in a model that loops over
    its layers' kinds and names this one."""
    sources = dict(package_sources())
    if any(f'device_scope("{scope}")' in text for text in sources.values()):
        return
    assert scope.startswith("tower.")
    kind = re.compile(r"\b" + scope[len("tower."):] + r"\b")
    assert any('device_scope("tower." +' in text and kind.search(text)
               for rel, text in sources.items()
               if rel.startswith("models")), scope


@pytest.mark.parametrize("program", trace.DEVICE_PROGRAMS)
def test_device_programs_are_the_feeds_jits(program):
    fn = getattr(pass_feed, program[len("jit_"):])
    assert hasattr(fn, "lower") and "jit_" + fn.__name__ == program


def test_perf_md_names_every_step_scope_and_both_tables():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    missing = [n for n in STEP_SCOPES + ("DEVICE_SCOPES", "DEVICE_PROGRAMS")
               if f"`{n}`" not in text]
    assert not missing, missing
