"""The program's one span primitive (``utils/trace.span``) and the spans
of the pass loop: a span counts into ``<name>_s`` with no flag, rings
when ``obs_trace`` is on, and lies in any ``jax.profiler`` trace as
``pbx:<name>`` on the device's clock.  Nothing here asserts a duration.
"""

import os
import threading

import numpy as np
import pytest

from paddlebox_tpu import fleet
from paddlebox_tpu.config import (DataFeedConfig, EmbeddingTableConfig,
                                  SlotConfig, SparseSGDConfig)
from paddlebox_tpu.models.deepfm import DeepFM
from paddlebox_tpu.ps.pass_manager import BoxPSEngine
from paddlebox_tpu.trainer.trainer import SparseTrainer
from paddlebox_tpu.utils import trace
from paddlebox_tpu.utils.monitor import StatRegistry, stat_snapshot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the pass loop's spans: span -> the span it lies inside in time (None:
# outermost on its thread).  PERF.md section 3 names each of them.
PASS_LOOP_SPANS = {
    "data.prefetch.gate_wait": None,
    "data.prefetch.build": None,
    "data.load_into_memory": "data.prefetch.build",
    "data.read.lines": "data.load_into_memory",
    "data.read.parse": "data.load_into_memory",
    "data.read.key_tap": "data.load_into_memory",
    "ps.engine.dedup_keys": "data.prefetch.build",
    "ps.engine.pull": "data.prefetch.build",
    "ps.engine.wait_build": "data.prefetch.build",
    "trainer.pack_pass_host": "data.prefetch.build",
    "data.prefetch.wait": None,
    "ps.engine.begin_pass": None,
    "ps.engine.upload_ws": "ps.engine.begin_pass",
    "ps.engine.refresh_stale": "ps.engine.begin_pass",
    "trainer.finish_pass_feed": None,
    "data.feed.upload_enqueue": "trainer.finish_pass_feed",
    "data.feed.plans": "trainer.finish_pass_feed",
    "trainer.train_pass": None,
    "trainer.dispatch_steps": "trainer.train_pass",
    "trainer.readback": "trainer.train_pass",
    "ps.engine.end_pass": None,
    "ps.engine.dump_to_cpu": "ps.engine.end_pass",
    "ps.engine.end_pass_write": "ps.engine.end_pass",
}
READER_SPANS = ("data.read.lines", "data.read.parse", "data.read.key_tap")


@pytest.fixture(autouse=True)
def _clean():
    StatRegistry.instance().reset()
    trace.disable()
    yield
    trace.disable()


def test_span_counts_with_the_tracer_off():
    assert trace.ACTIVE is None
    for k in range(3):
        with trace.span("t.unit.off", step=k) as s:
            assert s is None
        assert stat_snapshot("t.unit")["t.unit.off_s.count"] == k + 1
    assert stat_snapshot("t.unit")["t.unit.off_s.sum"] >= 0.0


def test_span_counts_when_its_body_raises():
    with pytest.raises(KeyError):
        with trace.span("t.unit.raises"):
            raise KeyError("x")
    assert stat_snapshot("t.unit")["t.unit.raises_s.count"] == 1


def test_span_rings_and_nests_with_the_tracer_on():
    tr = trace.enable(ring=16)
    with trace.span("t.unit.parent", pass_id=7) as parent:
        with trace.span("t.unit.child") as child:
            assert child.parent_id == parent.span_id
            assert trace.wire_context() == child.context()
    ring = {s["name"]: s for s in tr.spans()}
    assert ring["t.unit.child"]["trace_id"] == \
        ring["t.unit.parent"]["trace_id"]
    assert ring["t.unit.parent"]["attrs"] == {"pass_id": 7}
    snap = stat_snapshot("t.unit")
    assert snap["t.unit.parent_s.count"] == snap["t.unit.child_s.count"] == 1


def test_span_decorates_a_function():
    @trace.span("t.unit.decorated")
    def work(x):
        return x + 1

    assert [work(1), work(2)] == [2, 3]
    assert stat_snapshot("t.unit")["t.unit.decorated_s.count"] == 2


def test_profiler_aliases_are_the_span():
    from paddlebox_tpu.utils import profiler
    assert profiler.RecordEvent is trace.span
    assert profiler.annotate is trace.span


def _write_slot_file(path, rng, n):
    with open(path, "w") as f:
        for _ in range(n):
            parts = [f"1 {rng.integers(0, 2)}",
                     "3 " + " ".join(f"{rng.normal():.4f}"
                                     for _ in range(3))]
            for _s in range(4):
                k = rng.integers(1, 4)
                parts.append(f"{k} " + " ".join(
                    str(rng.integers(1, 500)) for _ in range(k)))
            f.write(" ".join(parts) + "\n")


def _events(xplane_file):
    """{span name: [(line index, start ns, end ns)]} of the ``pbx:``
    events on ``/host:CPU``."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(xplane_file).planes:
        if plane.name != "/host:CPU":
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(trace.ANNOTATION_PREFIX):
                    out.setdefault(
                        ev.name[len(trace.ANNOTATION_PREFIX):], []).append(
                        (k, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Two tiny passes through ``fleet.train_passes`` with prefetch on,
    under a ``jax.profiler`` session and with the ring on."""
    import jax
    tmp = tmp_path_factory.mktemp("spans")
    StatRegistry.instance().reset()
    cfg = DataFeedConfig(slots=tuple(
        [SlotConfig("label", dtype="float", is_dense=True, dim=1),
         SlotConfig("dense0", dtype="float", is_dense=True, dim=3)]
        + [SlotConfig(f"s{i}", slot_id=100 + i, capacity=3)
           for i in range(4)]))
    files = []
    for p in range(2):
        paths = [str(tmp / f"p{p}-{k}.txt") for k in range(2)]
        for k, path in enumerate(paths):
            _write_slot_file(path, np.random.default_rng(10 * p + k), 48)
        files.append(paths)
    eng = BoxPSEngine(EmbeddingTableConfig(
        embedding_dim=4, shard_num=4,
        sgd=SparseSGDConfig(mf_create_thresholds=0.0)), seed=0)
    ds = fleet.BoxPSDataset(cfg, engine=eng, read_threads=2)
    readers = set()         # (ident, name) of the threads that tap keys
    ds.dataset.register_key_consumer(lambda keys: readers.add(
        (threading.get_ident(), threading.current_thread().name)))
    model = DeepFM(num_slots=4, emb_width=3 + 4, dense_dim=3, hidden=(8,))
    tr = SparseTrainer(eng, model, cfg, batch_size=32, seed=0,
                       sparse_path="fast")
    tracer = trace.enable(ring=4096)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=options)
    try:
        metrics = fleet.train_passes(tr, ds, files, date="20260801",
                                     prefetch=True)
    finally:
        jax.profiler.stop_trace()
        trace.disable()
    found = [os.path.join(base, f) for base, _, names in os.walk(tmp)
             for f in names if f.endswith(".xplane.pb")]
    assert len(metrics) == 2 and len(found) == 1
    return {"events": _events(found[0]), "ring": tracer.spans(),
            "stats": stat_snapshot(), "readers": readers,
            "main": threading.get_ident()}


def test_every_pass_loop_span_is_in_the_profilers_trace(traced_run):
    missing = set(PASS_LOOP_SPANS) - set(traced_run["events"])
    assert not missing, missing


def test_every_pass_loop_span_counts_with_no_flag(traced_run):
    stats = traced_run["stats"]
    for name in PASS_LOOP_SPANS:
        assert stats.get(name + "_s.count", 0) >= 1, name
    # once a pass, on the thread that says so
    for name in ("data.prefetch.wait", "data.prefetch.build",
                 "trainer.pack_pass_host", "ps.engine.end_pass",
                 "trainer.train_pass", "ps.engine.dedup_keys"):
        assert stats[name + "_s.count"] == 2, name
    # what the spans replaced is gone
    for gone in ("data.pass_feed.upload_s.count",
                 "ps.engine.build_pull_s.count",
                 "ps.engine.end_pass_write_s"):
        assert gone not in stats
    assert stats["ps.engine.build_pull_s"] > 0      # the flat counter stays


def test_each_child_lies_inside_its_parent(traced_run):
    events = traced_run["events"]
    for child, parent in PASS_LOOP_SPANS.items():
        if parent is None:
            continue
        for _, a, b in events[child]:
            assert any(pa <= a and b <= pb
                       for _, pa, pb in events[parent]), (child, parent)


def test_reader_spans_run_on_the_reader_threads(traced_run):
    events, ring = traced_run["events"], traced_run["ring"]
    # in the profiler's trace: on other lines than the training thread's
    # and the prefetch worker's
    main_lines = {k for k, _, _ in events["trainer.train_pass"]}
    worker_lines = {k for k, _, _ in events["data.prefetch.build"]}
    assert len(main_lines) == len(worker_lines) == 1
    assert main_lines != worker_lines
    for name in READER_SPANS:
        lines = {k for k, _, _ in events[name]}
        assert lines and not lines & (main_lines | worker_lines), name
    # in the ring: on threads called pbox-read*
    readers = traced_run["readers"]
    assert readers and all(n.startswith("pbox-read") for _, n in readers)
    idents = {i for i, _ in readers}
    for s in ring:
        if s["name"] in READER_SPANS:
            assert s["tid"] in idents and s["tid"] != traced_run["main"]
    ring_names = {s["name"] for s in ring}
    assert set(PASS_LOOP_SPANS) <= ring_names


def test_ring_children_link_to_their_parents_on_one_thread(traced_run):
    by_id = {s["span_id"]: s for s in traced_run["ring"]}
    for s in traced_run["ring"]:
        parent = PASS_LOOP_SPANS.get(s["name"])
        same_thread = s["name"] not in READER_SPANS \
            and s["name"] != "ps.engine.pull"
        if parent is not None and same_thread:
            assert by_id[s["parent_id"]]["name"] == parent, s["name"]


def test_perf_md_names_every_span():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    missing = [n for n in PASS_LOOP_SPANS if f"`{n}`" not in text]
    assert not missing, missing


def test_no_second_annotation_site():
    """``jax.profiler.TraceAnnotation`` is entered in utils/trace.py and
    nowhere else in the package."""
    hits = []
    pkg = os.path.join(ROOT, "paddlebox_tpu")
    for base, _, names in os.walk(pkg):
        for n in names:
            if n.endswith(".py"):
                with open(os.path.join(base, n)) as f:
                    if "TraceAnnotation" in f.read():
                        hits.append(os.path.relpath(
                            os.path.join(base, n), pkg))
    assert hits == [os.path.join("utils", "trace.py")]


# -- a sequence model's spans, scopes and counters (models/looplm.py) -------

def test_sequence_tower_spans_scopes_and_counters(tmp_path):
    """What a pass of the looped language model leaves behind: the host
    span of the key plane, the ``tower.*`` counters, and the named scopes
    of the step that a device trace is read by."""
    import jax
    from looplm_fixture import BATCH, CAP, config, fleet_run
    from paddlebox_tpu.trainer.trainer import SparseTrainer
    cfg = config(layers=1, steps=2)
    trainer, metrics, engine = fleet_run(tmp_path, cfg, passes=1,
                                         trainer_cls=SparseTrainer)
    stats = stat_snapshot()
    assert stats["data.feed.seq_keys_s.count"] == 1
    steps = metrics[0]["batches"]
    assert stats["tower.recurrent_steps"] == 2 * steps
    assert stats["tower.tokens_valid"] + stats["tower.tokens_padded"] == \
        steps * BATCH * CAP
    assert 0 < stats["tower.tokens_valid"] < steps * BATCH * CAP
    assert 1.0 <= stats["tower.exit_expected_step"] <= 2.0
    # the jitted function keeps its name, the scopes are in its lowering
    assert trainer._packed_step_fn.__name__ == "step"
    engine.begin_feed_pass()
    engine.add_keys(np.arange(1, 9, dtype=np.uint64))
    engine.end_feed_pass()
    engine.begin_pass()
    i32 = np.int32
    data = {"indices": np.zeros((1, 1, CAP, BATCH), i32),
            "lengths": np.full((1, 1, BATCH), 3, i32),
            "dense": np.zeros((1, BATCH, 1), np.float32),
            "labels": np.zeros((1, BATCH), np.float32),
            "valid": np.ones((1, BATCH), bool),
            "seq_keys": np.ones((1, BATCH, CAP), i32)}
    from paddlebox_tpu.ps import mxu_path
    core = trainer._make_core("mxu")
    plan = mxu_path.build_plan(
        data["indices"][0], mxu_path.make_dims(
            CAP * BATCH, engine.ws["show"].shape[0]))
    text = jax.jit(lambda ws, p, o, a: core(
        ws, p, o, a, data["indices"][0], data["lengths"][0],
        data["dense"][0], data["labels"][0], data["valid"][0], plan,
        {"seq_keys": data["seq_keys"][0]})).lower(
        engine.ws, trainer.params, trainer.opt_state, trainer.auc_state
    ).as_text(debug_info=True)
    for scope in ("seq.pull", "tower.ut", "tower.head_loss", "seq.push",
                  "dense.adam"):
        assert scope in text, scope
    engine.end_pass()
