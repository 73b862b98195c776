"""The step's device time by the program's scope names, on hand-built
traces whose answers are worked out in the comments (nanoseconds
throughout): the module a trace carries, written here in the wire format
the reader parses, the reduction, the readers on a run without the scopes,
and the feed's programs on the ``XLA Modules`` line."""

import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import spec, step_scopes, xplane
from benchmark.harness.xplane import Event
from conftest import ROOT

STEP_METRICS = ["step.pull_table_ms", "step.pull_cross_ms",
                "step.push_cross_ms", "step.sparse_rule_ms",
                "step.dense_tower_ms", "step.dense_adam_ms",
                "step.scoped_share"]
NEW = STEP_METRICS + ["feed_build.plans_device_s_per_pass",
                      "device.step_share"]
PROGRAMS = ("jit__relayout", "jit__build_plans", "jit__build_static_planes")


# -- a trace's module, in the wire format ------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, payload):
    """A length-delimited field (a string, bytes or a message)."""
    if isinstance(payload, str):
        payload = payload.encode()
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def hlo_proto(instructions):
    """HloProto{hlo_module{computations{instructions{name, opcode,
    metadata{op_name}}}}}; an empty op_name leaves the metadata out."""
    body = b"".join(
        field(2, field(1, name) + field(2, "fusion") + varint(35 << 3)
              + varint(7)               # a varint field the reader skips
              + (field(7, field(1, "op") + field(2, op)) if op else b""))
        for name, op in instructions.items())
    return field(1, field(1, "jit_step") + field(3, field(1, "main") + body))


def xspace(programs, plane=step_scopes.METADATA_PLANE):
    """XSpace{planes{name, event_metadata{key, value{id, name,
    stats{metadata_id, bytes_value}}}}} and a device plane before it."""
    entries = b"".join(
        field(4, varint(1 << 3) + varint(k + 1)
              + field(2, varint(1 << 3) + varint(k + 1) + field(2, name)
                      + field(5, varint(1 << 3) + varint(1)
                              + field(6, hlo_proto(instructions)))))
        for k, (name, instructions) in enumerate(programs.items()))
    return field(1, field(2, "/device:TPU:0")) \
        + field(1, varint(1 << 3) + varint(9) + field(2, plane) + entries)


OPS = {
    # a step of a pooled model, as the compiler might fuse it
    "fusion.1": "jit(step)/feed.slice/dynamic_slice",
    "fusion.2": "jit(step)/ps.pull.table/scatter",
    "sorted_spmm_gather.1": "jit(step)/ps.pull.gather/pallas_call",
    "fusion.3": "jit(step)/ps.pull.cross/gather",
    "fusion.4": "jit(step)/ps.pull.pool/reduce_sum",
    "while.5": "jit(step)/dense.tower/jvp()/while",
    "fusion.6": "jit(step)/dense.tower/jvp()/while/body/dot_general",
    "fusion.7": "jit(step)/dense.tower/transpose(jvp())/dot_general",
    "fusion.8": "jit(step)/dense.adam/add",
    "fusion.9": "jit(step)/ps.push.cross/gather",
    "fusion.10": "jit(step)/seq.push/ps.push.rule/seq.head_push/gather",
    "fusion.11": "jit(step)/ps.push.rule/mul",
    "copy.12": ""}           # the compiler's own: no op_name, no owner


def ev(name, a, b):
    return Event(name, float(a), float(b), name)


def traced_run(tmp_path, programs, modules=None, ops=None, stats=None,
               units=2):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace(programs))
    trace = {
        "/device:TPU:0": {
            xplane.OPS_LINE: ops if ops is not None else [
                ev("fusion.1", 100, 110),
                ev("fusion.2", 110, 150),                # table 40
                ev("sorted_spmm_gather.1", 150, 200),
                ev("fusion.3", 200, 300),                # cross ...
                ev("fusion.4", 280, 320),                # ... pool: 120
                ev("while.5", 320, 400),                 # tower: the loop
                ev("fusion.6", 330, 390),                # and its body
                ev("fusion.7", 400, 420),                # backward: 100
                ev("fusion.8", 420, 450),                # adam 30
                ev("fusion.9", 450, 500),                # push cross 50
                ev("fusion.10", 500, 520),               # head merge ...
                ev("fusion.11", 520, 560),               # ... rule 60
                ev("copy.12", 560, 600),                 # unowned 40
                ev("fusion.2", 950, 1050),   # a run cut by the window
                ev("fusion.2", 1500, 1540)],             # outside it
            xplane.MODULES_LINE: modules if modules is not None else [
                ev("jit_step(7)", 100, 600),
                ev("jit_step(7)", 900, 1100)]},     # not wholly inside
        xplane.HOST_PLANE: {"main": [ev("bench.window", 0, 1000)]}}
    run = types.SimpleNamespace(
        measured=types.SimpleNamespace(trace_file=str(path)), trace=trace,
        trace_window=(0.0, 1000.0), chips=1, stats=stats or {},
        units=[object()] * units)
    run.step_runs = lambda plane: xplane.module_runs(
        trace, plane, run.trace_window, "jit_step")
    return run


def reader(name):
    return spec.load_module("layer_metrics", name).read


def test_the_module_is_read_from_the_traces_metadata_plane():
    raw = xspace({"jit_step(7)": OPS, "jit__relayout(3)": {"copy.1": "x"}})
    got = step_scopes.programs(raw)
    assert set(got) == {"jit_step(7)", "jit__relayout(3)"}
    assert got["jit_step(7)"] == {k: v for k, v in OPS.items() if v}
    # the same bytes under another plane's name are not a module
    assert step_scopes.programs(xspace({"jit_step(7)": OPS},
                                       plane="/host:CPU")) == {}
    assert step_scopes.programs(b"") == {}


# one run of 500 ns lies wholly in the window, so a scope's nanoseconds
# in it are its milliseconds a step times 1e6
@pytest.mark.parametrize("name,want_ns", [
    ("step.pull_table_ms", 40),
    ("step.pull_cross_ms", 120),    # [200,300] + [280,320] united
    ("step.push_cross_ms", 50),
    ("step.sparse_rule_ms", 60),    # the head's merge inside the rule
    ("step.dense_tower_ms", 100),   # while + body once, and the backward
    ("step.dense_adam_ms", 30)])
def test_a_scopes_time_a_step_on_a_hand_built_trace(tmp_path, name,
                                                    want_ns):
    run = traced_run(tmp_path, {"jit_step(7)": OPS})
    assert reader(name)(run) == pytest.approx(want_ns / 1e6)


def test_scoped_share_is_the_steps_time_under_any_name(tmp_path):
    run = traced_run(tmp_path, {"jit_step(7)": OPS})
    # all of [100,560] but the gather kernel's scope counts too: the
    # unowned copy's 40 of the 500 are what is missing
    assert reader("step.scoped_share")(run) == pytest.approx(92.0)


def test_the_unowned_instructions_are_listed_by_time(tmp_path):
    run = traced_run(tmp_path, {"jit_step(7)": OPS})
    assert step_scopes.longest(run) == [
        ("copy.12", "", pytest.approx(40 / 1e6))]
    # and a scope's own, each instruction with its own time
    assert step_scopes.longest(run, ("dense.tower",), 2) == [
        ("while.5", OPS["while.5"], pytest.approx(80 / 1e6)),
        ("fusion.6", OPS["fusion.6"], pytest.approx(60 / 1e6))]
    # a loop outside every scope whose body is inside one: its time that
    # the body does not cover, [320,330] + [390,400]
    loop_outside = dict(OPS, **{"while.5": "jit(step)/jvp()/while"})
    other = tmp_path / "loop"       # a trace is read once a path
    other.mkdir()
    run = traced_run(other, {"jit_step(7)": loop_outside})
    assert step_scopes.longest(run, n=1) == [
        ("copy.12", "", pytest.approx(40 / 1e6))]
    assert step_scopes.longest(run)[1] == (
        "while.5", "jit(step)/jvp()/while", pytest.approx(20 / 1e6))


def test_each_run_is_read_by_its_own_programs_map(tmp_path):
    """Two step programs in one trace (a rebuild inside it) both have a
    ``fusion.2``; a run's operations are named by the program it ran."""
    other = {"fusion.2": "jit(step)/dense.adam/add",
             "fusion.3": "jit(step)/ps.pull.table/scatter"}
    run = traced_run(
        tmp_path, {"jit_step(7)": OPS, "jit_step(8)": other},
        modules=[ev("jit_step(7)", 100, 400), ev("jit_step(8)", 500, 900)],
        ops=[ev("fusion.2", 100, 140), ev("fusion.3", 200, 300),
             ev("fusion.2", 500, 600), ev("fusion.3", 600, 620)])
    # table: 40 in the first run + 20 in the second, over two runs
    assert reader("step.pull_table_ms")(run) == pytest.approx(30 / 1e6)
    assert reader("step.dense_adam_ms")(run) == pytest.approx(50 / 1e6)


@pytest.mark.parametrize("name", STEP_METRICS)
def test_a_step_from_another_trees_cache_reads_none(tmp_path, name):
    """The compile cache keys no metadata, so a step another tree
    compiled comes back with that tree's names: the parent's row-model
    scopes and none of the table's.  No part is guessed."""
    stale = {"fusion.2": "jit(step)/seq.pull/scatter",
             "fusion.3": "jit(step)/jvp(tower.ut)/while",
             "fusion.9": "jit(step)/seq.push/gather", "copy.12": ""}
    run = traced_run(tmp_path, {"jit_step(7)": stale})
    if name == "step.scoped_share":
        # the families' names are the parent's too: what they cover is
        # true of the executable that ran
        assert reader(name)(run) == pytest.approx(100.0 * 190 / 500)
    else:
        assert reader(name)(run) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_return_none_without_a_trace_or_a_module(tmp_path, name):
    bare = types.SimpleNamespace(
        measured=types.SimpleNamespace(), trace=None, trace_window=None,
        chips=1, stats={}, units=[])
    assert reader(name)(bare) is None
    if name in STEP_METRICS:
        # a trace that carries no module, and one whose module is not
        # the step's
        assert reader(name)(traced_run(tmp_path, {})) is None
        assert reader(name)(traced_run(
            tmp_path, {"jit__relayout(3)": OPS})) is None


@pytest.mark.parametrize("name", ["step.scoped_share",
                                  "feed_build.plans_device_s_per_pass"])
def test_a_program_without_the_tables_leaves_the_metric_out(
        tmp_path, monkeypatch, name):
    """The parent of the PR that added ``DEVICE_SCOPES`` /
    ``DEVICE_PROGRAMS``: the reader asks the program, finds no table and
    returns nothing."""
    from paddlebox_tpu.utils import trace
    monkeypatch.delattr(trace, "DEVICE_SCOPES")
    monkeypatch.delattr(trace, "DEVICE_PROGRAMS")
    run = traced_run(tmp_path, {"jit_step(7)": OPS}, modules=[
        ev("jit_step(7)", 100, 600), ev("jit__build_plans(2)", 600, 700)])
    assert reader(name)(run) is None


def test_the_feeds_programs_are_counted_inside_the_window(tmp_path):
    from paddlebox_tpu.utils import trace
    assert trace.DEVICE_PROGRAMS == PROGRAMS
    modules = [ev("jit__relayout(1)", -50, 30),          # 30 inside
               ev("jit__build_plans(2)", 30, 130),       # 100
               ev("jit__build_static_planes(3)", 130, 150),     # 20
               ev("jit_step(7)", 150, 650),
               ev("jit_convert_element_type(4)", 650, 700),     # not ours
               ev("jit__build_plans(2)", 960, 1040)]     # 40 inside
    run = traced_run(tmp_path, {"jit_step(7)": OPS}, modules=modules,
                     stats={"data.prefetch.passes": 2.0})
    assert reader("feed_build.plans_device_s_per_pass")(run) == \
        pytest.approx(190e-9 / 2)
    run.stats = {}                      # no prefetcher: the run's units
    run.units = [object()]
    assert reader("feed_build.plans_device_s_per_pass")(run) == \
        pytest.approx(190e-9)
    assert step_scopes.seconds_by_program(
        run.trace, "/device:TPU:0", run.trace_window) == pytest.approx({
            "jit__relayout": 30e-9, "jit__build_plans": 140e-9,
            "jit__build_static_planes": 20e-9, "jit_step": 500e-9,
            "jit_convert_element_type": 50e-9})


def test_step_share_is_the_steps_runs_over_the_busy_time(tmp_path):
    run = traced_run(tmp_path, {"jit_step(7)": OPS})
    # busy in the window: [100,600] + [950,1000] = 550; the one run that
    # lies wholly inside is 500
    assert reader("device.step_share")(run) == pytest.approx(
        100.0 * 500 / 550)


def test_the_new_metrics_are_entries_with_readers_and_cells():
    bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py")), name
        e = entries[name]
        assert e["source"] == "device_trace"
        assert set(e["workloads"]) <= set(cells)
    ctr = [c for c in cells if c.startswith(("deepfm_", "widedeep_"))]
    assert entries["step.dense_tower_ms"]["workloads"] == ctr
    assert entries["feed_build.plans_device_s_per_pass"]["workloads"] == \
        ["deepfm_criteo.stream"]
    assert entries["feed_build.plans_device_s_per_pass"]["moves"] == \
        "pass_turnaround_s"
    assert all(entries[n]["workloads"] == cells for n in NEW
               if n not in ("step.dense_tower_ms",
                            "feed_build.plans_device_s_per_pass"))


def test_a_real_trace_carries_the_steps_module(tmp_path):
    """A real ``.xplane.pb`` written here on the CPU: the program's
    scopes are in the module its metadata plane holds, under the name the
    run has."""
    code = (
        "import jax, sys\n"
        "from paddlebox_tpu.utils import trace\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    with trace.device_scope('dense.tower'):\n"
        "        y, g = jax.value_and_grad(lambda x: (x * x).sum())(x)\n"
        "    with trace.device_scope('dense.adam'):\n"
        "        return x - 0.1 * g\n"
        "x = jax.numpy.ones((64, 64))\n"
        "step(x).block_until_ready()\n"
        "jax.profiler.start_trace(sys.argv[1])\n"
        "step(x).block_until_ready()\n"
        "jax.profiler.stop_trace()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    subprocess.run([sys.executable, "-c", code, str(tmp_path)], env=env,
                   check=True, timeout=300)
    found = [os.path.join(base, f) for base, _, files in os.walk(tmp_path)
             for f in files if f.endswith(".xplane.pb")]
    assert len(found) == 1
    with open(found[0], "rb") as f:
        got = step_scopes.programs(f.read())
    steps = [ops for name, ops in got.items() if name.startswith("jit_step")]
    assert len(steps) == 1
    assert step_scopes.instructions_under(steps[0], ("dense.adam",))
    assert step_scopes.instructions_under(steps[0], ("dense.tower",))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "device_by_program.py"), found[0]],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1 and "nothing ran on a chip" in proc.stderr
