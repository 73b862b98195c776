"""The program's spans in the benchmark: attribution of the chip's idle
time on a hand-built trace whose answers are worked out in the comments
(nanoseconds throughout), the readers on a run without the stats, and
the tool."""

import os
import subprocess
import sys

import pytest

from benchmark.harness import program_spans, spec, xplane
from benchmark.harness.xplane import Event
from conftest import ROOT

NEW = ["read_parse.lines_thread_s_per_pass",
       "read_parse.parse_thread_s_per_pass",
       "read_parse.key_tap_thread_s_per_pass",
       "ps_engine.dedup_s_per_pass", "feed_build.pack_s_per_pass",
       "feed_build.finish_s_per_pass", "pass_loop.exposed_wait_s_per_pass",
       "device.idle_attributed_share"]
WIN = (0.0, 1000.0)


def ev(name, a, b):
    return Event(name, float(a), float(b), name)


@pytest.fixture
def device():
    # busy [300,400] and [600,900]; idle [0,300] + [400,600] + [900,1000]
    return {"/device:TPU:0": {xplane.OPS_LINE: [ev("fusion.1", 300, 400),
                                                ev("fusion.2", 600, 900)]}}


@pytest.fixture
def lines():
    return program_spans.owners({
        program_spans.MAIN: [
            ev("bench.window", 0, 1000),
            ev("pbx:data.prefetch.wait", 0, 250),        # transparent
            ev("pbx:ps.engine.begin_pass", 250, 300),
            ev("pbx:trainer.train_pass", 300, 950),
            ev("pbx:trainer.readback", 900, 950)],
        # the prefetch worker, the build thread and two reader threads,
        # as one line
        program_spans.WORKERS: [
            ev("pbx:data.prefetch.build", 0, 240),
            ev("pbx:data.load_into_memory", 0, 100),
            ev("pbx:data.read.lines", 0, 60),            # concurrent: owns
            ev("pbx:data.read.parse", 10, 30),           # nothing
            ev("pbx:ps.engine.wait_build", 150, 200),    # transparent
            ev("pbx:ps.engine.pull", 140, 200),
            ev("pbx:data.prefetch.build", 420, 500)]})


def test_waits_and_reader_chunks_own_nothing(lines):
    names = {e.name for events in lines.values() for e in events}
    assert not names & set(program_spans.WAITS)
    assert not any(n.startswith(program_spans.CONCURRENT) for n in names)
    assert "bench.window" in names and "pbx:ps.engine.pull" in names


def test_a_gap_under_a_wait_goes_to_the_workers_innermost_span(device, lines):
    got = program_spans.idle_by_span(device, lines, WIN)
    # main first, innermost first: readback [900,950] -> 50;
    # begin_pass [250,300] -> 50; train_pass [300,950] minus readback:
    # [400,600] -> 200.  Main's wait [0,250] is dropped, so [0,250] falls
    # to the workers, shortest first: pull [140,200] -> 60 (the worker's
    # wait_build inside it is dropped); load_into_memory [0,100] -> 100
    # (the reader chunks inside it own nothing); prefetch.build [0,240]
    # takes what is left of it, [100,140] + [200,240] -> 80; its second
    # run [420,500] finds the gap taken by main.  Nothing covers
    # [240,250] and [950,1000] -> 60.  Together the 600 idle.
    assert got == pytest.approx({
        "pbx:trainer.readback": 50e-9, "pbx:ps.engine.begin_pass": 50e-9,
        "pbx:trainer.train_pass": 200e-9, "pbx:ps.engine.pull": 60e-9,
        "pbx:data.load_into_memory": 100e-9,
        "pbx:data.prefetch.build": 80e-9, "unattributed": 60e-9})
    assert sum(got.values()) == pytest.approx(600e-9)
    assert program_spans.attributed_share(got) == pytest.approx(90.0)


def test_a_gap_under_no_span_lowers_the_share(device, lines):
    lines[program_spans.WORKERS] = []
    got = program_spans.idle_by_span(device, lines, WIN)
    # [0,250] and [950,1000] are now under nothing: 300 of the 600 idle
    assert got["unattributed"] == pytest.approx(300e-9)
    assert program_spans.attributed_share(got) == pytest.approx(50.0)


def test_no_idle_and_no_chip_give_no_share(lines):
    assert program_spans.idle_by_span({}, lines, WIN) == {}
    assert program_spans.attributed_share({}) is None
    busy = {"/device:TPU:0": {xplane.OPS_LINE: [ev("fusion.1", 0, 1000)]}}
    assert program_spans.attributed_share(
        program_spans.idle_by_span(busy, lines, WIN)) is None


class RunWithout:
    """A run of a program that has none of the spans: the parent."""
    stats = {"data.prefetch.passes": 2.0, "ps.engine.build_pull_s": 1.0}
    units = [object(), object()]
    trace = None
    trace_window = None


def test_readers_return_none_without_the_stats():
    for name in NEW:
        read = spec.load_module("layer_metrics", name).read
        assert read(RunWithout()) is None, name


def test_per_pass_reads_the_span_histogram():
    run = RunWithout()
    run.stats = {"data.prefetch.passes": 2.0, "data.read.lines_s.sum": 50.0,
                 "data.read.lines_s.count": 960.0}
    assert program_spans.per_pass(run, "data.read.lines") == 25.0
    run.stats = {"trainer.pack_pass_host_s.sum": 6.0}     # no prefetcher:
    assert program_spans.per_pass(run, "trainer.pack_pass_host") == 3.0


def test_the_new_metrics_are_entries_of_the_benchmark():
    entries = {m["name"]: m for m in spec.load_json(
        os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]}
    for name in NEW:
        assert name in entries
    assert all(entries[n]["workloads"] == ["deepfm_criteo.stream"]
               and entries[n]["source"] == "program_span" for n in NEW[:-1])
    assert "workloads" not in entries["device.idle_attributed_share"]


def test_tool_refuses_a_trace_without_a_chip(tmp_path):
    """A real ``.xplane.pb`` written here on the CPU holds the program's
    spans and no device plane: the tool reads it and says so."""
    code = (
        "import jax, sys\n"
        "from paddlebox_tpu.utils import trace\n"
        "jax.profiler.start_trace(sys.argv[1])\n"
        "with trace.span('ps.engine.pull'):\n"
        "    jax.numpy.ones(8).block_until_ready()\n"
        "jax.profiler.stop_trace()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    subprocess.run([sys.executable, "-c", code, str(tmp_path)], env=env,
                   check=True, timeout=300)
    found = [os.path.join(base, f) for base, _, files in os.walk(tmp_path)
             for f in files if f.endswith(".xplane.pb")]
    assert len(found) == 1
    lines = program_spans.host_lines(found[0])
    assert [e.name for e in lines[program_spans.WORKERS]] == \
        ["pbx:ps.engine.pull"]         # no bench.window: no main line
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "idle_by_span.py"), found[0]],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1 and "nothing ran on a chip" in proc.stderr
