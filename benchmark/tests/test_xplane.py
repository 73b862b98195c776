"""The trace reduction on a hand-built trace whose answers are worked out
by hand in the comments (nanoseconds throughout)."""

import pytest

from benchmark.harness import xplane
from benchmark.harness.xplane import Event


def ev(text, a, b):
    return Event(xplane.op_name(text), float(a), float(b), text)


@pytest.fixture
def trace():
    ops = [
        ev("fusion.1", 100, 200),
        # as the profiler names ops: the whole instruction.  The kernel is
        # found by its own name; its consumer names it as an operand and
        # must not be taken for it
        ev("%sorted_spmm_gather.1 = f32[12,512]{1,0} custom-call(s32[3]{0} "
           "%copy-done.4), custom_call_target=\"tpu_custom_call\"", 200, 350),
        ev("%copy.2 = f32[512,12]{0,1} copy(f32[12,512]{1,0} "
           "%sorted_spmm_gather.1)", 220, 300),  # nested: union, not sum
        ev("all-gather.1", 400, 500),            # synchronous, alone
        ev("all-reduce-start.2", 600, 610),      # asynchronous pair with
        ev("fusion.3", 610, 680),                # compute under it
        ev("all-reduce-done.2", 690, 700),
        ev("fusion.9", 1200, 1300),              # outside the window
    ]
    return {
        "/device:TPU:0": {
            xplane.OPS_LINE: ops,
            xplane.MODULES_LINE: [ev("jit_step(123)", 100, 700),
                                  ev("jit__relayout(5)", 20, 60),
                                  ev("jit_step(123)", 900, 1300)]},
        xplane.HOST_PLANE: {
            "main": [ev("bench.window", 0, 1000),
                     ev("bench.train_pass", 90, 710),
                     ev("bench.end_pass", 710, 900)],
            "pbox-prefetch": [ev("bench.load_into_memory", 0, 95)]},
    }


def test_window_and_planes(trace):
    assert xplane.window(trace) == (0.0, 1000.0)
    assert xplane.device_planes(trace) == ["/device:TPU:0"]
    assert xplane.main_thread(trace) == "main"


def test_busy_is_the_union_inside_the_window(trace):
    # [100,350] + [400,500] + [600,680] + [690,700] = 440 (nothing runs
    # in [680,690]); fusion.9 is outside the window
    assert xplane.busy_seconds(trace, (0.0, 1000.0)) == [440e-9]


def test_idle_gaps(trace):
    gaps = xplane.idle_gaps(trace, "/device:TPU:0", (0.0, 1000.0))
    assert gaps == [(0.0, 100.0), (350.0, 400.0), (500.0, 600.0),
                    (680.0, 690.0), (700.0, 1000.0)]


def test_kernel_time_by_name(trace):
    ops = xplane.ops(trace, "/device:TPU:0", (0.0, 1000.0))
    found = xplane.matching(ops, "sorted_spmm_gather")
    assert [e.name for e in found] == ["sorted_spmm_gather.1"]
    assert sum(e.end - e.start for e in found) == 150.0
    assert xplane.matching(ops, "sorted_spmm_scatter") == []


def test_step_runs_lie_wholly_inside_the_window(trace):
    runs = xplane.module_runs(trace, "/device:TPU:0", (0.0, 1000.0),
                              "jit_step")
    assert [(e.start, e.end) for e in runs] == [(100.0, 700.0)]


def test_collectives_and_their_exposed_part(trace):
    # in flight: all-gather 100 + all-reduce start..done [600,700] 100
    # exposed: all-gather 100 (alone) + all-reduce 100 - fusion.3's 70
    flight, exposed = xplane.collective_seconds(
        trace, "/device:TPU:0", (0.0, 1000.0))
    assert flight == pytest.approx(200e-9)
    assert exposed == pytest.approx(130e-9)


def test_gaps_go_to_what_the_host_was_doing(trace):
    # end_pass (innermost on the dispatching thread) [710,900] -> 190
    # train_pass [90,710]: [90,100] + [350,400] + [500,600] + [680,690]
    #   + [700,710] = 180
    # the worker's load [0,95] takes what is left of it, [0,90] -> 90
    # nothing covers [900,1000] -> 100; together the 560 idle
    got = xplane.attribute_gaps(trace, "/device:TPU:0", (0.0, 1000.0))
    assert got == pytest.approx({"bench.end_pass": 190e-9,
                                 "bench.train_pass": 180e-9,
                                 "bench.load_into_memory": 90e-9,
                                 "unattributed": 100e-9})
    assert sum(got.values()) == pytest.approx(560e-9)


def test_top_ops(trace):
    top = xplane.top_ops(trace, "/device:TPU:0", (0.0, 1000.0), 1, label=30)
    assert top == [("%sorted_spmm_gather.1 = f32[12", pytest.approx(150e-9))]


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert xplane.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert xplane.total([(0, 2), (3, 5)]) == 4


def test_subtract_against_counting_points():
    import random
    rng = random.Random(1)
    for _ in range(300):
        ivs = [(a, a + rng.randint(1, 9)) for a in rng.sample(range(100), 5)]
        holes = [(a, a + rng.randint(1, 9)) for a in rng.sample(range(100), 6)]
        left = {x for a, b in ivs for x in range(a, b)} - \
            {x for a, b in holes for x in range(a, b)}
        assert xplane.total(xplane.subtract(ivs, holes)) == len(left)
