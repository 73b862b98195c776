"""PB9xx guarded-by inference + data-race detection (pboxlint
raceguard.py) and its runtime witness (lockdep.guards): positive and
negative snippets per check, the benign-publication model, the guard_map
export, the S4 deliberate-race integration (static PB901 + runtime
race_suspect, no hang), and the tier-1 cross-validation contract —
every runtime-observed (site, held-locks) pair from a real PS round-trip
+ prefetched pass must be contained in the static guarded-by map.
"""

import json
import os
import textwrap
import threading
import time

import numpy as np
import pytest

from paddlebox_tpu import flags
from paddlebox_tpu.tools.pboxlint import raceguard
from paddlebox_tpu.tools.pboxlint.core import Module, lint_source
from paddlebox_tpu.utils import doctor, flight, lockdep, workpool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes9(src, path="snippet.py"):
    """PB9xx codes only — dogfoods the --select machinery."""
    return [f.code for f in lint_source(textwrap.dedent(src), path,
                                        select=["PB9xx"])]


def analysis(*files):
    return raceguard.analyze(
        [Module(path, textwrap.dedent(src)) for path, src in files])


# -- PB901: unguarded write on a guarded field -------------------------------

def test_pb901_unguarded_write():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def hit(self):
            with self._lock:
                self._n += 1

        def hit2(self):
            with self._lock:
                self._n += 1

        def race(self):
            self._n += 1
    """
    assert codes9(src) == ["PB901"]


def test_pb901_constructor_writes_do_not_count():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0          # pre-publication: neither infers nor violates

        def hit(self):
            with self._lock:
                self._n += 1
    """
    assert codes9(src) == []


def test_pb901_init_only_private_helper_exempt():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._build()

        def _build(self):
            self._n = 0          # reachable only from __init__

        def hit(self):
            with self._lock:
                self._n += 1

        def hit2(self):
            with self._lock:
                self._n += 1
    """
    assert codes9(src) == []


def test_pb901_atomic_flag_publish_negative():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._stop = False

        def locked1(self):
            with self._lock:
                self._stop = False

        def locked2(self):
            with self._lock:
                self._stop = False

        def shutdown(self):
            self._stop = True    # single-word literal publish: GIL-atomic
    """
    assert codes9(src) == []


def test_pb901_annotation_honored():
    """An explicit guarded-by wins over inference (no majority needed)
    and disarms the atomic-flag exemption."""
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._ready = False  # pboxlint: guarded-by=snippet.C._lock

        def publish(self):
            self._ready = True   # annotated: even a literal store races
    """
    assert codes9(src) == ["PB901"]


def test_pb901_majority_rule_foreign_lock():
    """One incidental locked path through ANOTHER object's lock must not
    define a discipline for an otherwise main-thread class."""
    src = """
    import threading

    class Calc:
        def __init__(self):
            self._acc = 0

        def add(self):
            self._acc += 1       # standalone main-thread usage

        def add2(self):
            self._acc += 1

        def add3(self):
            self._acc += 1

    class Monitor:
        def __init__(self):
            self._lock = threading.Lock()
            self.calc = Calc()

        def fold(self):
            with self._lock:
                self.calc.add()  # entry-held flows into add via this edge
    """
    assert codes9(src) == []


def test_fresh_local_object_cannot_race():
    """Escape-analysis lite: mutations of a local constructed IN the
    function are unshared — they must not pollute guard inference even
    when they form the locked majority."""
    src = """
    import threading

    class Calc:
        def __init__(self):
            self._acc = 0

        def standalone(self):
            self._acc += 1

    class Monitor:
        def __init__(self):
            self._lock = threading.Lock()

        def windowed(self):
            with self._lock:
                calc = Calc()
                calc._acc = calc._acc + 1
                calc._acc = calc._acc + 2
                return calc
    """
    assert codes9(src) == []


def test_freeze_point_immutable_after_publish_negative():
    src = """
    import threading

    class Frozen:
        def __init__(self, rows):
            self._rows = list(rows)   # never mutated after construction

        def lookup(self, i):
            return self._rows[i]

        def size(self):
            return len(self._rows)
    """
    assert codes9(src) == []


def test_threading_local_fields_negative():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._tls = threading.local()

        def locked(self):
            with self._lock:
                self._tls = threading.local()

        def locked2(self):
            with self._lock:
                self._tls = threading.local()

        def reset(self):
            self._tls = threading.local()   # per-thread by definition
    """
    assert codes9(src) == []


# -- PB902: multi-word invariant read outside its lock -----------------------

_PAIR_SRC = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._map = None
        self._epoch = 0

    def adopt(self, m, e):
        with self._lock:
            self._map = m
            self._epoch = e

    def route(self):
        %s
"""


def test_pb902_torn_pair_read():
    src = _PAIR_SRC % "return (self._map, self._epoch)"
    assert "PB902" in codes9(src)


def test_pb902_reader_under_the_lock_negative():
    src = _PAIR_SRC % textwrap.indent(
        "with self._lock:\n    return (self._map, self._epoch)",
        "        ").lstrip()
    assert codes9(src) == []


# -- PB903: guarded container reference escape -------------------------------

_ESCAPE_SRC = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._rows = []

    def add(self, x):
        with self._lock:
            self._rows.append(x)

    def add2(self, x):
        with self._lock:
            self._rows.append(x)

    def snapshot(self):
        with self._lock:
            return %s
"""


def test_pb903_bare_reference_escape():
    assert "PB903" in codes9(_ESCAPE_SRC % "self._rows")


def test_pb903_copy_is_not_an_escape():
    assert codes9(_ESCAPE_SRC % "list(self._rows)") == []


# -- PB904: thread-spawned path touching guarded state -----------------------

_SPAWN_SRC = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []
        self._t = threading.Thread(target=self._worker, daemon=True)

    def add(self, x):
        with self._lock:
            self._items.append(x)

    def add2(self, x):
        with self._lock:
            self._items.append(x)

    def _worker(self):
        %s
"""


def test_pb904_spawned_container_traversal():
    src = _SPAWN_SRC % textwrap.indent(
        "for it in self._items:\n    print(it)", "        ").lstrip()
    assert "PB904" in codes9(src)


def test_pb904_lock_inside_task_negative():
    src = _SPAWN_SRC % textwrap.indent(
        "with self._lock:\n    for it in self._items:\n        print(it)",
        "        ").lstrip()
    assert codes9(src) == []


# -- interprocedural plumbing ------------------------------------------------

def test_widening_not_dropped_dynamic_call():
    """A dynamic (CHA-widened) call must PROPAGATE the caller's held
    set: bump() is only reached under the lock, so its write analyzes as
    locked — dropping the set would make it a false PB901."""
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def hit(self):
            with self._lock:
                self._n += 1

        def hit2(self):
            with self._lock:
                self._n += 1

        def drive(self, other):
            with self._lock:
                other.bump()     # untyped receiver: widened to C.bump

        def bump(self):
            self._n += 1         # entry-held = {_lock} via the meet
    """
    an = analysis(("m.py", src))
    assert not an.findings, [f.render() for f in an.findings]
    assert an.guard_map().get("m.C._n") == ["m.C._lock"]


def test_entry_meet_private_helper_called_under_lock():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def hit(self):
            with self._lock:
                self._apply()

        def hit2(self):
            with self._lock:
                self._apply()

        def _apply(self):
            self._n += 1         # always entered with the lock held
    """
    assert codes9(src) == []


def test_guard_map_export_shape():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._a = threading.Lock()
            self._b = threading.Lock()
            self._n = 0
            self._free = 0
            self._messy = 0

        def hit(self):
            with self._lock:
                self._n += 1

        def hit2(self):
            with self._lock:
                self._n += 1

        def loose(self):
            self._free += 1      # never locked: no guard, no entry

        def m1(self):
            with self._a:
                self._messy += 1

        def m2(self):
            with self._b:
                self._messy += 1   # disagreeing locks: inconsistent
    """
    gm = analysis(("m.py", src)).guard_map()
    assert gm.get("m.C._n") == ["m.C._lock"]
    assert "m.C._free" not in gm
    assert "m.C._messy" not in gm    # inconsistent sites never export


# -- runtime witness (lockdep.guards) ----------------------------------------

@pytest.fixture()
def guards_on():
    prev = {"lockdep": flags.get_flags("lockdep"),
            "lockdep_guards": flags.get_flags("lockdep_guards")}
    flags.set_flags({"lockdep": True, "lockdep_guards": True})
    lockdep.reset()
    yield
    flags.set_flags(prev)
    lockdep.reset()


class RacyCounter:
    """Deliberate two-thread race: locked_hit keeps the discipline,
    racy_hit breaks it.  Module-level so its runtime site name is
    stable: test_raceguard.RacyCounter._n."""

    def __init__(self):
        self._lock = lockdep.lock("test.raceguard.RacyCounter._lock")
        self._n = 0

    def locked_hit(self):
        with self._lock:
            lockdep.guards(self, "_n")
            self._n += 1

    def racy_hit(self):
        lockdep.guards(self, "_n")
        self._n += 1


_RACY_SITE = "test_raceguard.RacyCounter._n"


def test_guards_zero_cost_when_off():
    assert not lockdep.guards_enabled()
    c = RacyCounter()
    c.racy_hit()                       # a plain no-op: nothing recorded
    assert lockdep.guard_observations() == {}
    assert lockdep.guard_suspects() == []


def test_s4_deliberate_race_runtime_witness(guards_on, tmp_path):
    """The S4 integration: a two-thread racy writer under
    FLAGS_lockdep_guards yields ONE race_suspect flight event carrying
    the site and a postmortem with the suspect — without hanging (the
    witness is advisory; it never blocks or raises)."""
    lockdep.set_guard_map({_RACY_SITE: ["test.raceguard.RacyCounter._lock"]})
    c = RacyCounter()
    gate = threading.Barrier(2, timeout=10)

    def disciplined():
        gate.wait()
        for _ in range(50):
            c.locked_hit()

    def racer():
        gate.wait()
        for _ in range(50):
            c.racy_hit()

    t0 = time.monotonic()
    threads = [threading.Thread(target=disciplined, daemon=True),
               threading.Thread(target=racer, daemon=True)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20)             # the watchdog bound: no hang
    assert not any(t.is_alive() for t in threads)
    assert time.monotonic() - t0 < 20

    sus = [s for s in lockdep.guard_suspects() if s["site"] == _RACY_SITE]
    assert len(sus) == 1, lockdep.guard_suspects()   # once per site
    assert sus[0]["guard"] == ["test.raceguard.RacyCounter._lock"]

    evs = [e for e in flight.events(kind="race_suspect")
           if e.get("site") == _RACY_SITE]
    assert len(evs) == 1, "exactly one race_suspect flight event per site"

    # both held-set shapes were observed (containment data is complete)
    obs = lockdep.guard_observations()[_RACY_SITE]
    assert [] in obs
    assert ["test.raceguard.RacyCounter._lock"] in obs

    path = doctor.write_postmortem(reason="race-test",
                                   directory=str(tmp_path))
    with open(path, encoding="utf-8") as f:
        bundle = json.load(f)
    guards = bundle["lockdep"]["guards"]
    assert guards["enabled"] is True
    assert any(s["site"] == _RACY_SITE for s in guards["suspects"])


def test_deliberate_race_detected_statically_too():
    """The same shape the S4 test races at runtime must be a PB901 for
    the static half — detector and witness agree on the bug class."""
    src = """
    import threading

    class RacyCounter:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def locked_hit(self):
            with self._lock:
                self._n += 1

        def locked_hit2(self):
            with self._lock:
                self._n += 1

        def racy_hit(self):
            self._n += 1
    """
    assert "PB901" in codes9(src)


def test_sampling_probe_for_annotated_class(guards_on):
    class Annotated:
        def __init__(self):
            self._x = 0

    restore = lockdep.install_guard_probe(Annotated, ["_x"], every=1)
    try:
        a = Annotated()
        a._x = 1
        a._x = 2
    finally:
        restore()
    obs = lockdep.guard_observations()
    assert any(site.endswith("Annotated._x") for site in obs)
    a._x = 3                           # restored: no further recording
    n = sum(len(v) for k, v in lockdep.guard_observations().items()
            if k.endswith("Annotated._x"))
    assert n == sum(len(v) for k, v in obs.items()
                    if k.endswith("Annotated._x"))


# -- the tier-1 cross-validation contract ------------------------------------

class _StubArrays:
    num_real = 4


class _StubEngine:
    day_id = None

    def set_date(self, d):
        self.day_id = d

    def begin_feed_pass(self):
        pass

    def end_feed_pass(self, async_build=False):
        pass

    def peek_next_mapper(self):
        return None

    def feed_build_running(self):
        return False

    def wait_feed_pass_done(self):
        pass

    def begin_pass(self):
        pass

    def end_pass(self, need_save_delta=False, delta_path=""):
        pass


class _StubTrainer:
    def pack_pass_host(self, dataset, mapper=None):
        return _StubArrays()

    def finish_pass_feed(self, arrays, keep_host=False):
        return arrays


def test_cross_validation_runtime_guards_subset_of_static(guards_on):
    """Every runtime-observed (site, held-locks) pair from a real
    PSServer round-trip + a prefetched pass + a timeline fold must be
    contained in the static guarded-by map: site known → one of its
    inferred guards held.  Same fingerprint namespace, runtime ⊆ static
    over-approximation — the contract that made PB6xx trustworthy."""
    from paddlebox_tpu.config import EmbeddingTableConfig
    from paddlebox_tpu.data.prefetch import PassPrefetcher
    from paddlebox_tpu.ps.host_table import ShardedHostTable
    from paddlebox_tpu.ps.service import PSClient, PSServer
    from paddlebox_tpu.utils.timeline import TimelineRing

    static = raceguard.guard_map_paths(
        [os.path.join(REPO, "paddlebox_tpu")])
    lockdep.set_guard_map(static)

    prev_threads = flags.get_flags("ps_table_threads")
    flags.set_flags({"ps_table_threads": 1})
    try:
        # 1. real PS round-trip (host-table upsert under the shard lock)
        table = ShardedHostTable(
            EmbeddingTableConfig(embedding_dim=3, shard_num=4))
        srv = PSServer(table)
        try:
            client = PSClient(srv.addr)
            keys = np.arange(1, 40, dtype=np.uint64)
            rows = client.pull_sparse(keys, create=True)
            rows["show"][:] += 1
            client.push_sparse(keys, rows)
            client.end_day()
        finally:
            srv.shutdown()

        # 2. prefetched pass (the worker/consumer condition discipline)
        pre = PassPrefetcher(_StubEngine(), _StubTrainer())
        try:
            for i in range(2):
                pre.submit(lambda: None, tag=f"p{i}")
            for _ in range(2):
                pre.next_pass()
                pre.end_pass()
        finally:
            pre.close()

        # 3. timeline fold (ring sequence under the ring lock)
        ring = TimelineRing(cap=8)
        ring.append({"x": 1.0})
        ring.append({"x": 2.0})
    finally:
        flags.set_flags({"ps_table_threads": prev_threads})
        workpool.table_pool()           # resize the singleton back

    obs = {site: helds for site, helds in
           lockdep.guard_observations().items()
           if not site.startswith(("test.", "test_raceguard."))}
    # the soak is not allowed to be vacuous: each driven subsystem's
    # assertion point must have fired
    for want in ("ps.host_table._Shard._len",
                 "data.prefetch.PassPrefetcher._adopted_n",
                 "utils.timeline.TimelineRing._seq"):
        assert want in obs, sorted(obs)

    violations = []
    for site, helds in obs.items():
        want = static.get(site)
        assert want is not None, \
            f"runtime site {site} missing from the static guard map"
        for held in helds:
            if not set(held).intersection(want):
                violations.append((site, held, want))
    assert not violations, violations
    # and the advisory witness agrees: no production race suspects
    assert not [s for s in lockdep.guard_suspects()
                if not s["site"].startswith(("test.", "test_raceguard."))]
