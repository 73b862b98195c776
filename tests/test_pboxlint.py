"""pboxlint: per-checker unit tests (positive + negative snippets), the
suppression machinery, the CLI, and the tier-1 whole-package gate.

The regression snippet in test_cli_flags_prefix_service_lock_bug is the
PRE-FIX ps/service.py pull_sparse pattern (ADVICE.md round-5: the learned
row-size estimate mutated outside self._lock) — the canary PB102 must keep
catching even though the tree itself is fixed.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

from paddlebox_tpu.tools.pboxlint import lint_paths, lint_source

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(src, path="snippet.py"):
    return [f.code for f in lint_source(textwrap.dedent(src), path)]


# -- PB1xx lock discipline ---------------------------------------------------

def test_pb101_flags_mutation_outside_lock():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def locked(self):
            with self._lock:
                self._n = 1

        def unlocked(self):
            self._n = 2
    """
    assert codes(src) == ["PB101"]


def test_pb101_negative_all_mutations_under_lock():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def a(self):
            with self._lock:
                self._n = 1

        def b(self):
            with self._lock:
                self._n += 2
    """
    assert codes(src) == []


def test_pb101_init_writes_do_not_count():
    # __init__ runs before the instance is shared — its bare writes must
    # not turn every lock-guarded attribute into a finding
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def a(self):
            with self._lock:
                self._n = 1
    """
    assert codes(src) == []


def test_pb102_flags_unlocked_read_modify_write():
    src = """
    import threading

    class Client:
        def __init__(self):
            self._lock = threading.Lock()
            self._est = 512

        def _call(self):
            with self._lock:
                return 1

        def pull(self):
            per = self._est
            rows = self._call()
            self._est = per + rows
            return rows
    """
    assert codes(src) == ["PB102"]


def test_pb102_negative_rmw_under_lock():
    src = """
    import threading

    class Client:
        def __init__(self):
            self._lock = threading.Lock()
            self._est = 512

        def pull(self):
            with self._lock:
                per = self._est
                self._est = per + 1
            return per
    """
    assert codes(src) == []


def test_pb103_bare_acquire_without_try_finally():
    src = """
    import threading
    lock = threading.Lock()

    def bad():
        lock.acquire()
        work()
        lock.release()

    def good():
        lock.acquire()
        try:
            work()
        finally:
            lock.release()
    """
    assert codes(src) == ["PB103"]


def test_pb104_pre_fix_psclient_call_snippet():
    """The regression canary: the PRE-PIPELINING PSClient._call held the
    client-wide lock across connect/send/recv — exactly what the
    multi-stream wire path removed.  PB104 must keep catching it."""
    src = """
    import socket
    import threading

    def _send(sock, msg):
        sock.sendall(msg)

    def _recv(sock):
        return sock.recv(8)

    class PSClient:
        def __init__(self, addr):
            self.addr = addr
            self._sock = None
            self._lock = threading.Lock()

        def _call(self, req, timeout=60):
            with self._lock:
                if self._sock is None:
                    self._sock = socket.create_connection(
                        self.addr, timeout=timeout)
                self._sock.settimeout(timeout)
                _send(self._sock, req)
                return _recv(self._sock)
    """
    got = codes(src)
    assert got.count("PB104") == 3      # create_connection, _send, _recv


def test_pb104_module_level_lock_and_open():
    src = """
    import threading
    _LOCK = threading.Lock()

    def bad(path):
        with _LOCK:
            with open(path) as f:
                return f.read()

    def good(path):
        with open(path) as f:
            data = f.read()
        with _LOCK:
            return data
    """
    assert codes(src) == ["PB104"]


def test_pb104_negative_nested_def_and_io_outside_lock():
    # a def statement under a lock does not RUN under the lock; I/O after
    # the with-block is free; a condition-variable wait is not I/O
    src = """
    import socket
    import threading

    class C:
        def __init__(self):
            self._cv = threading.Condition()
            self._sock = socket.socket()

        def spawn(self):
            with self._cv:
                def worker():
                    self._sock.sendall(b"x")
                self._cv.wait(1.0)
            self._sock.sendall(b"y")
            return worker
    """
    assert codes(src) == []


def test_pb104_suppression():
    src = """
    import threading

    class Log:
        def __init__(self, path):
            self.path = path
            self._lock = threading.Lock()

        def append(self, rec):
            # pboxlint: disable-next=PB104 -- the file IS the locked thing
            with self._lock, open(self.path, "ab") as fh:
                fh.write(rec)
    """
    assert codes(src) == []


# -- PB2xx flag hygiene ------------------------------------------------------

def test_pb201_unregistered_flag_name():
    src = """
    from paddlebox_tpu.flags import define_flag, get_flags, set_flags

    define_flag("real_flag", 1, "help")
    a = get_flags("real_flag")
    b = get_flags("typo_flag")
    set_flags({"real_flag": 2, "other_typo": 3})
    """
    assert codes(src) == ["PB201", "PB201"]


def test_pb202_default_must_roundtrip_coerce():
    src = """
    from paddlebox_tpu.flags import define_flag, get_flags

    define_flag("ok_int", 20, "fine")
    define_flag("ok_bool", True, "fine")
    define_flag("ok_str", "auto", "fine")
    define_flag("bad_list", [1, 2], "env override cannot parse a list")
    vals = [get_flags(n) for n in
            ("ok_int", "ok_bool", "ok_str", "bad_list")]
    """
    assert codes(src) == ["PB202"]


def test_pb203_raw_flags_environ_read():
    src = """
    import os

    a = os.environ["FLAGS_record_pool_max_size"]
    b = os.getenv("FLAGS_check_nan_inf")
    c = os.environ.get("FLAGS_feed_pass_thread_num")
    d = os.environ["HOME"]          # non-FLAGS: fine
    """
    assert sorted(codes(src)) == ["PB203", "PB203", "PB203"]
    # the registry itself is allowed to read its own env overrides
    assert codes(src, path="flags.py") == []


def test_pb205_dead_flag_defined_but_never_read():
    src = """
    from paddlebox_tpu.flags import define_flag, get_flags

    define_flag("live_flag", 1, "read below")
    define_flag("dead_flag", 0, "never read anywhere")
    x = get_flags("live_flag")
    """
    assert codes(src) == ["PB205"]


def test_pb205_set_flags_literal_counts_as_use():
    src = """
    from paddlebox_tpu.flags import define_flag, set_flags

    define_flag("tuned_flag", 1, "set by the launcher")
    set_flags({"tuned_flag": 2})
    """
    assert codes(src) == []


def test_pb205_dynamic_reads_disarm_the_rule():
    # a get_flags(variable) anywhere means reads are out of static
    # reach — the rule must go quiet rather than false-positive
    src = """
    from paddlebox_tpu.flags import define_flag, get_flags

    define_flag("maybe_dead", 1, "read dynamically below")

    def read(name):
        return get_flags(name)
    """
    assert codes(src) == []


def test_pb206_flight_kind_unbounded_fstring():
    # the regression this rule exists for: an event kind minted from an
    # unbounded value (a rid) — shreds the /flightz vocabulary
    src = """
    from paddlebox_tpu.utils import flight

    def report(rid, cmd):
        flight.record(f"retry_{rid}")
        flight.record(f"retry_{cmd}")           # bounded field: fine
        flight.record("verb_retry", rid=rid)    # rid in FIELDS: fine
    """
    assert codes(src) == ["PB206"]


def test_pb206_literal_kind_must_be_lowercase_identifier():
    src = """
    from paddlebox_tpu.utils.flight import record as flight_record

    def f():
        flight_record("Pass.Begin")
        flight_record("pass_begin")
    """
    assert codes(src) == ["PB206"]


def test_pb206_literal_kind_must_be_in_closed_vocabulary():
    # the vocabulary is CLOSED: a lowercase literal kind that is not in
    # KNOWN_KINDS is minted ad hoc — new kinds land by editing
    # flight_events.KNOWN_KINDS in the same change
    src = """
    from paddlebox_tpu.utils import flight

    def f():
        flight.record("totally_new_kind")
        flight.record("heat_snapshot")      # in the vocabulary: fine
    """
    assert codes(src) == ["PB206"]


def test_pb206_unrelated_record_methods_out_of_scope():
    # bench.py's record(**kw) partials and ring.record(...) methods must
    # not trip the rule — sinks resolve through the flight import only
    src = """
    def record(**kw):
        pass

    def bench(self, rid):
        record(kind=rid)
        self._ring.record(f"x {rid}")
    """
    assert codes(src) == []


def test_pb208_raw_key_in_metric_name():
    # a 10^11-cardinality feature key minted into a stat name grows the
    # registry one entry per hot key; the sketch types are the sink.
    # PB204 flags the same site generically (unbounded f-string part) —
    # PB208 names the disease, so both fire.
    src = """
    from paddlebox_tpu.utils.monitor import stat_add

    def f(key, shard, n):
        stat_add(f"ps.hot.{key}", n)
        stat_add(f"ps.cluster.s{shard}.pull_keys", n)   # bounded: fine
    """
    assert sorted(codes(src)) == ["PB204", "PB208"]


def test_pb208_raw_key_in_flight_kind():
    src = """
    from paddlebox_tpu.utils import flight

    def f(feasign):
        flight.record(f"hot_{feasign}", n=1)
        flight.record("heat_imbalance", imbalance=4.5)  # fine
    """
    assert sorted(codes(src)) == ["PB206", "PB208"]


def test_pb208_per_key_dict_in_obs_module():
    # exact per-key state in the obs layer is unbounded memory by
    # construction — only obs-module basenames are in scope, and
    # utils/sketch.py is the sanctioned bounded sink
    src = """
    def bump(counts, key):
        counts[key] = counts.get(key, 0) + 1

    def seed(counts, feasign):
        counts.setdefault(feasign, 0)
    """
    assert codes(src, path="monitor.py") == ["PB208", "PB208"]
    assert codes(src, path="sketch.py") == []       # sanctioned sink
    assert codes(src, path="host_table.py") == []   # not obs code


# -- PB3xx JAX purity --------------------------------------------------------

def test_pb301_host_sync_in_jitted_fn():
    src = """
    import jax
    import numpy as np

    @jax.jit
    def bad(x):
        print(x)
        y = np.asarray(x)
        return float(y)

    def fine(x):
        print(x)                    # not traced: host calls are fine
        return float(np.asarray(x))
    """
    assert codes(src) == ["PB301", "PB301", "PB301"]


def test_pb301_scan_body_and_partial_jit():
    src = """
    from functools import partial
    import jax
    from jax import lax
    from paddlebox_tpu.flags import define_flag, get_flags

    define_flag("learning_rate", 0.05, "registered: no PB201 noise")

    @partial(jax.jit, donate_argnums=(0,))
    def step(ws, x):
        lr = get_flags("learning_rate")
        return ws, x

    def body(carry, x):
        v = x.item()
        return carry, v

    def run(xs):
        return lax.scan(body, 0.0, xs)
    """
    assert codes(src) == ["PB301", "PB301"]


def test_pb302_trace_time_state_mutation():
    src = """
    import jax

    class T:
        def build(self):
            @jax.jit
            def step(self, x):
                self.cache = x          # baked in at trace time
                return x
            return step
    """
    assert codes(src) == ["PB302"]


def test_pb302_negative_rebound_copy_is_functional_update():
    # `ws = dict(ws)` then item-assign is the idiomatic functional update
    # (trainer/graph_trainer.py) — NOT trace-time state mutation
    src = """
    import jax

    @jax.jit
    def step(ws, g):
        ws = dict(ws)
        ws["mf"] = ws["mf"] - g
        return ws
    """
    assert codes(src) == []


# -- PB4xx threading lifecycle -----------------------------------------------

def test_pb401_thread_without_daemon_or_join():
    src = """
    import threading

    def bad():
        t = threading.Thread(target=work)
        t.start()

    def good_daemon():
        t = threading.Thread(target=work, daemon=True)
        t.start()

    def good_joined():
        t = threading.Thread(target=work)
        t.start()
        t.join()
    """
    assert codes(src) == ["PB401"]


def test_pb401_class_scope_join_in_other_method():
    src = """
    import threading

    class Pool:
        def start(self):
            self._t = threading.Thread(target=self._run)
            self._t.start()

        def stop(self):
            self._t.join()

    class Leak:
        def start(self):
            self._t = threading.Thread(target=self._run)
            self._t.start()
    """
    assert codes(src) == ["PB401"]


def test_pb402_blocking_queue_get_in_loop():
    src = """
    import queue

    def bad(q2):
        q = queue.Queue()
        while True:
            item = q.get()
            handle(item)

    def good_sentinel():
        q = queue.Queue()
        while True:
            item = q.get()
            if item is None:
                break
            handle(item)

    def good_timeout():
        q = queue.Queue()
        while True:
            handle(q.get(timeout=5))
    """
    assert codes(src) == ["PB402"]


def test_pb402_queue_gated_loop_is_fine():
    src = """
    import queue

    def drain():
        q = queue.Queue()
        out = []
        while q.qsize():
            out.append(q.get())
        return out
    """
    # the loop only calls get() when the queue reports an item
    assert codes(src) == []


def test_pb403_executor_missing_prefix_and_shutdown():
    src = """
    import concurrent.futures

    def bad():
        pool = concurrent.futures.ThreadPoolExecutor(max_workers=4)
        pool.submit(print, 1)
    """
    # two distinct defects on the one ctor: anonymous threads AND a
    # forgotten lifecycle
    assert codes(src) == ["PB403", "PB403"]


def test_pb403_with_statement_still_needs_prefix():
    src = """
    from concurrent.futures import ThreadPoolExecutor

    def run(items):
        with ThreadPoolExecutor(max_workers=2) as pool:
            return list(pool.map(str, items))
    """
    # `with` covers shutdown; the missing prefix alone trips
    assert codes(src) == ["PB403"]


def test_pb403_negative_prefixed_and_shutdown():
    src = """
    from concurrent.futures import ThreadPoolExecutor

    def fn(items):
        ex = ThreadPoolExecutor(max_workers=2, thread_name_prefix="pk")
        try:
            return [f.result() for f in [ex.submit(str, i) for i in items]]
        finally:
            ex.shutdown(wait=False)

    def ctx(items):
        with ThreadPoolExecutor(max_workers=2,
                                thread_name_prefix="pk") as pool:
            return list(pool.map(str, items))

    class Owner:
        def __init__(self):
            self._ex = ThreadPoolExecutor(max_workers=1,
                                          thread_name_prefix="pk")

        def close(self):
            self._ex.shutdown()
    """
    assert codes(src) == []


def test_pb403_class_attr_without_shutdown():
    src = """
    from concurrent.futures import ThreadPoolExecutor

    class Leaky:
        def __init__(self):
            self._ex = ThreadPoolExecutor(max_workers=1,
                                          thread_name_prefix="pk")
    """
    assert codes(src) == ["PB403"]


def test_pb405_unjoined_looping_thread():
    src = """
    import threading

    class Pump:
        def start(self):
            self._t = threading.Thread(target=self._run, daemon=True)
            self._t.start()

        def _run(self):
            while True:
                self.step()
    """
    # daemon= satisfies PB401; the unjoined recurring loop still trips 405
    assert codes(src) == ["PB405"]


def test_pb405_joined_thread_is_managed_lifecycle():
    src = """
    import threading

    class Worker:
        def start(self):
            self._t = threading.Thread(target=self._run, daemon=True)
            self._t.start()

        def _run(self):
            while self.alive():
                self.step()

        def close(self):
            self._t.join()
    """
    assert codes(src) == []


def test_pb405_one_shot_target_not_flagged():
    src = """
    import threading

    class Handoff:
        def kick(self):
            self._t = threading.Thread(target=self._build, daemon=True)
            self._t.start()

        def _build(self):
            self.result = self.compute()
    """
    # no loop in the target: a one-shot handoff, not recurring work
    assert codes(src) == []


def test_pb405_unresolvable_target_skipped():
    src = """
    import threading

    def serve(srv):
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()

    def dynamic(fn):
        t = threading.Thread(target=fn, daemon=True)
        t.start()
    """
    # foreign receiver / dynamic callable: another object's lifecycle
    assert codes(src) == []


def test_pb405_anonymous_looping_thread():
    src = """
    import threading

    def _loop():
        while True:
            pass

    def fire():
        threading.Thread(target=_loop, daemon=True).start()
    """
    assert codes(src) == ["PB405"]


# -- suppressions ------------------------------------------------------------

# -- PB5xx retry/backoff discipline ------------------------------------------

def test_pb501_fixed_sleep_retry_loop():
    src = """
    import time

    def fetch(addr):
        for _ in range(3):
            try:
                return connect(addr)
            except ConnectionError:
                time.sleep(0.5)
    """
    assert codes(src) == ["PB501"]


def test_pb501_while_loop_and_bare_sleep_name():
    src = """
    from time import sleep

    def poll():
        while True:
            try:
                return check()
            except OSError:
                sleep(2)
    """
    assert codes(src) == ["PB501"]


def test_pb501_negative_computed_sleep_and_backoff_helper():
    # non-constant sleeps (variables, attributes, the shared helper) are
    # the sanctioned patterns; a constant sleep in a try-less poll loop
    # is polling, not retrying
    src = """
    import time
    from paddlebox_tpu.utils.backoff import Backoff

    def fetch(self, addr):
        bo = Backoff(base=0.05, deadline=30)
        attempt = 0
        while True:
            try:
                return connect(addr)
            except ConnectionError:
                attempt += 1
                if not bo.sleep(attempt):
                    raise
                time.sleep(self.retry_sleep)

    def watch(procs):
        while procs:
            reap(procs)
            time.sleep(0.2)
    """
    assert codes(src) == []


def test_pb501_suppression_escape():
    src = """
    import time

    def fetch(addr):
        for _ in range(3):
            try:
                return connect(addr)
            except ConnectionError:
                # pboxlint: disable-next=PB501 -- vendor API mandates 1s
                time.sleep(1.0)
    """
    assert codes(src) == []


# -- PB502 durable-write atomicity -------------------------------------------

def test_pb502_bare_open_in_save_function():
    src = """
    import json

    def save_manifest(path, obj):
        with open(path, "w") as f:
            json.dump(obj, f)
    """
    assert codes(src) == ["PB502"]


def test_pb502_savez_and_open_write_in_checkpoint_code():
    src = """
    import numpy as np

    def dump_shard(fs, part, data):
        np.savez(part, **data)
        with fs.open_write(part) as fh:
            fh.write(b"x")
    """
    assert codes(src) == ["PB502", "PB502"]


def test_pb502_io_module_scope():
    # under io/ every bare final-path write is durability-critical,
    # whatever the function is called
    src = """
    def publish(path, blob):
        with open(path, "wb") as f:
            f.write(blob)
    """
    assert codes(src, path="paddlebox_tpu/io/artifacts.py") == ["PB502"]


def test_pb502_negative_tmp_path_and_cold_code():
    # the scratch leg of write-tmp-then-rename is the SANCTIONED pattern;
    # reads and writes outside save/dump/io code are out of scope
    src = """
    import os

    def save_table(path, blob):
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)

    def load_table(path):
        with open(path, "rb") as f:
            return f.read()

    def debug_note(path, msg):
        with open(path, "a") as f:
            f.write(msg)
    """
    assert codes(src) == []


def test_pb502_suppression_escape():
    src = """
    def save_wal(path, rec):
        # pboxlint: disable-next=PB502 -- append-only WAL, index-gated
        with open(path, "ab") as f:
            f.write(rec)
    """
    assert codes(src) == []


# -- PB503 device-cache coherence discipline ---------------------------------

def test_pb503_foldback_outside_end_pass():
    src = """
    def train_step(self, feed):
        self.cache.update_after_pass(keys, soa, ws, pass_id=0)
    """
    assert codes(src) == ["PB503"]


def test_pb503_foldback_inside_end_pass_ok():
    src = """
    def end_pass(self):
        self.table.bulk_write(keys, soa)
        self.cache.update_after_pass(keys, soa, ws, pass_id=self.pass_id)
    """
    assert codes(src) == []


def test_pb503_invalidate_outside_coherence_point():
    src = """
    def train_pass(self, feed):
        engine.cache.invalidate("just in case")
    """
    assert codes(src) == ["PB503"]


def test_pb503_invalidate_at_named_coherence_points_ok():
    src = """
    def set_date(self, date):
        self.cache.invalidate("end_day")

    def reset_feed_state(self):
        self.cache.invalidate("reset")

    def resume(self, engine, trainer):
        engine.cache.invalidate("resume")

    def shrink(self):
        self.cache.invalidate("shrink")
    """
    assert codes(src) == []


def test_pb503_non_cache_receiver_out_of_scope():
    # same attr names on a non-cache receiver are someone else's protocol
    src = """
    def train_step(self):
        self.stats.invalidate("x")
        self.pool.update_after_pass(1)
    """
    assert codes(src) == []


def test_pb503_implementation_and_tests_exempt():
    src = """
    def helper(self):
        self.cache.invalidate("mid-flight")
    """
    assert codes(src, path="paddlebox_tpu/ps/device_cache.py") == []
    assert codes(src, path="tests/test_device_cache.py") == []


def test_pb503_suppression_escape():
    src = """
    def drain(self):
        # pboxlint: disable-next=PB503 -- elastic relaunch teardown
        self.cache.invalidate("relaunch")
    """
    assert codes(src) == []


def test_suppression_same_line_and_next_line():
    base = """
    import threading

    def bad():
        t = threading.Thread(target=work)
        t.start()
    """
    assert codes(base) == ["PB401"]
    inline = base.replace(
        "t = threading.Thread(target=work)",
        "t = threading.Thread(target=work)  "
        "# pboxlint: disable=PB401 -- test")
    assert codes(inline) == []
    nxt = base.replace(
        "        t = threading.Thread(target=work)",
        "        # pboxlint: disable-next=PB401 -- test\n"
        "        t = threading.Thread(target=work)")
    assert codes(nxt) == []


def test_suppression_is_code_specific():
    src = """
    import threading

    def bad():
        t = threading.Thread(target=work)  # pboxlint: disable=PB999
        t.start()
    """
    assert codes(src) == ["PB401"]      # wrong code: not suppressed


# -- CLI + whole-package tier-1 gate -----------------------------------------

_PREFIX_SERVICE_SNIPPET = """
import threading


class PSClient:
    def __init__(self):
        self._row_bytes_est = 512       # adapted from observed responses
        self._rows_learned = False      # first pull probes conservatively
        self._lock = threading.Lock()

    def _call(self, req):
        with self._lock:
            return {"rows": req}

    def _per_chunk(self, bytes_per_row):
        return max(1, 2 ** 22 // max(bytes_per_row, 1))

    def pull_sparse(self, keys):
        parts = []
        lo = 0
        while lo < len(keys):
            per = self._per_chunk(self._row_bytes_est)
            if not self._rows_learned:
                per = min(per, 65536)
            c = min(per, len(keys) - lo)
            rows = self._call({"keys": keys[lo:lo + c]})["rows"]
            if c:
                self._row_bytes_est = max(len(rows), 8)
                self._rows_learned = True
            parts.append(rows)
            lo += c
        return parts
"""


def test_cli_flags_prefix_service_lock_bug(tmp_path):
    """The PRE-FIX ps/service.py pull_sparse estimate (mutated outside
    self._lock) must exit the CLI non-zero with PB102 — the ADVICE.md
    canary this suite was built around."""
    snip = tmp_path / "prefix_service.py"
    snip.write_text(_PREFIX_SERVICE_SNIPPET)
    proc = subprocess.run(
        [sys.executable, "-m", "paddlebox_tpu.tools.pboxlint", str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "PB102" in proc.stdout
    assert "_row_bytes_est" in proc.stdout


def test_cli_parse_failure_exits_2(tmp_path):
    snip = tmp_path / "broken.py"
    snip.write_text("def f(:\n")
    proc = subprocess.run(
        [sys.executable, "-m", "paddlebox_tpu.tools.pboxlint", str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 2
    assert "PB000" in proc.stdout


def test_whole_package_zero_findings():
    """The tier-1 gate: every checker over the whole package, zero
    findings — the analyzer and the tree stay clean together."""
    findings, errors = lint_paths([os.path.join(REPO, "paddlebox_tpu")])
    assert not errors, errors
    assert not findings, "\n".join(f.render() for f in findings)


def test_cli_whole_package_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "paddlebox_tpu.tools.pboxlint",
         "paddlebox_tpu/"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_pb601_wired_into_default_checker_set():
    """PB6xx rides the same gate as every other family: plain
    lint_source over an ABBA snippet must surface PB601."""
    src = """
    import threading

    class S:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def one(self):
            with self._a:
                with self._b:
                    pass

        def two(self):
            with self._b:
                with self._a:
                    pass
    """
    assert "PB601" in codes(src)


def test_cli_json_and_baseline_diff(tmp_path):
    """--format=json emits findings/counts; --baseline exits 0 on an
    unchanged tree and 1 only when a NEW per-file/per-code bucket
    appears (line/message churn must not fail the diff)."""
    snip = tmp_path / "prefix_service.py"
    snip.write_text(_PREFIX_SERVICE_SNIPPET)
    base = tmp_path / "base.json"
    cmd = [sys.executable, "-m", "paddlebox_tpu.tools.pboxlint"]
    proc = subprocess.run(
        cmd + ["--format=json", "--write-baseline", str(base), str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert {f["code"] for f in out["findings"]} == {"PB102"}
    assert out["counts"] == {f"{snip}:PB102": len(out["findings"])}

    # same tree against its own baseline: no new buckets, exit 0
    proc = subprocess.run(
        cmd + ["--baseline", str(base), str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # a genuinely new finding bucket fails the diff
    leak = tmp_path / "leak.py"
    leak.write_text("import threading\n\n\n"
                    "def bad():\n"
                    "    t = threading.Thread(target=work)\n"
                    "    t.start()\n")
    proc = subprocess.run(
        cmd + ["--baseline", str(base), str(snip), str(leak)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "NEW vs baseline" in proc.stdout
    assert "PB401" in proc.stdout


def test_pb901_wired_into_default_checker_set():
    """PB9xx rides the same gate as every other family: plain
    lint_source over a racy-counter snippet must surface PB901."""
    src = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def hit(self):
            with self._lock:
                self._n += 1

        def hit2(self):
            with self._lock:
                self._n += 1

        def racy(self):
            self._n += 1
    """
    assert "PB901" in codes(src)


_RACY_SNIPPET = """
import threading


class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def hit(self):
        with self._lock:
            self._n += 1

    def hit2(self):
        with self._lock:
            self._n += 1

    def racy(self):
        self._n += 1
"""


def test_cli_select_filters_families(tmp_path):
    """--select=PB9xx keeps only the race family (exit 1 when it fires,
    0 when the selected family is clean) and composes with
    --format=json: counts contain only selected buckets."""
    snip = tmp_path / "racy.py"
    snip.write_text(_RACY_SNIPPET)
    cmd = [sys.executable, "-m", "paddlebox_tpu.tools.pboxlint"]

    proc = subprocess.run(
        cmd + ["--select=PB9xx", "--format=json", str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert {f["code"] for f in out["findings"]} == {"PB901"}
    assert all(":PB9" in k for k in out["counts"])

    # the same tree through a family with nothing to say: exit 0
    proc = subprocess.run(
        cmd + ["--select=PB6xx", str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # exact-code token: PB901 alone also selects the finding
    proc = subprocess.run(
        cmd + ["--select", "PB901", str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "PB901" in proc.stdout

    # an empty selector is an operator error, not "select nothing"
    proc = subprocess.run(
        cmd + ["--select=", str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 2


def test_cli_select_composes_with_baseline(tmp_path):
    """A baseline written under --select only carries selected buckets,
    and re-linting with the same selection diffs clean."""
    snip = tmp_path / "racy.py"
    snip.write_text(_RACY_SNIPPET)
    base = tmp_path / "base.json"
    cmd = [sys.executable, "-m", "paddlebox_tpu.tools.pboxlint",
           "--select=PB9xx"]
    proc = subprocess.run(
        cmd + ["--write-baseline", str(base), str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    counts = json.loads(base.read_text())["counts"]
    assert counts and all(":PB9" in k for k in counts)
    proc = subprocess.run(
        cmd + ["--baseline", str(base), str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_stats_reports_per_checker_timing(tmp_path):
    """--stats attaches per-checker wall seconds: a 'stats' object in
    JSON mode (checker-module keys, numeric values) and a stderr table
    in text mode — stdout findings stay machine-parseable."""
    snip = tmp_path / "racy.py"
    snip.write_text(_RACY_SNIPPET)
    cmd = [sys.executable, "-m", "paddlebox_tpu.tools.pboxlint"]

    proc = subprocess.run(
        cmd + ["--stats", "--format=json", str(snip)],
        capture_output=True, text=True, cwd=REPO)
    out = json.loads(proc.stdout)
    assert "stats" in out
    for name in ("raceguard", "lockgraph", "locks"):
        assert name in out["stats"], sorted(out["stats"])
    assert all(isinstance(v, (int, float)) and v >= 0
               for v in out["stats"].values())

    proc = subprocess.run(
        cmd + ["--stats", str(snip)],
        capture_output=True, text=True, cwd=REPO)
    assert "raceguard" in proc.stderr
    assert "TOTAL" in proc.stderr
    assert "raceguard" not in proc.stdout.replace("PB9", "")


def test_launcher_exports_and_readme_flags_are_registered():
    """S2 cross-check: every FLAGS_<name> env export in launch.py and
    every README flag-table row must name a flag actually registered via
    define_flag somewhere in the package — renaming or removing a flag
    must not leave a stale launcher export or doc row behind."""
    from paddlebox_tpu.tools.pboxlint.core import (Module, PackageContext,
                                                   iter_py_files)
    mods = []
    for path in iter_py_files([os.path.join(REPO, "paddlebox_tpu")]):
        with open(path, encoding="utf-8") as f:
            mods.append(Module(path, f.read()))
    defined = PackageContext(mods).defined_flags

    launch_src = open(
        os.path.join(REPO, "paddlebox_tpu", "launch.py"),
        encoding="utf-8").read()
    exported = set(re.findall(r'env(?:iron)?\[\s*"FLAGS_(\w+)"', launch_src))
    assert exported, "no FLAGS_ env exports found in launch.py"
    assert exported <= defined, \
        f"launch.py exports unregistered flags: {sorted(exported - defined)}"

    readme = open(os.path.join(REPO, "README.md"), encoding="utf-8").read()
    rows = set()
    in_table = False
    for line in readme.splitlines():
        if line.replace(" ", "").startswith("|flag|"):
            in_table = True
            continue
        if in_table:
            m = re.match(r"\|\s*`(\w+)`\s*\|", line)
            if m:
                rows.add(m.group(1))
            elif not line.startswith("|"):
                in_table = False
    assert rows, "no README flag-table rows parsed"
    assert rows <= defined, \
        f"README documents unregistered flags: {sorted(rows - defined)}"


# -- PB701: serving read-path purity -----------------------------------------

def serving_codes(src, path="ps/serving.py"):
    return codes(src, path)


def test_pb701_direct_mutator_on_read_path():
    src = """
    class Rep:
        def _serve_read(self, req):
            self.table.bulk_write(req["keys"], req["rows"])
    """
    assert "PB701" in serving_codes(src)


def test_pb701_transitive_through_helper():
    """The offense lives in a helper — the finding anchors at the
    serving-side call chain, proving reachability, not just grep."""
    src = """
    class Rep:
        def _serve_read(self, req):
            return self._fallback(req)

        def _fallback(self, req):
            self.table.upsert(req["keys"], req["rows"])
    """
    assert "PB701" in serving_codes(src)


def test_pb701_shard_lock_from_lookup():
    """lookup_rows is a read-path root: acquiring the host-table shard
    lock from it breaks the lock-free serving contract."""
    src = """
    from paddlebox_tpu.utils import lockdep

    class Tab:
        def __init__(self):
            self.lk = lockdep.lock("ps.host_table._Shard.lock")

        def lookup_rows(self, keys):
            with self.lk:
                return keys
    """
    assert "PB701" in serving_codes(src)


def test_pb701_clean_read_path_silent():
    src = """
    class Tab:
        def lookup_rows(self, keys):
            return {"embed_w": keys}

    class Rep:
        def _serve_read(self, req):
            t = Tab()
            return t.lookup_rows(req["keys"])
    """
    assert serving_codes(src) == []


def test_pb701_non_serving_module_out_of_scope():
    """The same mutating code outside a serving module is the training
    tier doing its job — not a PB701."""
    src = """
    class Rep:
        def _serve_read(self, req):
            self.table.bulk_write(req["keys"], req["rows"])
    """
    assert "PB701" not in serving_codes(src, path="ps/other.py")


# -- PB702: frozen-plane immutability -----------------------------------------

def test_pb702_inplace_patch_of_published_planes():
    """The pre-fix shortcut the rule exists for: an in-place 'hot patch'
    of a live FrozenHostTable's SoA — a data race against every in-flight
    lock-free reader, and it forks the replica from a from-scratch chain
    load.  The sanctioned path is the copy-on-write patch builder."""
    src = """
    import numpy as np

    class Rep:
        def apply_delta(self, tab, keys, rows):
            pos = np.searchsorted(tab._keys, keys)
            for f in rows:
                tab._soa[f][pos] = rows[f]      # in-place hot patch
    """
    assert "PB702" in serving_codes(src)


def test_pb702_whole_plane_reassignment():
    src = """
    class Rep:
        def rebase(self, tab, keys, soa):
            tab._keys = keys
            tab._soa = soa
    """
    assert serving_codes(src).count("PB702") == 2


def test_pb702_augmented_write():
    src = """
    class Tab:
        def decay(self, rate):
            self._soa["show"] *= rate
    """
    assert "PB702" in serving_codes(src)


def test_pb702_init_construction_allowed():
    """__init__ is the one sanctioned assignment site — construction of
    a NEW object (what patched()/restrict() do) is the COW path itself."""
    src = """
    import numpy as np

    class Tab:
        def __init__(self, keys, soa):
            order = np.argsort(keys, kind="stable")
            self._keys = keys[order]
            self._soa = {f: a[order] for f, a in soa.items()}
    """
    assert serving_codes(src) == []


def test_pb702_reads_and_locals_silent():
    """Reads of the planes and writes to LOCAL gather outputs (the miss
    path's out[f][found] = ...) are not plane writes."""
    src = """
    import numpy as np

    class Tab:
        def lookup_rows(self, keys):
            pos = np.searchsorted(self._keys, keys)
            out = {f: np.zeros(len(keys)) for f in self._soa}
            for f, arr in self._soa.items():
                out[f][pos] = arr[pos]
            return out
    """
    assert serving_codes(src) == []


def test_pb702_non_serving_module_out_of_scope():
    src = """
    class Tab:
        def rebase(self, keys):
            self._keys = keys
    """
    assert "PB702" not in serving_codes(src, path="ps/host_table.py")


# -- PB8xx PS-cluster commit discipline ---------------------------------------

def test_pb801_hand_built_lifecycle_frame():
    src = """
    def roll_day(client):
        client._call({"cmd": "end_day", "table": None}, dedup=True)
    """
    assert codes(src) == ["PB801"]


def test_pb801_hand_built_commit_frame():
    src = """
    def finish(client, group):
        client._call({"cmd": "lifecycle_commit", "verb": "end_day",
                      "txn": group}, shard=0)
    """
    assert codes(src) == ["PB801"]


def test_pb801_save_load_frames():
    src = """
    def snap(client, path):
        client._call({"cmd": "save", "path": path, "mode": "all"})
        client._call_attempts({"cmd": "load", "path": path}, attempts=2)
    """
    assert codes(src) == ["PB801", "PB801"]


def test_pb801_shard_local_verbs_ok():
    # shrink/size/row verbs are shard-local by construction — not in scope
    src = """
    def stats(client):
        client._call({"cmd": "size", "table": None})
        client._call({"cmd": "shrink", "threshold": 0.1})
        client._call({"cmd": "pull_sparse_chunk", "keys": keys})
    """
    assert codes(src) == []


def test_pb801_dynamic_cmd_out_of_scope():
    # a verb that is not a compile-time constant is someone else's
    # dispatch layer (the 2-phase helper itself builds frames this way)
    src = """
    def send(client, verb):
        client._call({"cmd": verb, "table": None})
    """
    assert codes(src) == []


def test_pb801_cluster_impl_and_tests_exempt():
    src = """
    def two_phase(client):
        client._call({"cmd": "lifecycle_prepare", "verb": "end_day"})
    """
    assert codes(src, path="paddlebox_tpu/ps/cluster.py") == []
    assert codes(src, path="tests/test_ps_cluster.py") == []


def test_pb802_member_lifecycle_send():
    src = """
    def roll(clients):
        clients[0].end_day()
    """
    assert codes(src) == ["PB802"]


def test_pb802_member_save_through_attribute_chain():
    src = """
    def snap(fleet, path):
        fleet.servers[1].save(path, mode="all")
    """
    assert codes(src) == ["PB802"]


def test_pb802_unsubscripted_receiver_ok():
    # the sharded client's own methods fan out cluster-wide — calling
    # them on a plain receiver is exactly the sanctioned route
    src = """
    def roll(client, path):
        client.end_day()
        client.save(path, mode="all")
        engine.table.end_day()
    """
    assert codes(src) == []


def test_pb802_non_lifecycle_member_calls_ok():
    src = """
    def pump(self, shard):
        self._free[shard].pop()
        self.jobs[shard].run()
    """
    assert codes(src) == []


def test_pb801_suppression_escape():
    src = """
    def probe(client):
        # pboxlint: disable-next=PB801 -- single-server probe harness
        client._call({"cmd": "end_day", "table": None})
    """
    assert codes(src) == []


def test_pb803_hand_built_server_map():
    src = """
    def fleet_map(addrs):
        return ServerMap(addrs, epoch=3)
    """
    assert codes(src) == ["PB803"]


def test_pb803_membership_attr_mutation():
    src = """
    def bump(m, addrs):
        m.epoch = m.epoch + 1
        m.addrs = addrs
    """
    assert codes(src) == ["PB803", "PB803"]


def test_pb803_augassign_epoch():
    src = """
    def bump(self):
        self.epoch += 1
    """
    assert codes(src) == ["PB803"]


def test_pb803_sanctioned_constructors_and_reads_ok():
    # make_server_map / map_from_desc are the sanctioned routes, and
    # READING the membership fields is how routing is supposed to work
    src = """
    def route(client, desc, addrs, keys):
        m = make_server_map(addrs, epoch=0)
        m2 = map_from_desc(desc)
        if m2.epoch > m.epoch:
            client._adopt_map(m2)
        return m2.addrs, m2.partition(keys)
    """
    assert codes(src) == []


def test_pb803_impl_modules_and_tests_exempt():
    src = """
    def mint(addrs, e):
        return ServerMap(addrs, epoch=e)
    """
    assert codes(src, path="paddlebox_tpu/ps/cluster.py") == []
    assert codes(src, path="paddlebox_tpu/ps/reshard.py") == []
    assert codes(src, path="tests/test_ps_reshard.py") == []


def test_pb803_suppression_escape():
    src = """
    def mirror(self, n):
        # pboxlint: disable-next=PB803 -- fleet-level epoch mirror
        self.epoch = n
    """
    assert codes(src) == []


# -- PB806 trainer-namespaced rid groups -------------------------------------

def test_pb806_bare_group_literal_in_trainer_scope():
    src = """
    def push(client, grads):
        client.push_sparse(grads, group="fleet.d:chunk0")
    """
    assert codes(src, path="paddlebox_tpu/trainer/push.py") == ["PB806"]


def test_pb806_rank_suffixed_literal_ok():
    src = """
    def push(client, grads):
        client.push_sparse(grads, group="fleet.d.t0:chunk0")
    """
    assert codes(src, path="paddlebox_tpu/trainer/push.py") == []


def test_pb806_namespaced_group_helper_ok():
    # the sanctioned mint: not a literal, never flagged (rank=None is the
    # leader-failover namespace and also routes through the helper)
    src = """
    def push(client, grads, rank):
        client.push_sparse(grads,
                           group=namespaced_group("fleet.d", rank, "c0"))
        client.end_day(table=None,
                       group=namespaced_group("fleet.day", None, "d0"))
    """
    assert codes(src, path="paddlebox_tpu/trainer/push.py") == []


def test_pb806_fstring_group_without_namespace():
    src = """
    def push(client, grads, v):
        client.push_sparse(grads, group=f"fleet.d:{v}")
    """
    assert codes(src, path="paddlebox_tpu/fleet.py") == ["PB806"]


def test_pb806_fstring_group_with_rank_namespace_ok():
    src = """
    def push(client, grads, rank, v):
        client.push_sparse(grads, group=f"fleet.d.t{rank}:{v}")
    """
    assert codes(src, path="paddlebox_tpu/fleet.py") == []


def test_pb806_pin_group_positional():
    src = """
    def writeback(adapter, rank):
        adapter.pin_group(None, "fleet.wb:turn")
    """
    assert codes(src, path="paddlebox_tpu/trainer/runner.py") == ["PB806"]


def test_pb806_out_of_scope_module_silent():
    # PS-side code owns its own rid discipline — the trainer namespace
    # rule only binds the fleet/trainer modules
    src = """
    def push(client, grads):
        client.push_sparse(grads, group="ps.local:chunk0")
    """
    assert codes(src, path="paddlebox_tpu/ps/engine_util.py") == []


def test_pb806_suppression_escape():
    src = """
    def push(client, grads):
        # pboxlint: disable-next=PB806 -- single-trainer bootstrap path
        client.push_sparse(grads, group="fleet.d:chunk0")
    """
    assert codes(src, path="paddlebox_tpu/trainer/push.py") == []


# -- PB605 bounded fleet-collective retries (PB604 family) -------------------

def test_pb605_unbounded_retry_in_collective():
    src = """
    def pump(self, frame):
        while True:
            try:
                self._send(frame)
                return
            except ConnectionError:
                continue
    """
    assert codes(src, path="paddlebox_tpu/parallel/collective.py") \
        == ["PB605"]


def test_pb605_monotonic_deadline_ok():
    src = """
    import time

    def pump(self, frame, deadline):
        while True:
            try:
                self._send(frame)
                return
            except ConnectionError:
                if time.monotonic() > deadline:
                    raise PeerDead("send")
    """
    assert codes(src, path="paddlebox_tpu/parallel/collective.py") == []


def test_pb605_backoff_budget_ok():
    # a Backoff built outside the loop: its .sleep() verdict gating the
    # raise IS the deadline evidence
    src = """
    def pump(self, frame, bo):
        attempt = 0
        while True:
            try:
                self._send(frame)
                return
            except OSError:
                attempt += 1
                if not bo.sleep(attempt):
                    raise PeerDead("send")
    """
    assert codes(src, path="paddlebox_tpu/parallel/collective.py") == []


def test_pb605_exit_handler_and_teardown_swallow_ok():
    # an accept loop's `except OSError: return` is shutdown, not retry,
    # and `try: conn.close() except OSError: pass` is a cleanup swallow
    src = """
    def accept_loop(self):
        while True:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            try:
                conn.close()
            except OSError:
                pass
    """
    assert codes(src, path="paddlebox_tpu/data/shuffle_transport.py") == []


def test_pb605_out_of_scope_module_silent():
    src = """
    def pump(self, frame):
        while True:
            try:
                self._send(frame)
                return
            except ConnectionError:
                continue
    """
    assert codes(src, path="paddlebox_tpu/ps/service.py") == []


# -- PB301 step-path full-working-set sweeps ---------------------------------

def test_pb301_prefix_push_and_update_full_n_sweeps():
    """The PRE-FIX ps/fast_path.py push_and_update shape this rule exists
    for: merged [N] accumulators fed through full-[N] elementwise passes
    (one per scalar field) inside the jitted per-step function.  Each
    sweep statement must surface PB301."""
    src = """
    import jax.numpy as jnp

    def push_and_update(ws, idx, g_show, g_click, touched, cfg):
        show = jnp.where(touched, ws["show"] + g_show, ws["show"])
        click = jnp.where(touched, ws["click"] + g_click, ws["click"])
        ratio = cfg.lr * jnp.sqrt(
            cfg.g2 / (cfg.g2 + ws["embed_g2sum"]))
        create = touched & (ws["mf_size"] == 0)
        return show, click, ratio, create
    """
    assert codes(src, path="paddlebox_tpu/ps/fast_path.py") == ["PB301"] * 4


def test_pb301_ragged_gather_update_scatter_clean():
    """The [U]-domain shape: gather the touched rows, do the math on the
    gathered sub-array, scatter once — plus the structural uses
    (.shape/.dtype/.at) and bare aliasing.  All allowed."""
    src = """
    import jax.numpy as jnp

    def push_and_update(ws, u_rows, g_show):
        n = ws["show"].shape[0]
        sub = ws["show"][u_rows] + g_show
        out = dict(ws)
        out["show"] = ws["show"].at[u_rows].set(sub)
        out["mf_scale"] = ws["mf_scale"]
        mf = jnp.take(ws["mf"], u_rows, axis=0)
        created = (ws["mf_size"][u_rows] > 0).astype(ws["show"].dtype)
        return out, mf, created
    """
    assert codes(src, path="paddlebox_tpu/ps/mxu_path.py") == []


def test_pb301_relayout_set_arg_allowed_wrapped_call_not():
    """A bare ws[...] fed to a scatter .set() is a relayout copy
    (mxu_path pull-table build) — allowed; the same array routed through
    any other call or attribute first is math — flagged."""
    src = """
    def _pull_table(ws, tab, n, f):
        tab = tab.at[0, :n].set(ws["show"])
        tab = tab.at[1, :n].set(f(ws["click"]))
        tab = tab.at[2, :n].set(ws["embed_w"].T)
        return tab
    """
    assert codes(src, path="paddlebox_tpu/ps/mxu_path.py") == ["PB301"] * 2


def test_pb301_out_of_scope_silent():
    """Host-side table code legitimately sweeps [N]; the rule only scopes
    the two step-lowering modules and functions taking ``ws``."""
    sweep = """
    import jax.numpy as jnp

    def compact(ws, live):
        return jnp.where(live, ws["show"] * 0.98, ws["show"])
    """
    no_ws = """
    import jax.numpy as jnp

    def decay(table, live):
        return jnp.where(live, table["show"] * 0.98, table["show"])
    """
    assert codes(sweep, path="paddlebox_tpu/ps/host_table.py") == []
    assert codes(no_ws, path="paddlebox_tpu/ps/fast_path.py") == []


def test_pb301_multiline_statement_single_finding_and_suppression():
    """A multiline sweep anchors at the statement's first line (one
    finding, not one per operand) and a disable-next comment there
    suppresses it."""
    flagged = """
    import jax.numpy as jnp

    def step(ws, touched, g):
        delta = jnp.where(
            touched,
            ws["delta_score"] + g,
            ws["delta_score"])
        return delta
    """
    assert codes(flagged, path="paddlebox_tpu/ps/fast_path.py") == ["PB301"]
    suppressed = """
    import jax.numpy as jnp

    def step(ws, touched, g):
        # pboxlint: disable-next=PB301 -- documented-cheap [N] scalar pass
        delta = jnp.where(
            touched,
            ws["delta_score"] + g,
            ws["delta_score"])
        return delta
    """
    assert codes(suppressed, path="paddlebox_tpu/ps/fast_path.py") == []
