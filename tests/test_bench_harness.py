"""bench.py harness logic: watchdog, partial emission, JSON contract.

The driver's view of a round is bench.py's LAST stdout line plus its exit
code — these tests pin that contract: always exactly one parseable JSON
object with metric/value/unit and the device it ran on
(platform/device_kind/n_devices), a watchdog that emits the best partial
value instead of hanging, non-finite floats sanitized to null, a non-zero
exit beside every error line, and NO fallback that would file a CPU number
under the per-chip metric.  Run in-process (module import, no subprocess)
with the phase clock manipulated directly, or through the supervisor in a
subprocess with BENCH_FORCE_CPU=1 (the functional mode: platform "cpu",
METRIC_OFF_CHIP).
"""

import importlib.util
import io
import json
import os
import sys
import time

import pytest


BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "bench.py")


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench_under_test",
                                                  BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CHIP_METRIC = _load_bench().METRIC


@pytest.fixture()
def bench(monkeypatch):
    """A fresh bench module per test (module-level _STATE is global)."""
    return _load_bench()


def _last_json(capture: io.StringIO):
    lines = [ln for ln in capture.getvalue().splitlines() if ln.strip()]
    assert lines, "bench printed nothing"
    return json.loads(lines[-1])


def test_emit_contract(bench, monkeypatch):
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench._STATE["device"] = {"platform": "tpu",
                              "device_kind": "TPU v5 lite", "n_devices": 1}
    bench.emit(123.456, final=True, basis="end_to_end", stage="full")
    line = _last_json(out)
    assert line["metric"] == bench.METRIC
    assert line["value"] == 123.5
    assert line["unit"] == "examples/s"
    assert (line["platform"], line["device_kind"], line["n_devices"]) == \
        ("tpu", "TPU v5 lite", 1)
    # the v5p-target ratio is gone: no line divides by another chip's goal
    assert "vs_baseline" not in line
    assert line["basis"] == "end_to_end"
    assert bench._STATE["done"] is True


@pytest.mark.parametrize("platform", ["cpu", None])
def test_chip_metric_name_is_reserved_for_the_tpu(bench, monkeypatch,
                                                  platform):
    """A line that did not run on a TPU — the BENCH_FORCE_CPU functional
    mode, or an error before any backend answered — is never filed under
    the per-chip metric."""
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench._STATE["device"] = {"platform": platform, "device_kind": platform,
                              "n_devices": 1 if platform else 0}
    bench.emit(5000.0, final=True, stage="full")
    line = _last_json(out)
    assert line["metric"] == bench.METRIC_OFF_CHIP != bench.METRIC
    assert line["platform"] == platform


def test_emit_sanitizes_non_finite(bench, monkeypatch):
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.emit(0.0, final=True,
               partial={"auc": float("nan"), "e2e": float("inf")})
    line = _last_json(out)  # must parse under strict JSON
    assert line["partial"]["auc"] is None
    assert line["partial"]["e2e"] is None


def test_best_prefers_e2e_over_smoke(bench):
    bench.record(smoke_device_step=10.0)
    assert bench._best() == 10.0
    bench.record(device_step=50.0)
    assert bench._best() == 50.0
    bench.record(e2e=40.0)
    assert bench._best() == 40.0   # e2e is the headline even if smaller


def test_watchdog_emits_partial_on_expired_phase(bench, monkeypatch):
    """A wedged phase must produce the best partial value + the phase name,
    not a hang or a bare 0.0."""
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    exited = {}

    def fake_exit(code):
        exited["code"] = code
        raise SystemExit                        # always escape the loop

    monkeypatch.setattr(os, "_exit", fake_exit)
    bench.record(device_step=473091.0)
    bench.set_phase("full:e2e", budget_s=-1)    # already expired
    with pytest.raises(SystemExit):
        bench._watchdog()
    line = _last_json(out)
    assert line["value"] == 473091.0
    assert "full:e2e" in line["error"]
    assert line["last_phase"] == "full:e2e"
    assert exited["code"] != 0      # an error line never exits 0


def test_watchdog_respects_done_flag(bench, monkeypatch):
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    bench._STATE["done"] = True
    t0 = time.time()
    bench._watchdog()                           # returns promptly, no emit
    assert time.time() - t0 < 10


def test_phase_budget_capped_by_global_deadline(bench):
    hard = bench.T0 + bench.TOTAL_BUDGET - 20
    bench.set_phase("x", budget_s=10 ** 9)
    assert bench._STATE["deadline"] <= hard


# -- supervisor: killable backend init (the round-4 failure mode) -----------

def _run_bench(env_extra, timeout):
    import subprocess
    env = dict(os.environ)
    env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, BENCH_PATH], capture_output=True, text=True,
        env=env, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, f"no stdout; stderr tail: {proc.stderr[-800:]}"
    # the per-chip metric's name appears on no line of a run without a TPU
    assert CHIP_METRIC not in proc.stdout
    return json.loads(lines[-1]), proc.stderr, proc.returncode


def _assert_cpu_functional_line(line):
    """A BENCH_FORCE_CPU line: says cpu, and not under the chip metric."""
    assert line["platform"] == "cpu", line
    assert line["device_kind"] and line["n_devices"] >= 1
    assert line["metric"] != CHIP_METRIC


def test_supervisor_kills_hung_backend_and_reports(tmp_path):
    """A jax.devices() hang must not eat the whole budget: the supervisor
    kills the wedged child, retries, and still prints one parseable JSON
    line with the wedge named."""
    line, err, rc = _run_bench({
        "BENCH_TEST_HANG_INIT": "1",
        "BENCH_BACKEND_ATTEMPT_S": "5",
        "BENCH_TIMEOUT_S": "60"}, timeout=90)
    assert rc != 0
    assert line["value"] == 0.0
    assert "wedged" in line.get("error", "")
    assert line["platform"] is None and line["n_devices"] == 0  # none seen
    assert line["supervisor_attempts"] >= 2      # it retried
    assert "killing" in err
    log = line.get("attempt_log")
    assert log and len(log) == line["supervisor_attempts"]
    assert all(e["last_phase"] == "backend-init" for e in log)


def test_supervisor_recovers_from_transient_hang(tmp_path):
    """First attempt wedges (a transient failure), second succeeds: the
    recorded result is the successful smoke run, not 0.0."""
    marker = str(tmp_path / "hang_once")
    open(marker, "w").close()
    line, _err, rc = _run_bench({
        "BENCH_TEST_HANG_INIT_ONCE": marker,
        "BENCH_FORCE_CPU": "1",
        "BENCH_SMOKE_ONLY": "1",
        "BENCH_BACKEND_ATTEMPT_S": "10",
        "BENCH_TIMEOUT_S": "240"}, timeout=260)
    assert rc == 0
    assert line["value"] > 0
    assert "error" not in line
    assert line["failed_phases"] == []
    assert line["supervisor_attempts"] == 2
    assert line["stage"] == "smoke"
    _assert_cpu_functional_line(line)


def test_supervisor_never_falls_back_to_cpu_after_wedge():
    """The fallback's absence, pinned: a wedged accelerator attempt used
    to switch later attempts to JAX_PLATFORMS=cpu and report the CPU
    number as the round's result.  Now every attempt asks for the same
    platform, and the round ends in an error line naming it and a
    non-zero exit — never in `backend up: cpu`."""
    line, err, rc = _run_bench({
        "BENCH_TEST_HANG_INIT": "1",
        "JAX_PLATFORMS": "tpu",
        "BENCH_BACKEND_ATTEMPT_S": "3",
        "BENCH_TIMEOUT_S": "52"}, timeout=80)
    assert rc != 0
    assert "falling back" not in err
    assert "backend up: cpu" not in err
    assert "platform_fallback" not in line
    assert "wedged on platform 'tpu'" in line["error"]
    log = line["attempt_log"]
    assert len(log) >= 2                          # retried, same platform
    assert all(e["platform"] == "tpu"
               and e["last_phase"] == "backend-init" for e in log)


def test_no_tpu_without_force_cpu_fails_and_names_the_platform():
    """`python bench.py` on a machine whose JAX finds only the CPU: the
    child reaches a backend, sees it is not a TPU, and fails.  Non-zero
    exit, an error naming the platform found, nothing under the per-chip
    metric (checked on every stdout line by _run_bench)."""
    env = {"JAX_PLATFORMS": "cpu", "BENCH_BACKEND_ATTEMPT_S": "60",
           "BENCH_TIMEOUT_S": "300"}
    assert "BENCH_FORCE_CPU" not in os.environ
    line, err, rc = _run_bench(env, timeout=200)
    assert rc != 0
    assert "no TPU" in line["error"] and "'cpu'" in line["error"]
    assert line["platform"] == "cpu" and line["value"] == 0.0
    assert line["supervisor_attempts"] <= 2      # deterministic: no spin


def test_better_prefers_clean_full_over_higher_value_smoke(bench):
    smoke = {"metric": bench.METRIC, "value": 9999.0, "stage": "smoke"}
    full = {"metric": bench.METRIC, "value": 1200.0, "stage": "full"}
    assert bench._better(smoke, full) is full
    assert bench._better(full, smoke) is full
    # error-free full still beats an errored full partial with more value
    part = {"metric": bench.METRIC, "value": 99999.0, "stage": "full",
            "error": "watchdog: ..."}
    assert bench._better(part, full) is full
    # an error line beats the bare backend-up marker at equal value
    up = {"metric": bench.METRIC, "value": 0.0, "stage": "backend-up"}
    err = {"metric": bench.METRIC, "value": 0.0, "error": "died"}
    assert bench._better(up, err) is err
    assert bench._better(err, up) is err


def test_supervisor_stops_on_repeated_deterministic_failure():
    """A post-backend failure that repeats identically must stop the retry
    loop (deterministic, not transient) — and the final line carries it."""
    line, err, rc = _run_bench({
        "BENCH_FORCE_CPU": "1",
        "BENCH_TEST_FAIL_AFTER_INIT": "boom-deterministic",
        "BENCH_BACKEND_ATTEMPT_S": "30",
        "BENCH_TIMEOUT_S": "600"}, timeout=300)
    assert rc != 0
    assert "boom-deterministic" in line.get("error", "")
    assert line["supervisor_attempts"] <= 2      # stopped early, not 20
    _assert_cpu_functional_line(line)


# -- wedge postmortems + feed-gap + compare mode -----------------------------

def test_watchdog_writes_postmortem_before_error_line(bench, monkeypatch,
                                                      tmp_path):
    """Phase-budget expiry must leave a stack bundle on disk BEFORE the
    error line, and the line must carry its path."""
    from paddlebox_tpu import flags
    from paddlebox_tpu.utils import doctor  # registers obs_postmortem_dir
    assert doctor is not None
    flags.set_flags({"obs_postmortem_dir": str(tmp_path)})
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)

    def fake_exit(code):
        raise SystemExit

    monkeypatch.setattr(os, "_exit", fake_exit)
    try:
        bench.record(device_step=1000.0)
        bench.set_phase("full:compile", budget_s=-1)
        with pytest.raises(SystemExit):
            bench._watchdog()
    finally:
        flags.set_flags({"obs_postmortem_dir": ""})
    line = _last_json(out)
    pm = line["postmortem"]
    assert pm and os.path.exists(pm), line
    bundle = json.load(open(pm))
    assert "full:compile" in bundle["reason"]
    assert any(t["name"] == "MainThread" for t in bundle["threads"])
    assert isinstance(bundle["stats"], dict)


def test_wedged_child_ships_postmortem_bundle(tmp_path):
    """The acceptance scenario: a simulated post-backend wedge.  The
    child's watchdog writes a postmortem naming the stuck phase and the
    stuck thread, and the supervisor's attempt_log carries its path."""
    pm_dir = str(tmp_path / "pm")
    line, _err, rc = _run_bench({
        "BENCH_FORCE_CPU": "1",
        "BENCH_TEST_WEDGE_PHASE": "1",
        "BENCH_TEST_WEDGE_BUDGET_S": "3",
        "FLAGS_obs_postmortem_dir": pm_dir,
        "BENCH_BACKEND_ATTEMPT_S": "60",
        "BENCH_TIMEOUT_S": "150"}, timeout=200)
    assert rc != 0
    assert "wedge-sim" in line.get("error", ""), line
    _assert_cpu_functional_line(line)
    log = line.get("attempt_log")
    assert log, line
    pm = log[0].get("postmortem")
    assert pm and os.path.exists(pm), log
    bundle = json.load(open(pm))
    assert "wedge-sim" in bundle["reason"]
    sleeper = [t for t in bundle["threads"] if t["name"] == "wedge-sleeper"]
    assert sleeper, [t["name"] for t in bundle["threads"]]
    assert any("sleep" in fr for fr in sleeper[0]["stack"])
    # last-N flight events rode along, including the phase trail
    phases = [e for e in bundle["flight"] if e["kind"] == "bench_phase"]
    assert any(e["phase"] == "wedge-sim" for e in phases)
    assert isinstance(bundle["stats"], dict)


def _result_file(path, value, gap, obs=None, wrapper=False):
    line = {"metric": "paddlebox_steady_examples_per_sec", "value": value,
            "unit": "examples/s", "final": True, "feed_gap_ratio": gap,
            "obs_stats": obs or {}}
    obj = {"n": 3, "cmd": "python bench.py", "rc": 0, "tail": "",
           "parsed": line} if wrapper else line
    path.write_text(json.dumps(obj))
    return str(path)


def test_compare_flags_throughput_regression(bench, monkeypatch, tmp_path):
    old = _result_file(tmp_path / "old.json", 1000.0, 2.0,
                       obs={"ps.client.retry": 1.0})
    new = _result_file(tmp_path / "new.json", 800.0, 2.0,
                       obs={"ps.client.retry": 9.0}, wrapper=True)
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    rc = bench.compare(old, new, threshold=0.05)
    assert rc == 1
    rep = json.loads(out.getvalue())
    assert rep["ok"] is False
    assert any("value" in r for r in rep["regressions"])
    assert rep["value"]["delta_frac"] == pytest.approx(-0.2)
    # obs movers beyond threshold are surfaced (informational)
    assert "ps.client.retry" in rep["obs_deltas"]


def test_compare_flags_feed_gap_regression_and_threshold(bench, monkeypatch,
                                                         tmp_path):
    old = _result_file(tmp_path / "old.json", 1000.0, 2.0)
    new = _result_file(tmp_path / "new.json", 1010.0, 3.0)
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    assert bench.compare(old, new, threshold=0.05) == 1   # gap +50%
    assert bench.compare(old, new, threshold=0.6) == 0    # within 60%


def test_compare_feed_gap_gate_skipped_when_device_idle(bench, monkeypatch,
                                                        tmp_path):
    """CPU-basis records carry device_busy_frac ~ 0: the gap ratio's
    denominator is milliseconds of device time, so a timing wobble
    swings it by double digits — the gate must not arm (the delta is
    still reported, flagged degenerate).  A real device measurement
    keeps it armed."""
    def rf(path, gap, db):
        path.write_text(json.dumps(
            {"metric": "m", "value": 1000.0, "final": True,
             "feed_gap_ratio": gap, "device_busy_frac": db}))
        return str(path)

    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    assert bench.compare(rf(tmp_path / "o1.json", 2.0, 0.0001),
                         rf(tmp_path / "n1.json", 3.0, 0.0002),
                         threshold=0.05) == 0
    rep = json.loads(out.getvalue())
    assert rep["feed_gap_ratio"]["degenerate"] is True
    monkeypatch.setattr(sys, "stdout", io.StringIO())
    assert bench.compare(rf(tmp_path / "o2.json", 2.0, 0.5),
                         rf(tmp_path / "n2.json", 3.0, 0.5),
                         threshold=0.05) == 1


def test_compare_flags_sparse_share_regression(bench, monkeypatch, tmp_path):
    """step_ms.sparse_share creeping back up is the padded-dense
    regression class — compare gates it."""
    def rf(path, share):
        path.write_text(json.dumps(
            {"metric": "m", "value": 1000.0, "final": True,
             "step_ms": {"sparse_share": share}}))
        return str(path)

    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    assert bench.compare(rf(tmp_path / "o1.json", 0.40),
                         rf(tmp_path / "n1.json", 0.60),
                         threshold=0.05) == 1
    rep = json.loads(out.getvalue())
    assert any("sparse_share" in r for r in rep["regressions"])
    assert rep["sparse_share"]["delta_frac"] == pytest.approx(0.5)
    monkeypatch.setattr(sys, "stdout", io.StringIO())
    assert bench.compare(rf(tmp_path / "o2.json", 0.40),
                         rf(tmp_path / "n2.json", 0.41),
                         threshold=0.05) == 0


def test_compare_cli_dispatch(tmp_path):
    import subprocess
    old = _result_file(tmp_path / "old.json", 1000.0, 2.0)
    new = _result_file(tmp_path / "new.json", 990.0, 2.1)
    proc = subprocess.run(
        [sys.executable, BENCH_PATH, "--compare", old, new,
         "--threshold=0.1"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["ok"] is True
    bad = subprocess.run(
        [sys.executable, BENCH_PATH, "--compare", old],
        capture_output=True, text=True, timeout=60)
    assert bad.returncode == 2                    # usage error


def test_supervisor_smoke_line_never_shadows_dead_full_run():
    """A clean MID-RUN smoke line must not pass for the round result when
    the child dies before the full run: the final line keeps the smoke
    value (best partial evidence) but carries an error naming the death."""
    line, _err, rc = _run_bench({
        "BENCH_FORCE_CPU": "1",
        "BENCH_TEST_DIE_AFTER_SMOKE": "1",
        "BENCH_BACKEND_ATTEMPT_S": "30",
        "BENCH_TIMEOUT_S": "360"}, timeout=380)
    assert rc != 0
    assert line.get("error"), line                # never a clean fake
    assert line["value"] > 0                      # smoke evidence kept
    assert line.get("stage") == "smoke"
    _assert_cpu_functional_line(line)
