"""chip_smoke.py off the chip, and the compile-cache placement rule.

The chip itself is reached only through the chip tool; what tier-1 can
pin is (a) the same code runs end to end at the --tiny size on the CPU,
(b) the full-size check refuses a machine without a TPU instead of
quietly measuring the CPU, and (c) where the persistent compile cache
goes.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_extra=None, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)      # conftest's eight virtual devices
    env.update(env_extra or {})
    return subprocess.run([sys.executable, SMOKE] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_tiny_runs_every_stage_on_cpu():
    proc = _run(["--tiny"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    # the result line: exactly these keys, the device as JAX reports it
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    tag = "[chip_smoke] summary: "
    assert lines[-2].startswith(tag), lines[-2][:200]
    out = json.loads(lines[-2][len(tag):])
    assert out["tiny"] is True
    assert {k: v["resolved_path"] for k, v in out["stages"].items()} == {
        "mxu": "mxu", "reference": "reference"}
    assert out["native"]["ok"] is True
    assert out["claim"] is None
    # the two relaxations of --tiny are announced, not silent
    assert "platform check and Mosaic assertion RELAXED" in proc.stdout


def test_full_size_refuses_a_machine_without_tpu():
    proc = _run([])
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""        # a refusal prints no result


def test_chips_beyond_the_host_fail_instead_of_shrinking_the_mesh():
    proc = _run(["--tiny", "--chips", "4"],
                {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert proc.returncode != 0
    assert "--chips 4" in proc.stderr and '"ok"' not in proc.stdout


# -- compile cache placement (paddlebox_tpu/utils/compile_cache.py) ---------

def test_cache_dir_from_env_sets_nothing_in_code(monkeypatch):
    import jax
    from paddlebox_tpu.utils import compile_cache

    def boom(*a, **kw):
        raise AssertionError("jax.config.update called with the env set")

    monkeypatch.setenv(compile_cache.ENV, "/some/operator/path")
    monkeypatch.setattr(jax.config, "update", boom)
    assert compile_cache.enable() == "/some/operator/path"


def test_cache_dir_default_is_fixed_in_checkout_across_calls_and_pids():
    code = ("from paddlebox_tpu.utils import compile_cache as c; import jax;"
            "a = c.enable(); b = c.enable();"
            "assert a == b == jax.config.jax_compilation_cache_dir;"
            "print(a)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    got = [subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120,
                          check=True).stdout.strip() for _ in range(2)]
    assert got[0] == got[1] == os.path.join(REPO, ".jax_cache")


# -- what JAX says it compiled (compile_cache.watch_compiles) ---------------

def test_a_silent_retrace_counts_in_jit_compile_s():
    """A jitted function that meets a new shape compiles again without
    anybody asking: the program's own counter sees it, and a caller that
    times the dispatch can tell (``compile_requests`` moved)."""
    import jax
    import numpy as np
    from paddlebox_tpu import fleet
    from paddlebox_tpu.utils import compile_cache
    from paddlebox_tpu.utils.monitor import stat_snapshot

    def count():
        return stat_snapshot("jit").get("jit.compile_s.count", 0)

    fleet.init()
    fleet.init()                        # registers once: no double count
    f = jax.jit(lambda x: (x * 3 + 1).sum())
    f(np.ones(7, np.float32)).block_until_ready()
    seen, requests = count(), compile_cache.compile_requests
    assert seen >= 1
    f(np.ones(7, np.float32)).block_until_ready()   # same shape: nothing
    assert (count(), compile_cache.compile_requests) == (seen, requests)
    f(np.ones(11, np.float32)).block_until_ready()  # new shape: a retrace
    assert count() == seen + 1
    assert compile_cache.compile_requests == requests + 1
