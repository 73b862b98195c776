"""The benchmark's own tests: ``python3 -m pytest benchmark/tests -q``.

They run here on the CPU and check arithmetic, data and plumbing; nothing
they compute is a speed.  Cells are run in child processes (``--rehearse``
pins the CPU platform and the virtual device count itself)."""

import json
import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_cell(args, root=ROOT, env=None, timeout=600):
    """``benchmark/run.py`` with ``args`` from ``root``; returns
    (exit code, parsed last stdout line or None, stderr)."""
    full_env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    full_env.update(env or {})
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=root,
        env=full_env, capture_output=True, text=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    return proc.returncode, (json.loads(lines[-1]) if lines else None), \
        proc.stderr
