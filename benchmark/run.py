#!/usr/bin/env python3
"""The benchmark's one command: a run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

See ``benchmark/harness/runner.py`` for what a run does and
``benchmark/harness/spec.py`` for how a cell's files are found.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark.harness import runner
    sys.exit(runner.main())
