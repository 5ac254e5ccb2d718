#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of stdout is one JSON object (correct, attempted, failed,
metrics, device, and breakdown when traced).  See perf/README.md.
"""

import time

T_START = time.perf_counter()   # set-up counts from here

import os       # noqa: E402
import sys      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)    # perf.* and dpark_tpu from this checkout

if __name__ == "__main__":
    from perf.lib import runner
    sys.exit(runner.main(sys.argv[1:], T_START))
