#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout. Its configuration, traffic mix and per-layer metrics are
files under ``chipbench/`` found by the names that entry gives. The last
line of standard output is the result as one JSON object; see
``chipbench/harness.py``.
"""

import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
