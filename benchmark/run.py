#!/usr/bin/env python3
"""Run one cell of the benchmark once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on the machine that holds the chips the cell
asks for.  Prints human-readable lines, each naming the device, and as the
last line of standard output one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``).  Exits
nonzero, with no result line, where JAX reports no TPU or fewer chips than
the cell needs.  See ``benchmark/README.md``.
"""
import time

T_PROCESS_START = time.perf_counter()   # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    from benchmark import harness

    sys.exit(harness.main(None, ROOT, T_PROCESS_START))
