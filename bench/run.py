#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload jacobi2d-16k.solve1000 --seed 7 \\
        --seconds 10 --trace 0

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and ``checks`` last).  It exits non-zero, printing
no result, where JAX finds no TPU, fewer chips than the cell asks for,
or a device kind missing from ``bench/peaks.json``.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
