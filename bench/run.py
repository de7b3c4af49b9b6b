"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell's pieces are found by name
(see ``harness.py``).  The run needs the chips the cell asks for: without
them it exits non-zero and prints no result.  With ``--trace 0`` the
result holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of a short window.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
