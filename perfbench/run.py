"""Run one cell of the on-chip benchmark once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for. The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, ``breakdown`` (traced
runs) and ``checks`` (each number compared, beside its limit). Without a TPU,
or with fewer chips than the cell needs, it prints no result and exits 3.
"""

import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT

if __name__ == "__main__":
    from perfbench import harness
    sys.exit(harness.main(t_start=T_START))
