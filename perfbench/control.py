"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 perfbench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in one process: serve the cell for ``--seconds`` as a run
does, then compare the served tokens with the float32 reference (the
program's reading) and, at the same positions, the tokens the reference
computed in fp8 would put first (the control's reading), and put the
control's reading through the run's own check in place of the program's.
One JSON line per seed; it exits 1 if the check passes the control on any
seed. The benchmark's own runs do not run this.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT


def main(argv=None) -> int:
    import argparse
    from perfbench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    try:
        devices = harness.chip_devices(cell.chips)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    failed_all = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        served = harness.serve(cell, seed, args.seconds, devices)
        t1 = time.perf_counter()
        lines = []
        checks = harness.check(cell, served, seed, control=True,
                               log=lambda s, **_: lines.append(s))
        g = json.loads(lines[0][len("readings "):])
        ok = harness.passed(checks)
        failed_all &= not ok
        g.update(workload=cell.name, seed=seed, ticks=len(served.ticks),
                 serve_s=t1 - t0, check_s=time.perf_counter() - t1,
                 control_passed=ok)
        print(json.dumps(g), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
