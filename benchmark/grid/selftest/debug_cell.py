"""A cell of ``cells.json`` on the chip, for finding a driver's faults
before a real cell pays for them:

    python3 benchmark/grid/selftest/debug_cell.py --workload \
        debug-train-1chip --seed 1 --seconds 10 --trace 1

Same arguments, device check and result line as ``run.py``; never a
measurement of a cell."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
GRID = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(GRID))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()
    for p in (HERE, GRID, ROOT):
        sys.path.insert(0, p)
    from mxtpu import runtime
    runtime.use_compile_cache()
    import run as grid_run
    import tiny
    parts = grid_run.load_cell(tiny.bench(), args.workload, HERE)
    device = grid_run.device_or_die(parts["cell"]["chips"])
    print(json.dumps(grid_run.run_cell(
        parts, device, args.seed, args.seconds, bool(args.trace))),
        flush=True)


if __name__ == "__main__":
    main()
