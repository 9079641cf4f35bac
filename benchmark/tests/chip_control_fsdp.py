"""The control of the FSDP2 cells on the chip, at the cell's
own size: runs of a cell with the control planted (tests/control_fsdp.py),
one per seed, in one process, each read by the harness's own comparison.

    python3 benchmark/tests/chip_control_fsdp.py --workload <name> \
        --seconds <s> --seeds <n> [<n> ...]

Prints one JSON line per seed. Not part of the benchmark's runs.
"""
import argparse
import json
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path[:0] = [os.path.dirname(BENCH), BENCH, TESTS]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    import control_fsdp as control
    from yardstick import runner, spec
    algs = control.plant(setattr, os.environ.__setitem__)
    cell = spec.load_cell(a.workload)
    for seed in a.seeds:
        algs.clear()
        r = runner.run_cell(cell, seed, a.seconds, False)
        print(json.dumps({"control": a.workload, "seed": seed,
                          "correct": r["correct"], "checks": r["checks"],
                          "algs": dict(algs)}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
