"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload joint_space.1e7 \\
        --seed 1234 --seconds 30 --trace 0

Prints the compared numbers beside their limits as the last lines of
standard error, and one JSON object as the last line of standard output.
Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    import harness
    cell = harness.find_cell(harness.load_spec(), args.workload)
    try:
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
