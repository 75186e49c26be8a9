"""The control of the correctness check: the plain reference, computed in
bfloat16 (the precision below the configurations' float32), put in the
program's place and judged by the cell's own check against the float32
reference.  Every cell's check must come out not correct on it.

    python3 benchmarks/chip/control.py --workload protocol_study.periodic \\
        --seeds 11 12 13

Prints one JSON line per seed with the numbers the check compared; the
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def control_results(runner):
    """What the window would hold, with the bfloat16 reference in the
    program's place."""
    import jax.numpy as jnp
    return runner.reference_answers(jnp.bfloat16)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    import harness
    cell = harness.find_cell(harness.load_spec(), args.workload)
    devs = harness.tpu_devices(cell.chips)
    runner_mod = harness.load_module("runners", cell.config["runner"])
    for seed in args.seeds:
        runner = runner_mod.Runner(cell.config, cell.traffic, seed,
                                   cell.chips)
        checks = runner.check(control_results(runner))
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control": "bfloat16",
            "correct": all(c["ok"] for c in checks.values()),
            "checks": {k: c["value"] for k, c in checks.items()},
            "device": harness.device_label(devs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
