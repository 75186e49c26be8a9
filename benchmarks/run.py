# One function per paper table/figure + framework benchmarks.
# Prints ``name,us_per_call,derived`` CSV.
#
# ``--smoke`` (CI fast mode) clamps every timing loop to one warmup + one
# iteration and skips the model-building suites (kernels, train_loop,
# serving) — the paper-model suites still run end-to-end, so the
# compile-once assertions and derived columns are exercised on every push.
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from benchmarks import common
from benchmarks.common import emit
from repro import compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(description="benchmark runner")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: 1 warmup + 1 iter per timing, "
                         "paper-model suites only")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the rows as JSON (the weekly CI "
                         "trend artifact) plus the repo-root "
                         "BENCH_flitsim.json flit-simulation trend file")
    args = ap.parse_args()
    common.SMOKE = args.smoke
    compile_cache.enable()

    rows = []
    from benchmarks import (
        bench_flitsim, bench_kernels, bench_lint, bench_paper_figures,
        bench_roofline, bench_serving, bench_streaming, bench_train_loop,
    )
    suites = [
        # lint first: the same pass gates CI, and the row keeps its
        # wall-clock on the trend (budget: bench_lint.LINT_BUDGET_S)
        ("lint", bench_lint.run),
        ("paper_figures", bench_paper_figures.run),
        ("flitsim", bench_flitsim.run),
        ("streaming", bench_streaming.run),
        ("kernels", bench_kernels.run),
        ("train_loop", bench_train_loop.run),
        ("serving", bench_serving.run),
        ("roofline", bench_roofline.run),
    ]
    if args.smoke:
        # serving stays: its trace-capacity rows need no model build
        # (bench_serving skips the live-engine row itself under smoke)
        skipped = {"kernels", "train_loop"}
        suites = [(n, fn) for n, fn in suites if n not in skipped]
        print(f"# smoke mode: skipping {sorted(skipped)}", file=sys.stderr)
    failed = []
    print("name,us_per_call,derived")
    for name, fn in suites:
        try:
            fn(rows)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    emit(rows)
    if args.json:
        out_dir = os.path.dirname(os.path.abspath(args.json))
        os.makedirs(out_dir, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke,
                       "failed_suites": failed,
                       "rows": [{"name": n, "us_per_call": us,
                                 "derived": d} for n, us, d in rows]},
                      f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)
        # repo-root flit-simulation trend file: batched-sweep us, the
        # adaptive-vs-fixed speedup, the cycles-to-convergence
        # histograms, the streaming sharded-sweep rows (async prefetch
        # speedup + overlap fraction), and the serving trace-capacity
        # rows (tokens/sec tied to sim_bandwidth_gbs) — CPU timings,
        # untracked, uploaded per CI matrix cell
        flit_rows = [{"name": n, "us_per_call": us, "derived": d}
                     for n, us, d in rows
                     if n.startswith(("flitsim/", "streaming/",
                                      "serving/"))]
        if flit_rows:
            trend = os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "BENCH_flitsim.json")
            with open(trend, "w") as f:
                json.dump({"smoke": args.smoke, "rows": flit_rows},
                          f, indent=1)
            print(f"# wrote {trend}", file=sys.stderr)
    if failed:
        print(f"FAILED_SUITES: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == '__main__':
    main()
