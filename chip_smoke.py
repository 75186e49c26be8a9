"""Smoke run of the design-space explorer on a TPU, at the explorer's own
sizes, through its normal entry points.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: phase C's space on
                                      # four devices against one

A  the ``--bridge`` design-space report (representative workloads) whose
   winner labels must equal ``experiments/golden/design_space_summary.json``;
B  the fixed, adaptive and Pallas flit-simulator engines on four grids:
   every engine within 1e-3 of the fixed scan (the reference) with the
   same winners, and every ``flit_sim`` launch lowered to a Mosaic kernel;
C  a streamed joint space of >= 10^6 cells at the default simulator
   horizons.

Each phase prints one JSON line with its set-up (first call, compiles
included) and warm times and its checks, labelled with the device.  The
last line, ``{"ok": true, "device": {...}}``, is printed only when the
platform is a TPU and every check passed; otherwise the exit code is
non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import sys
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(ROOT, "experiments", "golden",
                      "design_space_summary.json")
#: engine contract against the fixed-horizon reference
TOL = 1e-3
#: phase C's streamed space: 250 perturbations x 4 PHYs x 25 backlogs x
#: 41 mixes = 1,025,000 cells at the default horizons
STREAM_SHAPE = (250, 25, 41)
CHUNK_CELLS = 4096


def load(relpath: str):
    """Import a repo script (``examples/``, ``tools/``) by its path."""
    name = os.path.splitext(os.path.basename(relpath))[0]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_label() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def emit(line: dict) -> None:
    print(json.dumps({**line, "device": device_label()}), flush=True)


def run_twice(fn):
    """(cold result, warm result, timing): the first call carries the
    compiles (set-up), the second runs warm."""
    from repro.core import cache_stats
    m0 = cache_stats().misses
    t0 = time.perf_counter()
    cold = fn()
    t1 = time.perf_counter()
    m1 = cache_stats().misses
    warm = fn()
    t2 = time.perf_counter()
    return cold, warm, {"setup_s": t1 - t0, "warm_s": t2 - t1,
                        "compiles": m1 - m0,
                        "warm_compiles": cache_stats().misses - m1}


# -- A: the explorer report ---------------------------------------------------


def phase_a() -> dict:
    explorer = load("examples/memsys_explorer.py")
    summarize = load("tools/design_space_summary.py").summarize

    def report():
        # the explorer's progress prints go to stderr; stdout holds the
        # JSON lines
        with contextlib.redirect_stdout(sys.stderr):
            ds = explorer.bridge_report(explorer.representative_reports())
        return json.loads(json.dumps(summarize(ds), sort_keys=True))

    cold, warm, timing = run_twice(report)
    with open(GOLDEN) as f:
        golden = json.load(f)
    diff = sorted(k for k in set(golden) | set(cold)
                  if golden.get(k) != cold.get(k))
    return {"phase": "A", "name": "bridge_report", **timing,
            "sections_differing_from_golden": diff,
            "checks": {"winners_equal_golden": cold == golden,
                       "warm_equals_cold": warm == cold}}


# -- B: the engines against the fixed reference -------------------------------


def _mixes(n: int):
    from repro.core import mix_grid
    gx, gy = mix_grid(n)
    return list(zip(np.asarray(gx).tolist(), np.asarray(gy).tolist()))


def engine_grids() -> dict:
    """name -> (axes, metric).  The drained symmetric grid and the dense
    asymmetric perturbation grid are the periodic detectors' grids; the
    125-point sweep runs the symmetric chunk kernel (backlog 64 is past
    the periodic gate); the joint pipelining grid runs its chunk kernel."""
    from repro.core import axis, flitsim
    perts = [{}] + [{"total_lanes": round(0.6 + 0.03 * q, 4)}
                    for q in range(30)]
    return {
        "sym_drained_297": ([
            axis("protocol", tuple(flitsim.SYMMETRIC_PARAMS)),
            axis("backlog", [1.0, 1.5, 2.0]),
            axis("mix", _mixes(33))], "sim_efficiency"),
        "asym_dense_2542": ([
            axis("protocol_param", perts),
            axis("protocol", ("lpddr6_asym", "hbm_asym")),
            axis("mix", _mixes(41))], "sim_efficiency"),
        "sweep_125": ([axis("mix", _mixes(25))], "sim_efficiency"),
        "pipelining_30": ([
            axis("k", [1, 2, 3, 4, 6]),
            axis("ucie_line_ui", [8.0, 16.0]),
            axis("device_line_ui", [16.0, 32.0, 64.0])], "utilization"),
    }


#: (engine family, program kind) of each flit_sim launch function
LAUNCHES = {
    "symmetric_chunk": ("flitsim.symmetric", "pallas-chunk"),
    "symmetric_periodic": ("flitsim.symmetric", "periodic"),
    "asymmetric_periodic": ("flitsim.asymmetric", "periodic"),
    "pipelining_chunk": ("flitsim.pipelining", "pallas-chunk"),
}


def mosaic_launches() -> dict:
    """launch -> whether every cached Pallas program of it holds a Mosaic
    kernel (``tpu_custom_call``); None where none was compiled."""
    from repro.core import space
    progs = space.programs(sorted({fam for fam, _ in LAUNCHES.values()}))
    out = {}
    for name, (fam, kind) in LAUNCHES.items():
        texts = [p.as_text() for k, p in progs.items()
                 if k[0] == fam and kind in k and k[-1] == "pallas"]
        out[name] = (all("tpu_custom_call" in t for t in texts)
                     if texts else None)
    return out


def phase_b() -> dict:
    from repro.core import ADAPTIVE_SIM, FIXED_SIM, PALLAS_SIM, DesignSpace
    sims = {"fixed": FIXED_SIM, "adaptive": ADAPTIVE_SIM,
            "pallas": PALLAS_SIM}
    grids, checks = {}, {}
    for gname, (axes, metric) in engine_grids().items():
        vals, wins, rows = {}, {}, {}
        for ename, sim in sims.items():
            space = DesignSpace(axes, sim=sim)

            def evaluate(space=space, metric=metric):
                return space.evaluate(metrics=(metric,))[metric]

            cold, warm, timing = run_twice(evaluate)
            vals[ename] = np.asarray(cold.values, np.float64)
            checks[f"{gname}/{ename}/warm_equals_cold"] = bool(
                np.array_equal(np.asarray(warm.values),
                               np.asarray(cold.values)))
            wins[ename] = (np.asarray(cold.argbest("protocol").values,
                                      dtype=object)
                           if "protocol" in cold.dims else None)
            rows[ename] = timing
        ref = vals["fixed"]
        for ename in ("adaptive", "pallas"):
            dev = float(np.max(np.abs(vals[ename] - ref)))
            rows[ename].update(
                max_abs_dev_vs_fixed=dev,
                bitwise_equal_cells=int(np.sum(vals[ename] == ref)),
                cells=int(ref.size))
            checks[f"{gname}/{ename}/within_{TOL:g}"] = dev <= TOL
            if wins["fixed"] is not None:
                checks[f"{gname}/{ename}/winners_identical"] = bool(
                    np.array_equal(wins[ename], wins["fixed"]))
        grids[gname] = rows
    mosaic = mosaic_launches()
    for name, ok in mosaic.items():
        checks[f"mosaic/{name}"] = ok is True
    # the CPU suite pins the symmetric periodic path BITWISE to the fixed
    # engine on the drained grid; the chip result is reported, the 1e-3
    # contract above is what is checked
    sym = grids["sym_drained_297"]
    pins = {e: sym[e]["bitwise_equal_cells"] == sym[e]["cells"]
            for e in ("adaptive", "pallas")}
    return {"phase": "B", "name": "engines_vs_fixed", "grids": grids,
            "mosaic_launches": mosaic,
            "sym_periodic_bitwise_pin_holds": pins, "checks": checks}


# -- C: a streamed space at user size -----------------------------------------


def stream(devices: int, prefetch: int = 2):
    from benchmarks.bench_streaming import joint_space
    from repro.core import StreamConfig
    space = joint_space(*STREAM_SHAPE, n_flits=2048, n_accesses=4096)
    return space.evaluate(
        metrics=("sim_bandwidth_gbs",),
        stream=StreamConfig(chunk_cells=CHUNK_CELLS, devices=devices,
                            prefetch=prefetch))


def same_result(a, b) -> bool:
    return (np.array_equal(np.asarray(a.winners.values, dtype=object),
                           np.asarray(b.winners.values, dtype=object))
            and a.win_counts == b.win_counts
            and a.best_by_label == b.best_by_label)


def stream_checks(sr) -> dict:
    return {"n_cells_ge_1e6": sr.n_cells >= 10 ** 6,
            "compiles_le_2": sr.compiles <= 2,
            "peak_cells_per_chunk_le_budget":
                sr.peak_cells_per_chunk <= CHUNK_CELLS * 4,
            "win_counts_sum_to_n_cells":
                sum(sr.win_counts.values()) == sr.n_cells}


def stream_summary(sr, seconds: float) -> dict:
    return {"n_cells": sr.n_cells, "dispatches": sr.n_dispatches,
            "compiles": sr.compiles,
            "peak_cells_per_chunk": sr.peak_cells_per_chunk,
            "devices": sr.devices, "cells_per_s": sr.n_cells / seconds,
            "win_counts": sr.win_counts}


def phase_c() -> dict:
    from benchmarks.bench_streaming import equality_row
    t0 = time.perf_counter()
    seq = stream(1, prefetch=1)
    t1 = time.perf_counter()
    sr = stream(1, prefetch=2)
    t2 = time.perf_counter()
    rows: list = []
    equality_row(rows)              # asserts streamed == materialized
    return {"phase": "C", "name": "stream_joint_1e6",
            "setup_s": t1 - t0, "warm_s": t2 - t1,
            "prefetch1": stream_summary(seq, t1 - t0),
            "prefetch2": stream_summary(sr, t2 - t1),
            "equality_goldens": rows[0][2],
            "checks": {**stream_checks(seq),
                       "prefetch_1_equals_2": same_result(seq, sr)}}


def shard_devices() -> dict:
    """How many devices hold one dispatch's input and output shards, read
    from the streaming program compiled last."""
    from repro.core import space
    prog = list(space.programs(["stream.sim"]).values())[-1]
    ins = jax.tree.leaves(prog.input_shardings)
    outs = jax.tree.leaves(prog.output_shardings)
    return {"input_devices": max(len(s.device_set) for s in ins),
            "output_devices": max(len(s.device_set) for s in outs),
            "input_shards_replicated": [s.is_fully_replicated for s in ins],
            "output_shards_replicated": [s.is_fully_replicated for s in outs]}


def phase_c_mesh(chips: int) -> dict:
    t0 = time.perf_counter()
    wide = stream(chips)
    t1 = time.perf_counter()
    placement = shard_devices()
    t2 = time.perf_counter()
    one = stream(1)
    t3 = time.perf_counter()
    return {"phase": "C", "name": f"stream_joint_1e6_{chips}dev_vs_1dev",
            f"devices_{chips}": stream_summary(wide, t1 - t0),
            "devices_1": stream_summary(one, t3 - t2),
            "placement": placement,
            "checks": {**stream_checks(wide),
                       "mesh_spans_all_devices":
                           placement["input_devices"] == chips,
                       f"devices_{chips}_equals_1": same_result(wide, one)}}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only phase C's space on four devices "
                         "against one")
    args = ap.parse_args()
    dev = device_label()
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform {dev['platform']!r})")
    if dev["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{dev['count']} device(s)")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache
    compile_cache.enable()

    phases = ([lambda: phase_c_mesh(args.chips)] if args.chips > 1
              else [phase_a, phase_b, phase_c])
    failed = []
    for phase in phases:
        line = phase()
        emit(line)
        failed += [f"{line['phase']}:{k}" for k, ok in line["checks"].items()
                   if not ok]
    if failed:
        sys.exit(f"chip_smoke: failed checks {failed}")
    print(json.dumps({"ok": True, "device": device_label()}))


if __name__ == "__main__":
    main()
