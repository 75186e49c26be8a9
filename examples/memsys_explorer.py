"""Memory-system explorer: the paper bridge end-to-end.

Four modes:

  * artifact mode (default) — takes compiled workload cells from the
    dry-run artifacts named on the command line, derives each one's xRyW
    traffic mix from the HLO byte counts, and reports what every
    UCIe-Memory approach would deliver for that workload — bandwidth,
    power, latency — vs today's HBM.

        PYTHONPATH=src python examples/memsys_explorer.py cell.json [...]

  * sweep mode — full design-space exploration over a dense 2-D
    (read-fraction x backlog) grid: the batched flit-simulation sweep
    engine evaluates every simulated protocol over hundreds of grid points
    in one compiled call per simulator family, and the batched selector
    ranks the whole catalog across the read-fraction axis in one more.

        PYTHONPATH=src python examples/memsys_explorer.py --sweep

  * bridge mode — the batched workload->design-space bridge: every
    workload's HLO-derived traffic mix (from the dry-run artifacts named
    after ``--bridge``, representative train/prefill/decode workloads
    when none are) is stacked as a workload_config axis on top of the
    dense mix grid and a shoreline axis (the axes-first DesignSpace API),
    and the whole
    [configs x catalog x mixes x shorelines] space resolves through ONE
    compiled catalog evaluation.  Each workload reports its frontier:
    best system, read-fraction crossovers, shoreline sensitivity.  The
    mode then runs the joint (mix x backlog x shoreline)
    analytic-vs-flit-simulated frontier and flags the regions where the
    cycle-level simulation disagrees with the closed forms about the best
    memory system, evaluates the PHY-stacked frontier (UCIe-A/S at 32G
    plus the forward-looking 48G points, via the first-class ``phy``
    axis) plus its cycle-level counterpart (``sim_phy_frontier``: the
    simulated efficiency threaded onto each PHY's raw link bandwidth, per
    queue depth), and writes the whole report to
    experiments/dryrun/design_space.json (the CI artifact — a checked-in
    summary of its winner labels gates CI against drift).  The
    flit-simulated parts run the convergence-adaptive engine
    (``ADAPTIVE_SIM``) — the chunked cores early-exit once every grid
    cell's estimate converges, deviating <= ~1e-3 from the fixed engine.

        PYTHONPATH=src python examples/memsys_explorer.py --bridge [cell ...]

  * serving mode — the serving-trace frontier: synthetic serving traces
    (per-model memory traffic under Poisson/diurnal/bursty arrival
    processes, no weights needed) evaluated through the design space's
    ``trace`` axis, with queue/credit state carried across phase
    boundaries inside the flit simulators.  Reports which memory
    approach wins at which (model, QPS) point plus the trace-scan
    telemetry.  Bridge mode embeds the same report as the
    ``serving_frontier`` section of design_space.json.

        PYTHONPATH=src python examples/memsys_explorer.py --serving
"""
import json
import os
import sys
import time

import numpy as np

from repro import compile_cache
from repro.core import TrafficMix, rank, SelectionConstraints

DRYRUN = os.path.join(os.path.dirname(__file__), "..", "experiments",
                      "dryrun")

def _cell_artifacts(paths):
    """Decoded per-cell artifacts among ``paths`` as (path, dict) pairs.

    The aggregate design-space report (and any axes-first export carrying
    phy / catalog_param dimensions) lives next to the per-cell artifacts
    but has a different schema — per-cell consumers must SKIP anything
    that is not a workload cell, not crash on missing keys.
    """
    from repro.roofline.analysis import is_cell_artifact
    out = []
    for f in paths:
        try:
            with open(f) as fh:
                d = json.load(fh)
        except (OSError, ValueError):
            continue
        if is_cell_artifact(d):
            out.append((f, d))
    return out


def explore(d: dict):
    r = d["roofline"]
    br = d["memsys_bridge"]
    print(f"cell: {d['arch']} × {d['shape']} × {d['mesh']} "
          f"({d['chips']} chips)")
    print(f"  traffic mix (from HLO bytes): {br['mix']} "
          f"(read fraction {br['read_fraction']:.2f})")
    print(f"  roofline: compute {r['compute_s']*1e3:.1f} ms | "
          f"memory {r['memory_s']*1e3:.1f} ms | "
          f"collective {r['collective_s']*1e3:.1f} ms  "
          f"-> {r['dominant']}-bound")
    print(f"\n  memory systems for this workload "
          f"(8 mm shoreline; HBM-baseline memory term "
          f"{br['hbm_baseline_memory_s']*1e3:.1f} ms):")
    rows = sorted(br["systems"].items(),
                  key=lambda kv: kv[1]["memory_term_s"])
    for key, s in rows:
        print(f"    {key:32s} {s['bandwidth_gbs']:8.0f} GB/s  "
              f"{s['pj_per_bit']:.3f} pJ/b  {s['latency_ns']:4.1f} ns  "
              f"memory term {s['memory_term_s']*1e3:8.2f} ms  "
              f"{s['interconnect_energy_j_per_step']:.2f} J/step")


def sweep_mode(n_fracs: int = 41, backlogs=(1, 2, 4, 8, 16, 32, 64, 128)):
    """Dense design-space sweep: read-fraction x backlog x protocol.

    Runs the convergence-adaptive engine (``ADAPTIVE_SIM``): the chunked
    cores early-exit as soon as the slowest grid cell converges, with the
    few non-converging straggler cells re-simulated exactly.
    """
    from repro.core import ADAPTIVE_SIM, flitsim, mix_grid
    from repro.core.space import DesignSpace, axis

    x, y = mix_grid(n_fracs)
    fracs = np.asarray(x) / 100.0

    t0 = time.perf_counter()
    res = DesignSpace([
        axis("backlog", list(backlogs)),
        axis("read_fraction", fracs),
    ], sim=ADAPTIVE_SIM).evaluate(metrics=("sim_efficiency",))
    sa = res["sim_efficiency"]
    protocols = list(sa.coord("protocol"))
    eff = np.asarray(sa.values)                   # [P, B, M]
    t_sim = time.perf_counter() - t0
    n_pts = eff.size
    stats = flitsim.compile_cache_stats()
    print(f"flit-simulated {n_pts} grid points "
          f"({len(protocols)} protocols x {len(backlogs)} backlogs x "
          f"{n_fracs} read fractions) in {t_sim:.2f}s "
          f"[{stats.misses} compiles, {stats.hits} cache hits]")
    for fam, info in sorted(flitsim.last_run_info().items()):
        if info.get("mode") != "adaptive":
            continue
        print(f"    {fam.split('.')[1]:10s} adaptive: "
              f"{info['cycles_run']}/{info['horizon']} cycles "
              f"({info['stragglers']} stragglers re-simulated exactly)")

    bl_ref = list(backlogs).index(64) if 64 in backlogs else len(backlogs) - 1
    print(f"\nsimulated data efficiency at backlog={backlogs[bl_ref]} "
          f"(read fraction 0 / 0.5 / 1):")
    mid = n_fracs // 2
    for i, key in enumerate(protocols):
        e = eff[i, bl_ref]
        sens = float(np.max(eff[i, :, mid]) - np.min(eff[i, :, mid]))
        print(f"    {key:12s} {e[0]:.3f} / {e[mid]:.3f} / {e[-1]:.3f}   "
              f"backlog sensitivity @50/50: {sens:.3f}")

    print("\nbest simulated protocol per read-fraction regime "
          f"(backlog={backlogs[bl_ref]}):")
    best = np.argmax(eff[:, bl_ref, :], axis=0)
    start = 0
    for j in range(1, n_fracs + 1):
        if j == n_fracs or best[j] != best[start]:
            key = protocols[best[start]]
            print(f"    read fraction {fracs[start]:.2f}-"
                  f"{fracs[j - 1]:.2f}: {key}")
            start = j

    # catalog ranking over the same read-fraction axis, one compiled call
    t0 = time.perf_counter()
    cres = DesignSpace([axis("read_fraction", fracs)]).evaluate(
        metrics=("bandwidth_gbs",))
    keys = cres.frontier("bandwidth_gbs").values
    n_sys = len(cres["bandwidth_gbs"].coord("system"))
    t_rank = time.perf_counter() - t0
    print(f"\ncatalog ranking over {n_fracs} read fractions "
          f"({n_sys} systems) in {t_rank*1e3:.1f} ms:")
    start = 0
    for j in range(1, n_fracs + 1):
        if j == n_fracs or keys[j] != keys[start]:
            print(f"    read fraction {fracs[start]:.2f}-"
                  f"{fracs[j - 1]:.2f}: {keys[start]}")
            start = j


#: Fallback workloads (per-chip bytes) when no dry-run artifacts exist:
#: training reads weights+activations and writes gradients; prefill is
#: read-heavy; decode is nearly pure weight streaming.
REPRESENTATIVE_WORKLOADS = {
    "train_67R33W": (6.7e9, 3.3e9, 1.0e10),
    "prefill_85R15W": (1.27e10, 2.3e9, 1.5e10),
    "decode_95R5W": (1.9e10, 1.0e9, 2.0e10),
}


def phy_frontier_report(n_fracs: int = 21, shorelines=(4.0, 8.0, 16.0)):
    """First-class ``phy`` axis: the catalog across UCIe-A/UCIe-S at 32G
    plus the forward-looking 48G (UCIe 2.0 scaling) points, in ONE
    PHY-stacked evaluation.  Thin wrapper over the unified report API
    (:func:`repro.core.report.build_report`, section ``"phy"``); returns
    the JSON-able report section for the CI design-space artifact."""
    from repro.core.report import ReportSpec, build_report

    spec = ReportSpec(sections=("phy",), verbose=True,
                      options={"phy": {"n_fracs": n_fracs,
                                       "shorelines": shorelines}})
    return build_report(spec)["phy"].payload


def sim_phy_frontier_report(n_fracs: int = 21, backlogs=(2.0, 64.0)):
    """Simulation-corrected PHY-absolute frontier: the flit simulators'
    data efficiency threaded onto each PHY generation's raw link bandwidth
    (``sim_bandwidth_gbs`` = sim efficiency x ``UCIePhy.raw_bandwidth_gbs``)
    — the cycle-level counterpart of the analytic ``phy_frontier``.  Thin
    wrapper over the unified report API (section ``"sim_phy"``); runs the
    convergence-adaptive engine and returns the JSON-able report section
    for the CI design-space artifact."""
    from repro.core.report import ReportSpec, build_report

    spec = ReportSpec(sections=("sim_phy",), verbose=True,
                      options={"sim_phy": {"n_fracs": n_fracs,
                                           "backlogs": backlogs}})
    return build_report(spec)["sim_phy"].payload


def serving_frontier_report(models=None, qps_points=None, **kwargs):
    """Serving-trace frontier: which memory approach wins at which
    (model, QPS) point.  Synthetic serving traces (config shapes only, no
    weights) are evaluated through the design space's ``trace`` axis —
    queue/credit state carried across phase boundaries — and each
    (model, QPS) cell's winning protocol on the UCIe-A PHY is mapped to
    its catalog memory approach.  Prints the frontier plus the trace-scan
    telemetry; returns the JSON-able ``serving_frontier`` artifact
    section (sourced through the unified report API, section
    ``"serving"``)."""
    from repro.core.report import ReportSpec, build_report

    t0 = time.perf_counter()
    opts = dict(kwargs, models=models, qps_points=qps_points)
    spec = ReportSpec(sections=("serving",), options={"serving": opts})
    rep = build_report(spec)["serving"].payload
    dt = time.perf_counter() - t0
    n_cells = len(rep["models"]) * len(rep["qps_points"])
    print(f"serving frontier: {len(rep['models'])} models x "
          f"{len(rep['qps_points'])} QPS points x "
          f"{len(rep['protocols'])} protocols ({rep['n_phases']} phases "
          f"per trace, {rep['arrival']} arrivals) in {dt:.2f}s "
          f"[{rep['compiles']} compiles on {rep['phy']}]")
    for fam, tele in sorted(rep["telemetry"].items()):
        print(f"    {fam.split('.')[1]:10s} trace-scan: "
              f"{tele['phases']} phases x {tele['cycles_per_phase']} "
              f"cycles ({tele['trace_cells']} cells, state carried "
              f"across {tele['state_carry_depth']} cycles)")
    for m in rep["models"]:
        wins = rep["winner_by_model_qps"][m]
        gbs = rep["winner_gbs_by_model_qps"][m]
        pts = "  ".join(
            f"qps={q}: {wins[q]} ({gbs[q]:.0f} GB/s)" for q in wins)
        tag = "QPS-SENSITIVE" if rep["qps_sensitive"][m] else \
            "qps-insensitive"
        print(f"    {m:14s} {pts}  [{tag}]")
    if n_cells and not any(rep["qps_sensitive"].values()):
        print("    (one approach serves every load point on this PHY)")
    return rep


def serving_mode():
    """``--serving``: print the serving-trace frontier standalone."""
    rep = serving_frontier_report()
    traces = rep["traces"]
    print(f"\n{len(traces)} synthetic traces "
          f"({rep['n_ticks']} engine ticks each):")
    for name in rep["trace_names"]:
        t = traces[name]
        rf = "/".join(f"{r:.2f}" for r in t["read_fractions"])
        bl = "/".join(f"{b:.0f}" for b in t["backlogs"])
        print(f"    {name:22s} read fraction {rf}  backlog {bl}")


def representative_reports():
    """:data:`REPRESENTATIVE_WORKLOADS` as roofline reports."""
    from repro.roofline.analysis import RooflineReport
    return {name: RooflineReport(
        arch=name, shape="-", mesh="-", chips=256,
        hlo_flops_per_chip=0.0, hlo_bytes_per_chip=hb,
        collective_bytes_per_chip=0.0, compute_s=0.0,
        memory_s=hb / 8.192e11, collective_s=0.0,
        dominant="memory", model_flops=0.0, useful_flops_ratio=0.0,
        read_bytes_per_chip=r, write_bytes_per_chip=w)
        for name, (r, w, hb) in REPRESENTATIVE_WORKLOADS.items()}


def bridge_report(reports, n_fracs: int = 41,
                  shorelines=(2.0, 4.0, 8.0, 16.0)):
    """The design_space.json report for ``reports``: the batched
    workload->design-space bridge plus the joint, PHY, simulated-PHY and
    serving frontiers.  Prints its progress."""
    from repro.core.memsys import grid_cache_stats
    from repro.roofline.analysis import bridge_design_space

    t0 = time.perf_counter()
    ds = bridge_design_space(reports, n_fracs=n_fracs,
                             shorelines=shorelines)
    dt = time.perf_counter() - t0
    stats = grid_cache_stats()
    n_pts = (len(reports) * len(ds["keys"]) * (n_fracs + 1)
             * len(shorelines))
    print(f"design space: {len(reports)} workloads x {len(ds['keys'])} "
          f"systems x {n_fracs + 1} mixes x {len(shorelines)} shorelines "
          f"= {n_pts} points in {dt:.2f}s "
          f"[{stats.misses} compiles, {stats.hits} cache hits]\n")
    for name, w in ds["workloads"].items():
        hbm_t = w["hbm_baseline_memory_s"]
        best_t = w["systems"][w["best"]]["memory_term_s"]
        print(f"{name}  ({w['mix']}, read fraction "
              f"{w['read_fraction']:.2f})")
        print(f"    best @ {ds['reference_shoreline_mm']:g} mm: "
              f"{w['best']}  memory term {best_t*1e3:.2f} ms "
              f"(HBM baseline {hbm_t*1e3:.2f} ms, "
              f"x{hbm_t / best_t:.2f})")
        regimes = ", ".join(
            f"{c['read_fraction_lo']:.2f}-{c['read_fraction_hi']:.2f}:"
            f"{c['best']}" for c in w["crossovers"])
        print(f"    read-fraction frontier: {regimes}")
        if w["shoreline_sensitive"]:
            print(f"    shoreline-SENSITIVE: {w['shoreline_frontier']}")
        else:
            budgets = ", ".join(f"{s:g}" for s in ds["shorelines"])
            print(f"    shoreline-insensitive ({budgets} mm)")
        print()

    # joint (mix x backlog x shoreline) analytic-vs-simulated frontier:
    # where do the closed forms and the cycle-level simulation DISAGREE
    # about the best memory system?  Runs the convergence-adaptive engine
    # (canonical artifact grid; winner labels are gate-checked against
    # the fixed-mode golden summary).
    from repro.core import ADAPTIVE_SIM
    from repro.core.report import ReportSpec, build_report
    jf = build_report(ReportSpec(sections=("joint",), sim=ADAPTIVE_SIM,
                                 verbose=True))["joint"].payload
    errs = ", ".join(f"{k}={v:.1%}"
                     for k, v in jf["protocol_rel_err"].items())
    print(f"    worst simulated-vs-analytic efficiency error: {errs}")
    if jf["disagreement_regions"]:
        print("    disagreement regions (simulation overrules the closed "
              "forms):")
        for r in jf["disagreement_regions"][:8]:
            print(f"      backlog={r['backlog']:g} "
                  f"shoreline={r['shoreline_mm']:g}mm read fraction "
                  f"{r['read_fraction_lo']:.2f}-{r['read_fraction_hi']:.2f}"
                  f": analytic {r['analytic_best']} -> simulated "
                  f"{r['simulated_best']}")
        extra = len(jf["disagreement_regions"]) - 8
        if extra > 0:
            print(f"      ... and {extra} more regions")
    else:
        print("    no disagreement: the closed forms pick the simulated "
              "winner everywhere")

    # PHY as a first-class axis: UCIe-A/S at 32G + the 48G (UCIe 2.0
    # scaling) points, one PHY-stacked compiled evaluation
    print()
    pf = phy_frontier_report()

    # ...and its cycle-level counterpart: the flit-simulated efficiency
    # threaded onto each PHY's raw bandwidth (sim_bandwidth_gbs), per
    # queue depth
    print()
    spf = sim_phy_frontier_report()

    # ...and the serving-trace frontier: time-varying traffic from the
    # LLM serving workloads, winners per (model, QPS) point
    print()
    sf = serving_frontier_report()

    ds["joint_frontier"] = jf
    ds["phy_frontier"] = pf
    ds["sim_phy_frontier"] = spf
    ds["serving_frontier"] = sf
    return ds


def bridge_mode(paths=()):
    """Batched workload->design-space bridge over the dry-run artifacts
    in ``paths`` (the representative workloads when there are none);
    writes design_space.json."""
    from repro.roofline.analysis import DESIGN_SPACE_JSON, RooflineReport

    reports = {f"{d['arch']}__{d['shape']}__{d['mesh']}":
               RooflineReport(**d["roofline"])
               for _, d in _cell_artifacts(paths)}
    if reports:
        print(f"{len(reports)} workload cells from dry-run artifacts")
    else:
        print("no dry-run artifacts given; using representative workloads")
        reports = representative_reports()
    ds = bridge_report(reports)
    os.makedirs(DRYRUN, exist_ok=True)
    out_path = os.path.join(DRYRUN, DESIGN_SPACE_JSON)
    with open(out_path, "w") as f:
        json.dump(ds, f, indent=1)
    print(f"\nwrote {os.path.relpath(out_path)}")


def main():
    args = [a for a in sys.argv[1:]]
    compile_cache.enable()
    if "--sweep" in args:
        sweep_mode()
        return
    if "--bridge" in args:
        bridge_mode([a for a in args if a != "--bridge"])
        return
    if "--serving" in args:
        serving_mode()
        return
    cells = [d for _, d in _cell_artifacts(args)]
    if not cells:
        print("no dry-run artifacts given; pass cell JSON files written by "
              "`PYTHONPATH=src python -m repro.launch.dryrun --all` "
              "(or try `--sweep` for the design-space sweep, which needs "
              "no artifacts)")
        return
    for d in cells:
        explore(d)
        print()


if __name__ == "__main__":
    main()
