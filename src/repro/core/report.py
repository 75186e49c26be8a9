"""ONE frontier/report API over every engine and execution mode.

Five frontier builders accreted across PRs 3-8: ``SpaceResult.frontier``,
``joint_frontier``, the explorer's ``phy_frontier_report`` /
``sim_phy_frontier_report``, and ``DesignSpace.serving_frontier``.  They
now converge here: :func:`build_report` (the engine behind
:meth:`repro.core.space.DesignSpace.report`) resolves a
:class:`ReportSpec` into typed :class:`FrontierReport` sections whose
payloads are byte-identical to the legacy ``design_space.json`` sections
— the explorer functions are thin wrappers over this module, and the
summary golden pins the winner labels of every section.

Sections:

* ``"frontier"`` — the calling space's own winner map
  (``argbest``-reduced, optionally constraint-masked, optionally through
  the STREAMING engine via a ``stream=StreamConfig`` option — the path
  that scales one section to 10^6–10^8 cells).
* ``"joint"`` — :func:`repro.core.space.joint_frontier`: the
  (mix x backlog x shoreline) analytic-vs-simulated disagreement map,
  which since the streaming PR also carries the folded
  ``sim_bandwidth_gbs`` PHY-absolute subsection.
* ``"phy"`` — the PHY-stacked analytic frontier (UCIe-A/S, 32G + 48G).
* ``"sim_phy"`` — its cycle-level counterpart (simulated efficiency x
  raw PHY bandwidth, per queue depth).
* ``"serving"`` — the per-(model, QPS) serving-trace winner map, or
  one :class:`~repro.traces.deployment.ServingDeployment`'s (option
  ``deployment``).
* ``"workloads"`` — the workload->design-space bridge
  (:func:`repro.roofline.analysis.bridge_design_space`) over the
  roofline reports of option ``reports``.

Every section accepts keyword options via ``ReportSpec.options`` (keyed
by section name); ``verbose=True`` reproduces the explorer's progress
prints byte-for-byte (the explorer wrappers pass it).  Each section runs
inside a ``repro.report.section`` span (family: the section), and its
seconds and engine runs (sequential cycles, cells, probe-certified
cells) are the ``"report"`` counters of
:func:`repro.core.flitsim.last_run_info`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Mapping, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["FrontierReport", "ReportSpec", "build_report"]

#: sections that need no DesignSpace instance (they build their own)
STANDALONE_SECTIONS: Tuple[str, ...] = ("joint", "phy", "sim_phy",
                                        "serving")


@dataclasses.dataclass(frozen=True)
class ReportSpec:
    """What to report: which sections, under which execution config.

    ``options`` maps section name -> keyword options for that section's
    builder (e.g. ``{"phy": {"n_fracs": 41}}``; the ``"frontier"``
    section accepts ``metric`` / ``dim`` / ``mode`` / ``constraints`` /
    ``stream``).  ``sim`` is the default :class:`~repro.core.space.
    SimConfig` for simulated sections (a per-section ``sim`` option
    wins).  ``verbose`` reproduces the explorer's progress prints.
    """

    sections: Tuple[str, ...] = STANDALONE_SECTIONS
    sim: Optional[Any] = None
    options: Mapping[str, Mapping[str, Any]] = dataclasses.field(
        default_factory=dict)
    verbose: bool = False

    def __post_init__(self):
        object.__setattr__(self, "sections",
                           tuple(str(s) for s in self.sections))


@dataclasses.dataclass(frozen=True)
class FrontierReport:
    """One typed report section: the JSON-able payload (byte-identical
    to the legacy ``design_space.json`` section of the same name) plus
    its identity."""

    section: str
    payload: Dict[str, Any]

    def __getitem__(self, key: str) -> Any:
        return self.payload[key]

    def __contains__(self, key: str) -> bool:
        return key in self.payload


def build_report(spec: Optional[ReportSpec] = None, *,
                 space=None) -> Dict[str, FrontierReport]:
    """Resolve ``spec`` into ``{section: FrontierReport}``.

    ``space`` is the :class:`~repro.core.space.DesignSpace` the
    ``"frontier"`` section reduces (required for that section only;
    :meth:`DesignSpace.report` passes itself).
    """
    from repro.core import flitsim
    spec = spec if spec is not None else ReportSpec()
    builders = {"frontier": _frontier_section, "joint": _joint_section,
                "phy": _phy_section, "sim_phy": _sim_phy_section,
                "serving": _serving_section,
                "workloads": _workloads_section}
    unknown = [s for s in spec.sections if s not in builders]
    if unknown:
        raise ValueError(f"unknown report sections {unknown}; choose "
                         f"from {sorted(builders)}")
    out: Dict[str, FrontierReport] = {}
    seconds: Dict[str, float] = {}
    engines: Dict[str, Dict[str, int]] = {}
    for section in spec.sections:
        if section == "frontier" and space is None:
            raise ValueError(
                "the 'frontier' section reduces a DesignSpace instance; "
                "call space.report(spec) (or pass build_report(spec, "
                "space=...)) instead of the standalone form")
        opts = dict(spec.options.get(section, {}))
        if section in ("joint", "sim_phy", "frontier") \
                and spec.sim is not None:
            opts.setdefault("sim", spec.sim)
        mark = flitsim.run_mark()
        t0 = time.perf_counter()
        with TraceAnnotation("repro.report.section", family=section):
            payload = builders[section](space, spec.verbose, **opts)
        seconds[section] = time.perf_counter() - t0
        engines[section] = _engine_counts(flitsim.runs_since(mark))
        out[section] = FrontierReport(section=section, payload=payload)
    flitsim.record_counters("report", seconds=seconds, engines=engines)
    return out


def _engine_counts(runs: Mapping[str, Mapping[str, Any]]) -> Dict[str, int]:
    """What a section's engine runs did (each section evaluates one space,
    which runs each engine family once): sequential cycles (an adaptive
    run's ``sequential_depth``, a trace scan's ``cycles_run``), the
    adaptive runs' cells and those of them the periodic probes
    certified."""
    depth = cells = certified = 0
    for rec in runs.values():
        if rec.get("mode") == "adaptive":
            depth += rec["sequential_depth"]
            cells += rec["cells"]
            certified += sum(rec.get("periods", {}).values())
        elif rec.get("mode") == "trace":
            depth += rec["cycles_run"]
    return {"sequential_depth": depth, "cells": cells,
            "certified_cells": certified}


# =========================================================================
# sections
# =========================================================================


def _frontier_section(space, verbose, *, metric: str = "bandwidth_gbs",
                      dim: str = "system", mode: str = "max",
                      constraints=None, sim=None, stream=None
                      ) -> Dict[str, Any]:
    """The calling space's own winner map — materialized
    (``SpaceResult.frontier``) or streamed (``StreamConfig``), one
    payload schema for both."""
    if stream is not None:
        res = space.evaluate(metrics=(metric,), sim=sim, stream=stream)
        winners = res.winners
        extra = {"engine": "streaming", "win_counts": res.win_counts,
                 "n_cells": res.n_cells,
                 "peak_cells_per_chunk": res.peak_cells_per_chunk,
                 "devices": res.devices, "compiles": res.compiles}
        mode = res.mode
    else:
        metrics = [metric]
        if constraints is not None:
            # point-dependent constraints read these arrays
            if constraints.max_power_w is not None:
                metrics.append("power_w")
            if constraints.required_bandwidth_gbs is not None:
                metrics.append("bandwidth_gbs")
        res = space.evaluate(metrics=tuple(dict.fromkeys(metrics)),
                             sim=sim)
        where = res.feasible(constraints) if constraints is not None \
            else None
        winners = res.frontier(metric, dim, mode, where=where)
        extra = {"engine": "materialized"}
    payload = {"metric": metric, "dim": dim, "mode": mode,
               "dims": list(winners.dims),
               "coords": [[str(c) for c in coord]
                          for coord in winners.coords],
               "winners": np.asarray(winners.values, dtype=object)
               .tolist(), **extra}
    if verbose:
        print(f"frontier: {metric} argbest({dim!r}, {mode!r}) over dims "
              f"{payload['dims']} [{extra['engine']}]")
    return payload


def _joint_section(space, verbose, **opts) -> Dict[str, Any]:
    from repro.core.space import joint_frontier
    t0 = time.perf_counter()
    jf = joint_frontier(**opts)
    dt = time.perf_counter() - t0
    if verbose:
        n_jf = (len(jf["read_fractions"]) * len(jf["backlogs"])
                * len(jf["shorelines"]))
        print(f"analytic-vs-simulated frontier: {n_jf} joint "
              f"(mix x backlog x shoreline) points in {dt:.2f}s; winners "
              f"disagree on {jf['disagreement_fraction']:.0%} of the "
              f"space")
    return jf


def _phy_section(space, verbose, *, n_fracs: int = 21,
                 shorelines=(4.0, 8.0, 16.0)) -> Dict[str, Any]:
    """First-class ``phy`` axis: the catalog across UCIe-A/UCIe-S at 32G
    plus the forward-looking 48G (UCIe 2.0 scaling) points, in ONE
    PHY-stacked evaluation."""
    from repro.core import (
        UCIE_A_32G_55U, UCIE_A_48G_45U, UCIE_S_32G, UCIE_S_48G_110U,
    )
    from repro.core.memsys import grid_cache_stats
    from repro.core.space import DesignSpace, axis, regimes

    phys = [UCIE_S_32G, UCIE_A_32G_55U, UCIE_S_48G_110U, UCIE_A_48G_45U]
    fracs = np.linspace(0.0, 1.0, n_fracs)
    before = grid_cache_stats()
    t0 = time.perf_counter()
    res = DesignSpace([
        axis("phy", phys),
        axis("read_fraction", fracs),
        axis("shoreline_mm", shorelines),
    ]).evaluate(metrics=("bandwidth_gbs", "gbs_per_watt"))
    dt = time.perf_counter() - t0
    after = grid_cache_stats()
    bw = res["bandwidth_gbs"]          # [S, F, M, L]
    if verbose:
        n_pts = int(np.prod(bw.shape))
        print(f"phy axis: {len(phys)} PHYs x {len(bw.coord('system'))} "
              f"approaches x {n_fracs} mixes x {len(shorelines)} "
              f"shorelines = {n_pts} points in {dt:.2f}s "
              f"[{after.misses - before.misses} compiles]")
    report = {"phys": [p.name for p in phys],
              "read_fractions": fracs.tolist(),
              "shorelines": [float(s) for s in shorelines],
              "best_approach_by_phy": {}, "regimes_by_phy": {}}
    for p in phys:
        front = res.frontier("bandwidth_gbs").sel(phy=p.name,
                                                  shoreline_mm=8.0)
        regs = regimes(front.values.tolist(), fracs)
        report["regimes_by_phy"][p.name] = [
            {"read_fraction_lo": lo, "read_fraction_hi": hi,
             "best": str(lab)} for lo, hi, lab in regs]
        at70 = front.values[int(round(0.7 * (n_fracs - 1)))]
        report["best_approach_by_phy"][p.name] = str(at70)
        if verbose:
            peak = float(bw.sel(phy=p.name,
                                shoreline_mm=8.0).values.max())
            print(f"    {p.name:18s} best@70R30W {at70:24s} "
                  f"peak {peak:6.0f} GB/s @ 8 mm")
    # §V scaling check surfaced in the artifact: at the SAME bump pitch
    # (both UCIe-S points are 110um) 48G carries exactly 48/32 = 1.5x the
    # bandwidth at identical pJ/b.  (The advanced 48G point above stacks
    # a further 55/45 pitch gain on top, hence its larger peak.)
    g32 = float(bw.sel(phy=UCIE_S_32G.name).values.max())
    g48 = float(bw.sel(phy=UCIE_S_48G_110U.name).values.max())
    report["bw_gain_48g_vs_32g_same_pitch"] = g48 / g32
    if verbose:
        print(f"    48G vs 32G same-pitch bandwidth gain: "
              f"x{g48 / g32:.2f} at constant pJ/b")
    return report


def _sim_phy_section(space, verbose, *, n_fracs: int = 21,
                     backlogs=(2.0, 64.0), sim=None) -> Dict[str, Any]:
    """Simulation-corrected PHY-absolute frontier: the flit simulators'
    data efficiency threaded onto each PHY generation's raw link
    bandwidth — the cycle-level counterpart of the ``phy`` section, and
    the first one that can disagree with it per queue depth."""
    from repro.core import (
        ADAPTIVE_SIM, UCIE_A_32G_55U, UCIE_A_48G_45U, UCIE_S_32G,
        UCIE_S_48G_110U, flitsim,
    )
    from repro.core.selector import approach_key_for
    from repro.core.space import DesignSpace, axis, regimes

    sim = sim if sim is not None else ADAPTIVE_SIM
    phys = [UCIE_S_32G, UCIE_A_32G_55U, UCIE_S_48G_110U, UCIE_A_48G_45U]
    fracs = np.linspace(0.0, 1.0, n_fracs)
    before = flitsim.compile_cache_stats()
    t0 = time.perf_counter()
    res = DesignSpace([
        axis("phy", phys),
        axis("read_fraction", fracs),
        axis("backlog", backlogs),
    ], sim=sim).evaluate(
        metrics=("sim_efficiency", "sim_bandwidth_gbs"))
    dt = time.perf_counter() - t0
    after = flitsim.compile_cache_stats()
    bw = res["sim_bandwidth_gbs"]      # [protocol, phy, backlog, mix]
    info = flitsim.last_run_info()
    cycles = {fam.split(".")[1]: info[fam]["cycles_run"] for fam in info
              if info[fam].get("mode") == "adaptive"}
    if verbose:
        print(f"sim-phy frontier: {len(bw.coord('protocol'))} protocols "
              f"x {len(phys)} PHYs x {len(backlogs)} backlogs x "
              f"{n_fracs} read fractions = {int(np.prod(bw.shape))} "
              f"points in {dt:.2f}s "
              f"[{after.misses - before.misses} compiles; adaptive "
              f"cycles {cycles}]")
    report = {"phys": [p.name for p in phys],
              "backlogs": [float(b) for b in backlogs],
              "read_fractions": fracs.tolist(),
              "adaptive_cycles": cycles,
              "peak_sim_gbs_by_phy": {},
              "best_protocol_by_phy": {},
              "regimes_by_phy_backlog": {}}
    for p in phys:
        regs_by_bl = {}
        for b in backlogs:
            front = bw.sel(phy=p.name, backlog=b).argbest("protocol")
            regs_by_bl[f"{b:g}"] = [
                {"read_fraction_lo": lo, "read_fraction_hi": hi,
                 "best": str(lab),
                 "approach": approach_key_for(str(lab))}
                for lo, hi, lab in regimes(front.values.tolist(), fracs)]
        report["regimes_by_phy_backlog"][p.name] = regs_by_bl
        deep = bw.sel(phy=p.name, backlog=backlogs[-1])
        at70 = deep.argbest("protocol").values[
            int(round(0.7 * (n_fracs - 1)))]
        report["best_protocol_by_phy"][p.name] = str(at70)
        peak = float(deep.values.max())
        report["peak_sim_gbs_by_phy"][p.name] = peak
        if verbose:
            print(f"    {p.name:18s} best@70R30W {str(at70):12s} "
                  f"peak {peak:5.0f} GB/s (raw link, simulated)")
    # the shallow-queue disagreement the closed forms cannot see: winners
    # at backlog 2 vs saturation
    shallow = {p.name: [r["best"]
                        for r in report["regimes_by_phy_backlog"][p.name]
                        [f"{backlogs[0]:g}"]] for p in phys}
    deep_w = {p.name: [r["best"]
                       for r in report["regimes_by_phy_backlog"][p.name]
                       [f"{backlogs[-1]:g}"]] for p in phys}
    report["shallow_queue_disagrees"] = {
        name: shallow[name] != deep_w[name] for name in shallow}
    return report


def _workloads_section(space, verbose, *, reports, **opts
                       ) -> Dict[str, Any]:
    """The workload->design-space bridge over ``reports`` (name ->
    :class:`repro.roofline.analysis.RooflineReport`); ``opts`` as for
    :func:`repro.roofline.analysis.bridge_design_space`."""
    from repro.roofline.analysis import bridge_design_space
    return bridge_design_space(reports, **opts)


def _serving_section(space, verbose, *, models=None, qps_points=None,
                     **kwargs) -> Dict[str, Any]:
    from repro.core.space import DesignSpace
    rep = DesignSpace.serving_frontier(models, qps_points, **kwargs)
    if verbose:
        print(f"serving frontier: {len(rep['models'])} models x "
              f"{len(rep['qps_points'])} QPS points x "
              f"{len(rep['protocols'])} protocols on {rep['phy']}")
    return rep
