"""Query runner for the explorer's whole report with a serving deployment:
one ``build_report`` call (what ``DesignSpace.report`` runs) of the
sections

* ``workloads`` — the workload->design-space bridge over the explorer's
  three representative workloads;
* ``joint``, ``phy``, ``sim_phy`` — at the ``--bridge`` explorer's
  defaults, the simulated ones under ``ADAPTIVE_SIM``;
* ``serving`` — the configuration's deployment (DeepSeek-V3 on one chip
  of an expert-parallel deployment) replayed under every arrival process
  of the traffic file at each multiple of its service rate, through the
  trace-scan cores.

A run draws ``pool`` trace seeds from ``--seed``, warms a query of each,
and cycles them.  The check compares the serving section of queries 0, 1
and one drawn among 2-7 with :mod:`reference_serving`, the joint and
sim_phy sections' peak bandwidths with :mod:`reference`, and the winner
labels of the other sections with the committed golden summary.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

import harness
import reference
import reference_serving as ref

_study = harness.load_module("runners", "protocol_study")
GOLDEN_SECTIONS = ("workloads", "joint_frontier", "phy_frontier",
                   "sim_phy_frontier")
#: the report sections a query builds, and the name of each in the
#: explorer's design_space.json
SECTIONS = {"joint": "joint_frontier", "phy": "phy_frontier",
            "sim_phy": "sim_phy_frontier", "serving": "serving_frontier"}
TRACE_PROGRAMS = {"flitsim.symmetric": "jit_flitsim_symmetric_trace",
                  "flitsim.asymmetric": "jit_flitsim_asymmetric_trace"}


def _summary_tool():
    path = os.path.join(harness.ROOT, "tools", "design_space_summary.py")
    spec = importlib.util.spec_from_file_location("design_space_summary",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: the CI's drift-stable summary of a design_space.json (winner labels
#: only), from ``tools/design_space_summary.py``
summarize = _summary_tool().summarize


def leaves(d: Any, path: Tuple = ()) -> Dict[Tuple, Any]:
    if isinstance(d, dict):
        out: Dict[Tuple, Any] = {}
        for k, v in d.items():
            out.update(leaves(v, path + (k,)))
        return out
    return {path: json.dumps(d, sort_keys=True)}


def mismatches(got: Dict[str, Any], want: Dict[str, Any]) -> int:
    """Leaves of the golden sections that differ or are missing."""
    a = leaves({k: got.get(k) for k in GOLDEN_SECTIONS})
    b = leaves({k: want.get(k) for k in GOLDEN_SECTIONS})
    return sum(a.get(p) != b.get(p) for p in set(a) | set(b))


class Runner:
    RATE_METRIC = "grid_cells_per_s"
    P95_METRIC = None

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, chips: int):
        from repro.traces.deployment import ServingDeployment
        self.config, self.traffic = config, traffic
        self.deployment = ServingDeployment.from_dict(dict(
            config["deployment"], model=config["model"],
            **traffic["sessions"]))
        rng = np.random.default_rng(seed)
        self.pool = [int(s) for s in rng.integers(0, 2 ** 31,
                                                  int(traffic["pool"]))]
        #: the queries of the window whose answers are checked: the first
        #: two and one drawn from the seed among the next six
        self.checked = {0, 1, int(rng.integers(2, 8))}
        self.protocols = tuple(config["protocols"])
        n_traces = len(traffic["arrivals"]) * len(traffic["qps_multiples"])
        rep = config["report"]
        joint = rep["joint"]["n_fracs"] * len(config["backlogs"]["joint"])
        sim_phy = (rep["sim_phy"]["n_fracs"]
                   * len(config["backlogs"]["sim_phy"]))
        #: simulated cells: protocol x (trace x phase + the joint and
        #: sim_phy grids' points)
        self.cells_per_query = len(self.protocols) * (
            n_traces * int(traffic["n_phases"]) + joint + sim_phy)
        with open(os.path.join(harness.ROOT, config["golden"])) as f:
            self.golden = json.load(f)

    # -- the timed query --------------------------------------------------

    def reports(self):
        from repro.roofline.analysis import RooflineReport
        w = self.config["workloads"]
        return {name: RooflineReport(
            arch=name, shape="-", mesh="-", chips=int(w["chips"]),
            hlo_flops_per_chip=0.0, hlo_bytes_per_chip=hb,
            collective_bytes_per_chip=0.0, compute_s=0.0,
            memory_s=hb / w["hbm_bytes_per_s"], collective_s=0.0,
            dominant="memory", model_flops=0.0, useful_flops_ratio=0.0,
            read_bytes_per_chip=r, write_bytes_per_chip=wb)
            for name, (r, wb, hb) in w["bytes"].items()}

    def report_spec(self, trace_seed: int):
        from repro.core import ADAPTIVE_SIM, ucie
        from repro.core.report import ReportSpec
        cfg, tr, rep = self.config, self.traffic, self.config["report"]
        bl = cfg["backlogs"]
        opts = {
            "workloads": dict(rep["workloads"], reports=self.reports()),
            "joint": dict(rep["joint"], backlogs=tuple(bl["joint"])),
            "phy": dict(rep["phy"]),
            "sim_phy": dict(rep["sim_phy"], backlogs=tuple(bl["sim_phy"])),
            "serving": {
                "deployment": self.deployment,
                "arrivals": list(tr["arrivals"]),
                "qps_points": list(tr["qps_multiples"]),
                "n_ticks": int(tr["n_ticks"]),
                "n_phases": int(tr["n_phases"]),
                "seed": trace_seed,
                "phy": getattr(ucie, cfg["phy"]["name"]),
                "protocols": self.protocols}}
        return ReportSpec(sections=("workloads", "joint", "phy", "sim_phy",
                                    "serving"),
                          sim=ADAPTIVE_SIM, options=opts)

    def query(self, i: int):
        from repro.core.report import build_report
        k = i % len(self.pool)
        with jax.profiler.TraceAnnotation("bench.build"):
            spec = self.report_spec(self.pool[k])
        with jax.profiler.TraceAnnotation("bench.evaluate"):
            return k, build_report(spec)

    def cells(self, res) -> int:
        return self.cells_per_query

    def warmup(self) -> None:
        """A query of each trace seed.  The first compiles every program
        the window runs (every seed's traces share their shapes); the
        others hold the window to seeds that ran once already."""
        for i in range(len(self.pool)):
            self.query(i)

    def counters(self) -> Dict[str, Any]:
        """The report's section seconds, its engine runs summed over the
        sections, the replay's counters and the trace programs that ran,
        of the query just finished."""
        from repro.core import flitsim
        info = flitsim.last_run_info()
        replay = info.get("traces.replay", {})
        report = info.get("report", {})
        seconds = report.get("seconds", {})
        engines = report.get("engines", {}).values()
        return {"report_s": sum(seconds.values()),
                "section_s": dict(seconds),
                **{k: sum(e[k] for e in engines) for k in
                   ("sequential_depth", "cells", "certified_cells")},
                "replay_s": replay.get("replay_s", 0.0),
                "replay": {k: v for k, v in replay.items() if k != "mode"},
                "trace_runs": {prog: 1 for fam, prog in TRACE_PROGRAMS.items()
                               if fam + ".trace" in info}}

    def digest(self, i: int, res):
        """What the check needs of a checked query: the serving section's
        phases, per-phase efficiencies and winning protocols, and the
        other sections' winner labels."""
        if i not in self.checked:
            return None
        k, rep = res
        serving = rep["serving"].payload
        ds = dict(rep["workloads"].payload)
        for section in ("joint", "phy", "sim_phy"):
            ds[SECTIONS[section]] = rep[section].payload
        names = serving["trace_names"]
        return {"k": k, "labels": summarize(ds),
                "peaks": {"joint": ds["joint_frontier"]["sim_bandwidth_gbs"][
                    "peak_gbs_by_phy"],
                          "sim_phy": ds["sim_phy_frontier"][
                              "peak_sim_gbs_by_phy"]},
                "protocols": list(serving["protocols"]),
                "phases": [serving["traces"][n] for n in names],
                "eff": np.asarray([serving["phase_efficiency"][p]
                                   for p in serving["protocols"]],
                                  np.float64),
                "winners": np.asarray(
                    [serving["protocol_by_model_qps"][self.config["model"]][
                        key] for key in self.trace_keys()], dtype=object)}

    def trace_keys(self) -> List[str]:
        return [f"{a}@{x:g}" for a in self.traffic["arrivals"]
                for x in self.traffic["qps_multiples"]]

    def release(self) -> None:
        from repro.core import clear_cache
        clear_cache()

    # -- correctness ------------------------------------------------------

    def reference(self, k: int, dtype):
        """The serving section of pool entry ``k`` as the plain reference
        gives it: phases, per-phase efficiency ``[protocol, trace,
        phase]`` and bandwidth ``[protocol, trace]``."""
        cfg, tr = self.config, self.traffic
        model = ref.ByteModel(cfg)
        mu = ref.service_rate(cfg["deployment"], tr["sessions"])
        traces = []
        for a in tr["arrivals"]:
            for x in tr["qps_multiples"]:
                ticks = ref.replay(model, cfg["deployment"], tr["sessions"],
                                   x * mu, a, int(tr["n_ticks"]),
                                   self.pool[k])
                traces.append(ref.phases(ticks, int(tr["n_phases"])))
        eff = ref.trace_efficiency(self.protocols, traces, dtype=dtype,
                                   **cfg["horizons"])
        bw = ref.bandwidth(eff, traces, cfg["phy"]["raw_bandwidth_gbs"])
        return traces, eff, bw

    def reference_peaks(self, dtype) -> Dict[str, Dict[str, float]]:
        """Peak simulated bandwidth per PHY of the joint section (over
        protocols, backlogs and read fractions) and of the sim_phy section
        (at its deepest backlog), from :mod:`reference`'s full-horizon
        simulators in ``dtype``."""
        cfg, rep = self.config, self.config["report"]
        sym = [p for p in self.protocols if p in reference.SYMMETRIC]
        asym = [p for p in self.protocols if p in reference.ASYMMETRIC]
        out: Dict[str, Dict[str, float]] = {}
        for section, deepest in (("joint", False), ("sim_phy", True)):
            fracs = np.linspace(0.0, 1.0, rep[section]["n_fracs"])
            mixes = np.stack([100.0 * fracs, 100.0 - 100.0 * fracs], 1)
            eff = reference.grid_efficiency(
                sym, asym, mixes, cfg["backlogs"][section], [{}],
                dtype=dtype, **cfg["horizons"])
            eff = np.stack([eff[p][0] for p in self.protocols])  # [P,B,M]
            if deepest:
                eff = eff[:, -1]
            out[section] = {phy["name"]: float(
                (eff * np.float32(phy["raw_bandwidth_gbs"])).max())
                for phy in cfg["phys"]}
        return out

    def reference_answers(self, dtype):
        """The checked queries' answers with the reference in ``dtype`` in
        the program's place (phases rounded to it too); the other
        sections' labels are the golden's, which the reference does not
        recompute."""
        import jax.numpy as jnp
        out = []
        peaks = self.reference_peaks(dtype)
        for i in sorted(self.checked):
            k = i % len(self.pool)
            traces, eff, bw = self.reference(k, dtype)
            rounded = [{f: np.asarray(jnp.asarray(v, dtype),
                                      np.float64).tolist()
                        for f, v in t.items()} for t in traces]
            labels = np.asarray(self.protocols, dtype=object)
            out.append((i, {"k": k, "labels": self.golden, "peaks": peaks,
                            "protocols": list(self.protocols),
                            "phases": rounded,
                            "eff": eff.astype(np.float64),
                            "winners": labels[np.argmax(bw, axis=0)]}))
        return out

    def check(self, results) -> Dict[str, Dict[str, Any]]:
        """The checked queries against the float32 reference of their
        trace seed, and their labels against the golden summary."""
        import jax.numpy as jnp
        limits = self.config["check_limits"]
        refs = {k: self.reference(k, jnp.float32)
                for k in sorted({d["k"] for _, d in results})}
        peaks = self.reference_peaks(jnp.float32)
        gaps = {"phase_gap": 0.0, "eff_gap": 0.0, "winner_gap": 0.0,
                "peak_gap": 0.0, "golden_mismatches": 0}
        for _, d in results:
            traces, eff, bw = refs[d["k"]]
            got = d["eff"] if d["protocols"] == list(self.protocols) \
                else d["eff"][:0]
            for name, v in (
                    ("phase_gap", phase_gap(d["phases"], traces)),
                    ("eff_gap", _study.rel_gap(got, eff)),
                    ("winner_gap", _study.winner_shortfall(
                        bw, d["winners"], self.protocols, axis=0)),
                    ("peak_gap", peak_gap(d["peaks"], peaks))):
                gaps[name] = worst(gaps[name], v)
            gaps["golden_mismatches"] += mismatches(d["labels"],
                                                    self.golden)
        if not results:
            gaps = {k: float("nan") for k in gaps}
        return {name: {"value": v, "limit": limits[name],
                       "ok": bool(v <= limits[name])}
                for name, v in gaps.items()}


def worst(a: float, b: float) -> float:
    """``max`` that keeps a NaN (which ``max`` may drop)."""
    return float("nan") if math.isnan(a) or math.isnan(b) else max(a, b)


def peak_gap(got: Dict[str, Dict[str, float]],
             want: Dict[str, Dict[str, float]]) -> float:
    """Largest relative gap of a section's peak bandwidth on a PHY (inf
    where a PHY is missing)."""
    pairs = [(got.get(sec, {}).get(phy), v) for sec, by in want.items()
             for phy, v in by.items()]
    if any(g is None for g, _ in pairs):
        return float("inf")
    return _study.rel_gap(np.asarray([g for g, _ in pairs]),
                          np.asarray([v for _, v in pairs]))


def phase_gap(got: List[Dict[str, List[float]]],
              want: List[Dict[str, List[float]]]) -> float:
    """Largest relative difference of a phase's duration, read fraction
    or backlog (inf where the traces or phases disagree in number)."""
    if len(got) != len(want):
        return float("inf")
    largest = 0.0
    for g, w in zip(got, want):
        for field in ("durations", "read_fractions", "backlogs"):
            a = np.asarray(g[field], np.float64)
            b = np.asarray(w[field], np.float64)
            if a.shape != b.shape:
                return float("inf")
            gap = np.abs(a - b) / np.maximum(np.abs(b), 1e-12)
            if not np.all(np.isfinite(gap)):
                return float("nan")
            largest = max(largest, float(gap.max(initial=0.0)))
    return largest
