"""Query runner for the streamed joint space: perturbations of one
protocol field x PHYs x backlogs x read fractions, reduced to the winning
protocol per cell by ``DesignSpace.evaluate(..., stream=StreamConfig(
chunk_cells, devices))``.

The configuration fixes the space's axes and the streaming chunk; the
traffic file says how many perturbation points the region holds.  The
perturbation scales are evenly spaced over the configuration's range and
the seed orders them; the horizons are fixed, so every seed does the same
work.  Each query of a run asks the same space.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import numpy as np

import reference


def phy_objects(names: List[str]):
    from repro.core import ucie
    return [getattr(ucie, n) for n in names]


class Runner:
    RATE_METRIC = "stream_cells_per_s"
    P95_METRIC = None

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, chips: int):
        self.config, self.traffic = config, traffic
        self.chips = int(chips)
        self.rng = np.random.default_rng(seed)
        pert = config["perturbation"]
        lo, hi = pert["range"]
        self.field = pert["field"]
        # the same evenly spaced scales for every seed, in the seed's order:
        # two seeds then ask the same cells, and no seed changes the work
        self.scales = self.rng.permutation(np.linspace(
            float(lo), float(hi), int(traffic["perturbations"])))
        bl = config["backlogs"]
        self.backlogs = [float(b) for b in
                         np.linspace(bl["start"], bl["stop"], bl["count"])]
        self.read_fractions = [float(r) for r in
                               np.linspace(0.0, 1.0,
                                           config["read_fractions"])]
        self.phys = config["phys"]
        self.protocols = tuple(config["protocols"])
        #: the queries of the window whose answers are checked: the first
        #: two and one drawn from the seed among the next six
        self.checked = {0, 1, int(self.rng.integers(2, 8))}

    def space(self, backlogs=None, read_fractions=None):
        from repro.core import DesignSpace, axis
        return DesignSpace([
            axis("protocol_param",
                 [{self.field: float(s)} for s in self.scales]),
            axis("protocol", self.protocols),
            axis("phy", phy_objects([p["name"] for p in self.phys])),
            axis("backlog", backlogs or self.backlogs),
            axis("read_fraction", read_fractions or self.read_fractions),
        ], n_flits=int(self.config["n_flits"]),
            n_accesses=int(self.config["n_accesses"]))

    def stream_config(self):
        from repro.core import StreamConfig
        return StreamConfig(chunk_cells=int(self.config["chunk_cells"]),
                            devices=self.chips)

    def query(self, i: int):
        with jax.profiler.TraceAnnotation("bench.build"):
            space = self.space()
        with jax.profiler.TraceAnnotation("bench.evaluate"):
            return space.evaluate(metrics=(self.config["metric"],),
                                  stream=self.stream_config())

    def cells(self, res) -> int:
        return int(res.n_cells)

    def warmup(self) -> None:
        """One stream over the whole perturbation axis and just enough
        backlogs and read fractions to fill a whole dispatch: the same
        dispatch program and per-perturbation parameter stacks as the
        window's queries, a few dispatches instead of hundreds."""
        need = self.chips * int(self.config["chunk_cells"])
        q = len(self.scales)
        m = min(len(self.read_fractions), max(1, math.ceil(need / q)))
        b = min(len(self.backlogs), max(1, math.ceil(need / (q * m))))
        space = self.space(self.backlogs[:b], self.read_fractions[:m])
        space.evaluate(metrics=(self.config["metric"],),
                       stream=self.stream_config())

    def counters(self) -> Dict[str, Any]:
        from repro.core import flitsim
        rec = flitsim.last_run_info().get("stream.sim", {})
        return {k: rec.get(k) for k in ("marshal_s", "elapsed_s",
                                        "overlap_frac", "dispatches")}

    def release(self) -> None:
        from repro.core import clear_cache
        clear_cache()

    # -- correctness ------------------------------------------------------

    def reference_values(self, dtype) -> np.ndarray:
        """Reference ``sim_bandwidth_gbs`` as ``[Q, F, B, M, P]``."""
        mixes = [(100.0 * r, 100.0 - 100.0 * r) for r in self.read_fractions]
        perts = [{self.field: float(s)} for s in self.scales]
        sym = [p for p in self.protocols if p in reference.SYMMETRIC]
        asym = [p for p in self.protocols if p in reference.ASYMMETRIC]
        eff = reference.grid_efficiency(
            sym, asym, mixes, self.backlogs, perts,
            n_flits=int(self.config["n_flits"]),
            n_accesses=int(self.config["n_accesses"]), dtype=dtype)
        eff = np.stack([eff[p] for p in self.protocols], axis=-1)
        raw = np.asarray([p["raw_bandwidth_gbs"] for p in self.phys],
                         np.float32)
        # [Q, B, M, P] x [F] -> [Q, F, B, M, P], f32 like the program
        return (eff[:, None] * raw[None, :, None, None, None]).astype(
            np.float32)

    def reference_answers(self, dtype):
        """The answer of query 0 as the reference gives it in ``dtype``
        (the control puts it in the program's place)."""
        import types
        ref = self.reference_values(dtype)
        codes = np.argmax(ref, axis=-1)
        labels = np.asarray(self.protocols, dtype=object)
        answer = types.SimpleNamespace(
            winners=types.SimpleNamespace(values=labels[codes]),
            win_counts={k: int(c) for k, c in zip(
                labels, np.bincount(codes.reshape(-1),
                                    minlength=len(labels)))},
            best_by_label={k: float(b) for k, b in zip(
                labels, ref.reshape(-1, len(labels)).max(axis=0))},
            n_cells=int(codes.size))
        return [(0, self.digest(0, answer))]

    def digest(self, i: int, res):
        """The answer of query ``i`` in compact form where it is checked
        (winner codes, counts, bests), else nothing: the window keeps no
        10^7-entry label arrays alive."""
        if i not in self.checked:
            return None
        w = np.asarray(res.winners.values, dtype=object)
        codes = np.full(w.shape, -1, np.int8)
        for j, lab in enumerate(self.protocols):
            codes[w == lab] = j
        return {"codes": codes, "n_cells": int(res.n_cells),
                "counts": np.asarray([res.win_counts.get(p, 0)
                                      for p in self.protocols], np.float64),
                "bests": np.asarray([res.best_by_label.get(p, np.nan)
                                     for p in self.protocols], np.float64)}

    def check(self, results) -> Dict[str, Dict[str, Any]]:
        """The checked queries of the window against the float32
        reference: every cell's winner, the win counts and the best per
        label."""
        import jax.numpy as jnp
        limits = self.config["check_limits"]
        gaps = {k: float("nan") for k in limits}
        if results:
            ref = self.reference_values(jnp.float32)
            ref_counts = np.bincount(np.argmax(ref, axis=-1).reshape(-1),
                                     minlength=len(self.protocols))
            ref_best = ref.reshape(-1, len(self.protocols)).max(axis=0)
            gaps = {k: 0.0 for k in limits}
            for _, d in results:
                g = stream_gaps(d, ref, ref_counts, ref_best)
                gaps = {k: max(gaps[k], g[k]) if np.isfinite(g[k])
                        else float("nan") for k in gaps}
        return {k: {"value": v, "limit": limits[k],
                    "ok": bool(v <= limits[k])} for k, v in gaps.items()}


def stream_gaps(d, ref, ref_counts, ref_best) -> Dict[str, float]:
    """The numbers one streamed answer is judged by:

    * ``winner_gap``: largest share by which the reference value of the
      returned winner lies below the reference's best, over all cells;
    * ``count_gap``: largest difference of a label's win count from the
      reference's, as a share of all cells;
    * ``best_gap``: largest relative difference of a label's best value,
      over the labels whose best is a number;
    * ``nonfinite_bests``: labels whose best is not a number.
    """
    codes = d["codes"]
    n_cells = int(np.prod(ref.shape[:-1]))
    if codes.shape != ref.shape[:-1] or np.any(codes < 0):
        winner_gap = float("inf")
    else:
        chosen = np.take_along_axis(ref, codes[..., None].astype(np.int64),
                                    -1)[..., 0]
        best = ref.max(axis=-1)
        winner_gap = float(np.max((best - chosen)
                                  / np.maximum(best, 1e-12)))
    count_gap = float(np.max(np.abs(d["counts"] - ref_counts)) / n_cells)
    if d["n_cells"] != n_cells:
        count_gap = float("inf")
    bests = d["bests"]
    finite = np.isfinite(bests)
    best_gap = float(np.max(np.abs(bests[finite] - ref_best[finite])
                            / np.maximum(np.abs(ref_best[finite]), 1e-12),
                            initial=0.0))
    return {"winner_gap": winner_gap, "count_gap": count_gap,
            "best_gap": best_gap,
            "nonfinite_bests": float(np.sum(~finite))}
