"""Query runner for the explorer's materialized protocol study.

One query evaluates two grids back to back under the explorer's preset
``ADAPTIVE_SIM`` and reads their winners back to the host:

* the symmetric grid: protocols x backlogs x read:write mixes;
* the asymmetric grid: lane perturbations x protocols x mixes.

The configuration fixes the grids' shapes and horizons; the traffic file
says which values fill them and how the seed draws them (see
:func:`draw_mixes`).  A run draws ``pool`` distinct queries from the
seed, warms every one of them (so each shape and every straggler bucket
is compiled in set-up), then cycles through them in the window.
"""
from __future__ import annotations

from fractions import Fraction
from typing import Any, Dict, List, Sequence, Tuple

import jax
import numpy as np

import reference

SYM_FAMILY = "flitsim.symmetric"
ASYM_FAMILY = "flitsim.asymmetric"


def mix_family(spec: Dict[str, Any]) -> List[Fraction]:
    """The read fractions a traffic file's ``mixes`` entry allows.

    ``{"multiples_of": q}``        a/q for a = 0..q;
    ``{"max_denominator": q}``     every fraction in [0, 1] whose reduced
                                   denominator is at most q;
    ``{"spread_over": q, "count": n}``  the n fractions round(q k/(n+1))/q,
                                   k = 1..n (a fixed set)."""
    if "multiples_of" in spec:
        q = int(spec["multiples_of"])
        fam = {Fraction(a, q) for a in range(q + 1)}
    elif "max_denominator" in spec:
        qmax = int(spec["max_denominator"])
        fam = {Fraction(a, q) for q in range(1, qmax + 1)
               for a in range(q + 1)}
    elif "spread_over" in spec:
        q, n = int(spec["spread_over"]), int(spec["count"])
        fam = {Fraction(round(q * k / (n + 1)), q) for k in range(1, n + 1)}
    else:
        raise ValueError(f"unknown mix family {spec}")
    return sorted(fam)


def draw_mixes(rng: np.random.Generator, spec: Dict[str, Any],
               count: int) -> List[Tuple[float, float]]:
    """``count`` distinct read fractions from the family, in the seed's
    order, as ``(x, y)`` read:write weights summing to 100."""
    fam = mix_family(spec)
    if len(fam) < count:
        raise ValueError(f"mix family {spec} has {len(fam)} members, "
                         f"the grid needs {count}")
    pick = rng.choice(len(fam), size=count, replace=False)
    return [(100.0 * float(fam[i]), 100.0 - 100.0 * float(fam[i]))
            for i in pick]


def draw_perturbations(rng: np.random.Generator,
                       spec: Dict[str, Any]) -> List[Dict[str, float]]:
    """The baseline (``{}``) then ``count`` scales of one field drawn
    uniformly from ``range``."""
    lo, hi = spec["range"]
    scales = rng.uniform(float(lo), float(hi), int(spec["count"]))
    return [{}] + [{spec["field"]: float(s)} for s in scales]


class Runner:
    RATE_METRIC = "grid_cells_per_s"
    P95_METRIC = "grid_query_p95_ms"

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, chips: int):
        from repro.core import ADAPTIVE_SIM
        self.config, self.traffic = config, traffic
        self.sim = ADAPTIVE_SIM
        sym, asym = config["symmetric"], config["asymmetric"]
        self.sym_protocols = tuple(sym["protocols"])
        self.asym_protocols = tuple(asym["protocols"])
        rng = np.random.default_rng(seed)
        tsym, tasym = traffic["symmetric"], traffic["asymmetric"]
        self.pool = []
        for _ in range(int(traffic["pool"])):
            backlogs = [float(b) for b in rng.permutation(
                np.asarray(tsym["backlogs"], np.float64))]
            if len(backlogs) != int(sym["backlogs"]):
                raise ValueError("traffic backlogs do not fill the grid")
            self.pool.append({
                "sym_backlogs": backlogs,
                "sym_mixes": draw_mixes(rng, tsym["mixes"], int(sym["mixes"])),
                "asym_mixes": draw_mixes(rng, tasym["mixes"],
                                         int(asym["mixes"])),
                "asym_perts": draw_perturbations(rng, asym["perturbation"]),
            })
        self.cells_per_query = (
            len(self.sym_protocols) * int(sym["backlogs"]) * int(sym["mixes"])
            + len(self.asym_protocols) * int(asym["mixes"])
            * (int(asym["perturbation"]["count"]) + 1))

    # -- the timed query --------------------------------------------------

    def spaces(self, inp):
        from repro.core import DesignSpace, axis
        kw = dict(sim=self.sim, n_flits=int(self.config["n_flits"]),
                  n_accesses=int(self.config["n_accesses"]))
        sym = DesignSpace([axis("protocol", self.sym_protocols),
                           axis("backlog", inp["sym_backlogs"]),
                           axis("mix", inp["sym_mixes"])], **kw)
        asym = DesignSpace([axis("protocol_param", inp["asym_perts"]),
                            axis("protocol", self.asym_protocols),
                            axis("mix", inp["asym_mixes"])], **kw)
        return sym, asym

    def query(self, i: int):
        k = i % len(self.pool)
        with jax.profiler.TraceAnnotation("bench.build"):
            spaces = self.spaces(self.pool[k])
        out = [k]
        for space in spaces:
            with jax.profiler.TraceAnnotation("bench.evaluate"):
                eff = space.evaluate(metrics=("sim_efficiency",))[
                    "sim_efficiency"]
            with jax.profiler.TraceAnnotation("bench.readback"):
                win = eff.argbest("protocol")
                out += [np.asarray(eff.values, np.float32),
                        np.asarray(win.values, dtype=object)]
        return tuple(out)

    def cells(self, res) -> int:
        return self.cells_per_query

    def warmup(self) -> None:
        for i in range(len(self.pool)):
            self.query(i)

    def counters(self) -> Dict[str, Any]:
        """The engines' records of the query just finished."""
        from repro.core import flitsim
        info = flitsim.last_run_info()
        depth = certified = cells = 0
        for fam in (SYM_FAMILY, ASYM_FAMILY):
            rec = info.get(fam, {})
            depth += int(rec.get("sequential_depth", 0))
            cells += int(rec.get("cells", 0))
            certified += sum(rec.get("periods", {}).values())
        return {"sequential_depth": depth, "certified_cells": certified,
                "cells": cells}

    def digest(self, i: int, res):
        """Every answer of the window is kept and checked (they are
        small)."""
        return res

    def release(self) -> None:
        from repro.core import clear_cache
        clear_cache()

    # -- correctness ------------------------------------------------------

    def reference(self, inp, dtype) -> Tuple[np.ndarray, np.ndarray]:
        """Reference efficiency of both grids, in the program's layout:
        ``[protocol, backlog, mix]`` and ``[protocol_param, protocol,
        mix]``."""
        n_flits = int(self.config["n_flits"])
        n_acc = int(self.config["n_accesses"])
        sym = reference.grid_efficiency(
            self.sym_protocols, (), inp["sym_mixes"], inp["sym_backlogs"],
            [{}], n_flits=n_flits, n_accesses=n_acc, dtype=dtype)
        asym = reference.grid_efficiency(
            (), self.asym_protocols, inp["asym_mixes"], [0.0],
            inp["asym_perts"], n_flits=n_flits, n_accesses=n_acc,
            dtype=dtype)
        s = np.stack([sym[k][0] for k in self.sym_protocols])  # [P, B, M]
        a = np.stack([asym[k][:, 0] for k in self.asym_protocols],
                     axis=1)                                  # [Q, P, M]
        return s, a

    def reference_answers(self, dtype):
        """The window's answers as the reference gives them in ``dtype``,
        one per query of the pool (the control puts them in the
        program's place)."""
        out = []
        for k, inp in enumerate(self.pool):
            s, a = self.reference(inp, dtype)
            labels = np.asarray(self.sym_protocols, dtype=object)
            alabels = np.asarray(self.asym_protocols, dtype=object)
            out.append((k, (k, s, labels[np.argmax(s, axis=0)],
                            a, alabels[np.argmax(a, axis=1)])))
        return out

    def check(self, results) -> Dict[str, Dict[str, Any]]:
        """Every query of the window against the float32 reference of its
        input: the per-cell efficiency and the winner each cell
        returned."""
        import jax.numpy as jnp
        limits = self.config["check_limits"]
        refs = {k: self.reference(self.pool[k], jnp.float32)
                for k in sorted({r[1][0] for r in results})}
        eff_gap = winner_gap = 0.0
        for _, res in results:
            k, sv, sw, av, aw = res
            rs, ra = refs[k]
            eff_gap = max(eff_gap, rel_gap(sv, rs), rel_gap(av, ra))
            winner_gap = max(
                winner_gap,
                winner_shortfall(rs, sw, self.sym_protocols, axis=0),
                winner_shortfall(ra, aw, self.asym_protocols, axis=1))
        if not results:
            eff_gap = winner_gap = float("nan")
        return {name: {"value": v, "limit": limits[name],
                       "ok": bool(v <= limits[name])}
                for name, v in (("eff_gap", eff_gap),
                                ("winner_gap", winner_gap))}


def rel_gap(values: np.ndarray, ref: np.ndarray) -> float:
    """Largest ``|value - ref| / |ref|`` (NaN when anything is NaN)."""
    v = np.asarray(values, np.float64)
    r = np.asarray(ref, np.float64)
    if v.shape != r.shape:
        return float("inf")
    gap = np.abs(v - r) / np.maximum(np.abs(r), 1e-12)
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else float("nan")


def winner_shortfall(ref: np.ndarray, winners: np.ndarray,
                     labels: Sequence[str], axis: int) -> float:
    """Largest share by which the reference value of the returned winner
    lies below the reference's best, over all cells."""
    ref = np.moveaxis(np.asarray(ref, np.float64), axis, -1)
    codes = np.full(winners.shape, -1, np.int64)
    for j, lab in enumerate(labels):
        codes[winners == lab] = j
    if codes.shape != ref.shape[:-1] or np.any(codes < 0):
        return float("inf")
    chosen = np.take_along_axis(ref, codes[..., None], -1)[..., 0]
    best = ref.max(axis=-1)
    gap = (best - chosen) / np.maximum(best, 1e-12)
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else float("nan")
