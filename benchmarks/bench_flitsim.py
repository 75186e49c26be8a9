"""Flit-level simulator vs analytic closed forms (Appendix Fig 13 +
validation of eqs 3/14/20), via the batched sweep engine.

The validation sweep (all 5 protocols x 5 canonical mixes) runs as ONE
compiled program per simulator family; a speedup row compares the batched
path against the legacy per-point loop on a 125-point grid.  Adaptive
rows run the same 125-point sweep under the convergence-adaptive chunked
engine (``ADAPTIVE_SIM``) and report the wall-clock and sequential-depth
cuts vs the fixed-horizon engine, the fixed-vs-adaptive max deviation
(asserted <= 1e-3), and the per-family cycles-to-convergence histograms.
Sensitivity rows perturb protocol parameters (slot counts, credit limits,
the write-buffer depth) through the ``protocol_param`` design-space axis,
and a joint-pipelining row sweeps (k, ucie_line_ui, device_line_ui) —
faster DRAM generations behind the logic die — in one compiled call.
"""
from __future__ import annotations

import numpy as np

from benchmarks import common
from benchmarks.common import time_us
from repro.core import flitsim, mix_grid
from repro.core.flitsim import (
    ADAPTIVE_SIM, ANALYTIC, SIMULATORS, SYMMETRIC_PARAMS, simulate_grid,
    sweep_perturbed,
)
from repro.core.flitsim import _sweep_impl as sweep
from repro.core.flitsim import _sweep_pipelining_impl as sweep_pipelining


def _per_point_grid(mixes):
    """The pre-batching path: one scalar simulator call per grid point."""
    out = []
    for key in SIMULATORS:
        for (x, y) in mixes:
            out.append(SIMULATORS[key](x, y))
    return out


def run(rows: list):
    flitsim.clear_compile_cache()

    # -- validation sweep: 5 protocols x 5 mixes, one compile per family ----
    res = sweep()
    stats = flitsim.compile_cache_stats()
    assert stats.misses == 2, (
        f"expected exactly one compile per simulator family, got {stats}")
    for i, key in enumerate(res.protocols):
        worst = 0.0
        for j, (x, y) in enumerate(res.mixes):
            a = float(ANALYTIC[key].bw_eff(x, y))
            s = float(res.efficiency[i, j])
            worst = max(worst, abs(a - s) / a)
        # scalar-call steady-state cost; auto-scaled so the sub-resolution
        # per-point dispatch still yields a real fractional-us figure
        us_scalar_pt = time_us(SIMULATORS[key], 2.0, 1.0,
                               warmup=1, iters=5, min_total_us=10_000.0)
        rows.append((f"flitsim/{key}", us_scalar_pt,
                     f"worst_err_vs_analytic={worst:.4%}"))
    rows.append(("flitsim/sweep_compiles", 0.0,
                 f"families_compiled={stats.misses};cache_hits={stats.hits}"))

    # -- batched vs per-point wall clock on a 125-point grid ----------------
    gx, gy = mix_grid(25)
    mixes = list(zip(np.asarray(gx).tolist(), np.asarray(gy).tolist()))
    n_points = len(SIMULATORS) * len(mixes)
    us_batched = time_us(lambda: sweep(mixes=mixes).efficiency,
                         warmup=1, iters=5)
    us_scalar = time_us(lambda: _per_point_grid(mixes), warmup=1, iters=3)
    speedup = us_scalar / us_batched
    rows.append((f"flitsim/sweep_batched_{n_points}pt", us_batched,
                 f"per_point_us={us_scalar:.0f};speedup=x{speedup:.1f}"))

    # -- convergence-adaptive vs fixed on the same 125-point grid -----------
    eff_fixed = np.asarray(sweep(mixes=mixes).efficiency)
    eff_adapt = np.asarray(sweep(mixes=mixes, sim=ADAPTIVE_SIM).efficiency)
    max_dev = float(np.max(np.abs(eff_fixed - eff_adapt)))
    assert max_dev <= 1e-3, (
        f"adaptive engine deviates {max_dev:.2e} > 1e-3 from the fixed "
        f"engine on the {n_points}-pt sweep")
    us_adapt = time_us(
        lambda: np.asarray(sweep(mixes=mixes, sim=ADAPTIVE_SIM).efficiency),
        warmup=1, iters=5)
    info = flitsim.last_run_info()
    depth = {fam.split(".")[1]: f"{v['cycles_run']}/{v['horizon']}"
             for fam, v in sorted(info.items())}
    # sequential_depth counts a straggler-escalation pass as full-horizon
    depth_cut = min(v["horizon"] / max(v["sequential_depth"], 1)
                    for v in info.values())
    rows.append((f"flitsim/sweep_adaptive_{n_points}pt", us_adapt,
                 f"fixed_us={us_batched:.0f};"
                 f"wall_speedup=x{us_batched / us_adapt:.2f};"
                 f"depth_cut_min=x{depth_cut:.1f};"
                 f"cycles={';'.join(f'{k}={v}' for k, v in depth.items())};"
                 f"max_dev_vs_fixed={max_dev:.1e};"
                 f"per_point_us={us_scalar:.0f};"
                 f"speedup_vs_per_point=x{us_scalar / us_adapt:.1f}"))
    for fam, v in sorted(info.items()):
        hist = ">".join(f"{c}:{n}" for c, n in sorted(
            v["converged_cycles"].items(),
            key=lambda kv: (kv[0] == "horizon",
                            int(kv[0]) if kv[0] != "horizon" else 0)))
        rows.append((f"flitsim/convergence_hist/{fam.split('.')[1]}", 0.0,
                     f"cells={v['cells']};stragglers={v['stragglers']};"
                     f"cycles_to_convergence={hist}"))

    # -- period-exact asymmetric cut: dense perturbation grid ---------------
    # [31 lane-count scales x 2 asym protocols x 41 mixes]; every mix has a
    # small credit denominator, so the detector closes the warm window at
    # PERIOD_OBS steps instead of the 4096-access horizon — this is where
    # the adaptive depth cut becomes a wall-clock cut
    gx41, gy41 = mix_grid(41)
    asym_keys = ("lpddr6_asym", "hbm_asym")
    perts_dense = [{}] + [{"total_lanes": round(0.6 + 0.03 * q, 4)}
                          for q in range(30)]
    dense_cells = len(perts_dense) * len(asym_keys) * 41

    def _dense(sim=None):
        return np.asarray(simulate_grid(asym_keys, gx41, gy41, [64.0],
                                        perturbations=perts_dense, sim=sim))

    eff_fixed_d, eff_adapt_d = _dense(), _dense(ADAPTIVE_SIM)
    max_dev_d = float(np.max(np.abs(eff_fixed_d - eff_adapt_d)))
    assert max_dev_d <= 1e-3, (
        f"period-exact engine deviates {max_dev_d:.2e} > 1e-3 on the "
        f"dense asymmetric grid")
    assert (eff_fixed_d.argmax(axis=1)
            == eff_adapt_d.argmax(axis=1)).all(), (
        "period-exact engine flips a protocol winner on the dense grid")
    us_fixed_d = time_us(_dense, warmup=1, iters=3)
    us_adapt_d = time_us(lambda: _dense(ADAPTIVE_SIM), warmup=1, iters=3)
    speedup_d = us_fixed_d / us_adapt_d
    if not common.SMOKE:
        assert speedup_d >= 2.5, (
            f"period-exact asymmetric cut only x{speedup_d:.2f} vs fixed "
            f"XLA on the {dense_cells}-cell grid (expected >= x2.5)")
    vi = flitsim.last_run_info()["flitsim.asymmetric"]
    rows.append((f"flitsim/periodic_dense_asym_{dense_cells}pt", us_adapt_d,
                 f"fixed_us={us_fixed_d:.0f};wall_speedup=x{speedup_d:.2f};"
                 f"max_dev_vs_fixed={max_dev_d:.1e};"
                 f"cycles_run={vi['cycles_run']}/{vi['horizon']};"
                 f"stragglers={vi['stragglers']};"
                 f"n_periods={len(vi.get('periods', {}))}"))

    # -- period-exact symmetric cut: dense drained-backlog grid -------------
    # [3 symmetric protocols x 3 drained backlogs x 33 mixes]; drained
    # credit pools settle into an exactly-repeating f32 core state, so the
    # symmetric detector certifies the period inside its SYM_PERIOD_OBS
    # observation window and extrapolates the warm-window delivery sum
    # BITWISE to the 2048-flit horizon — agreement is exact, not approx
    gx33, gy33 = mix_grid(33)
    sym_mixes = list(zip(gx33.tolist(), gy33.tolist()))
    sym_bls = [1.0, 1.5, 2.0]
    sym_cells = len(SYMMETRIC_PARAMS) * len(sym_bls) * 33

    def _dense_sym(sim=None):
        return np.asarray(sweep(protocols=tuple(SYMMETRIC_PARAMS),
                                mixes=sym_mixes, backlogs=sym_bls,
                                sim=sim).efficiency)

    eff_fixed_s, eff_adapt_s = _dense_sym(), _dense_sym(ADAPTIVE_SIM)
    dev_s = float(np.max(np.abs(eff_fixed_s - eff_adapt_s)))
    assert dev_s == 0.0, (
        f"symmetric period-exact engine deviates {dev_s:.2e} from the "
        f"fixed engine on the drained dense grid (expected BITWISE)")
    assert (eff_fixed_s.argmax(axis=0)
            == eff_adapt_s.argmax(axis=0)).all(), (
        "symmetric period-exact engine flips a protocol winner")
    us_fixed_s = time_us(_dense_sym, warmup=1, iters=3)
    us_adapt_s = time_us(lambda: _dense_sym(ADAPTIVE_SIM), warmup=1,
                         iters=3)
    speedup_s = us_fixed_s / us_adapt_s
    if not common.SMOKE:
        assert speedup_s >= 2.0, (
            f"symmetric period-exact cut only x{speedup_s:.2f} vs fixed "
            f"XLA on the {sym_cells}-cell grid (expected >= x2.0)")
    vs = flitsim.last_run_info()["flitsim.symmetric"]
    rows.append(("flitsim/periodic_dense_sym", us_adapt_s,
                 f"cells={sym_cells};fixed_us={us_fixed_s:.0f};"
                 f"wall_speedup=x{speedup_s:.2f};"
                 f"max_dev_vs_fixed={dev_s:.1e};"
                 f"cycles_run={vs['cycles_run']}/{vs['horizon']};"
                 f"stragglers={vs['stragglers']};"
                 f"n_periods={len(vs.get('periods', {}))}"))

    # -- million-cell asymmetric grid: cycles/sec/cell per mode -------------
    # the fixed engine is rate-measured at a reduced 256-access horizon
    # (full 4096 x 1e6 cells is minutes of CPU); the adaptive engine runs
    # the real 4096-access problem and reports its own retired-cycle rate
    if not common.SMOKE:
        m_mixes = 41
        m_q = 1_000_000 // (len(asym_keys) * m_mixes) + 1   # -> 1,000,072
        perts_m = [{}] + [{"total_lanes": round(0.5 + 1.0 * q / m_q, 6)}
                          for q in range(1, m_q)]
        m_cells = m_q * len(asym_keys) * m_mixes

        def _million(sim=None, n_accesses=4096):
            return np.asarray(simulate_grid(
                asym_keys, gx41, gy41, [64.0], perturbations=perts_m,
                n_accesses=n_accesses, sim=sim))

        us_fixed_m = time_us(lambda: _million(n_accesses=256),
                             warmup=1, iters=1)
        rate_fixed = 256 / (us_fixed_m * 1e-6)
        parts = [f"cells={m_cells}",
                 f"xla_fixed_256acc={rate_fixed:.0f}c/s/cell"]
        us_m = time_us(lambda: _million(sim=ADAPTIVE_SIM), warmup=1,
                       iters=1)
        vm = flitsim.last_run_info()["flitsim.asymmetric"]
        parts.append(
            f"xla_adaptive={vm['cycles_run'] / (us_m * 1e-6):.0f}c/s/cell"
            f"(launches={vm['launches']},stragglers={vm['stragglers']})")
        rows.append((f"flitsim/million_cell_asym_{m_cells}", us_m,
                     ";".join(parts)))

    # -- backlog-sensitivity grid (symmetric family only) -------------------
    bl = sweep(protocols=tuple(SYMMETRIC_PARAMS), mixes=[(2, 1)],
               backlogs=[1, 2, 4, 8, 64])
    for i, key in enumerate(bl.protocols):
        e = np.asarray(bl.efficiency[i, :, 0])
        rows.append((f"flitsim/backlog_sensitivity/{key}", 0.0,
                     f"eff@bl1={e[0]:.3f};eff@bl64={e[-1]:.3f}"))

    # -- protocol-parameter sensitivity via the perturbation axis -----------
    # write_buffer_lines rides along: the write-buffer depth is its own
    # perturbable field now (it used to silently alias the read credit)
    perts = [{}, {"credit_lines": 0.1}, {"g_slots": 0.8},
             {"reqs_per_g": 0.5, "resps_per_g": 0.5},
             {"write_buffer_lines": 0.1}]
    sens = sweep_perturbed(perts, protocols=tuple(SYMMETRIC_PARAMS),
                           mixes=[(2, 1)], backlogs=[4.0, 64.0])
    eff = sens["sim_efficiency"]        # [pert, protocol, backlog, mix]
    base = eff.sel(protocol_param="baseline")
    for q, label in enumerate(eff.coord("protocol_param")):
        if label == "baseline":
            continue
        for i, key in enumerate(eff.coord("protocol")):
            d4 = float(eff.values[q, i, 0, 0] - base.values[i, 0, 0])
            d64 = float(eff.values[q, i, 1, 0] - base.values[i, 1, 0])
            rows.append((f"flitsim/sensitivity/{key}/{label}", 0.0,
                         f"d_eff@bl4={d4:+.3f};d_eff@bl64={d64:+.3f}"))

    # -- Fig 13: pipelining, batched over k in one call ---------------------
    ks = (1, 2, 3, 4)
    util = np.asarray(sweep_pipelining(ks))
    for k, u in zip(ks, util):
        rows.append((f"flitsim/lpddr6_pipelining_k{k}", 0.0,
                     f"link_utilization={u:.3f}"))

    # -- joint (k x ucie_line_ui x device_line_ui) pipelining sweep ---------
    # smaller device_line_ui models faster DRAM generations; the derived
    # column reports the smallest k that saturates the link per column
    us_axis, ds_axis = (8.0, 16.0), (16.0, 32.0, 64.0)
    joint = np.asarray(sweep_pipelining((1, 2, 3, 4, 6),
                                        ucie_line_ui=us_axis,
                                        device_line_ui=ds_axis))
    for ui, u_line in zip(us_axis, joint.transpose(1, 0, 2)):
        k_sat = []
        for d, col in zip(ds_axis, u_line.T):
            sat = np.nonzero(col >= 0.99)[0]
            k_sat.append(f"dev{d:g}ui:k="
                         f"{(1, 2, 3, 4, 6)[sat[0]] if sat.size else '>6'}")
        rows.append((f"flitsim/pipelining_joint_ucie{ui:g}ui", 0.0,
                     "saturating_" + ";".join(k_sat)))
