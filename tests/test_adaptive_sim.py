"""Convergence-adaptive flit-simulation engine (SimConfig) tests.

Contracts:

  * ``mode="fixed"`` (the default) is the exact pre-config engine — the
    pinned seed goldens in test_flitsim_sweep.py keep covering it, and the
    explicit ``sim=FIXED_SIM`` spelling is bit-identical to the default.
  * ``mode="adaptive"`` tracks the fixed engine within 1e-3 across mixes,
    backlogs, perturbations and all five protocols (property-based when
    hypothesis is available), while running fewer sequential cycles.
  * switching SimConfig never invalidates other configs' warm cache
    entries (the config participates in the shared cache key).
  * the PHY-absolute ``sim_bandwidth_gbs`` metric threads UCIePhy raw
    bandwidth into the simulated efficiency (phy axis or phy=).
  * the ``write_buffer_lines`` bugfix field: default preserves numerics
    bit-for-bit, and the write path is now independently perturbable.
"""
import dataclasses
import re

import numpy as np
import pytest

from repro.core import flitsim
from repro.core import space as space_mod
from repro.core.flitsim import (
    ADAPTIVE_SIM, FIXED_SIM, SYMMETRIC_PARAMS, SimConfig,
    SymmetricFlitParams, simulate_symmetric,
)
from repro.core.flitsim import _sweep_impl as sweep
from repro.core.flitsim import _sweep_pipelining_impl as sweep_pipelining
from repro.core.space import DesignSpace, axis
from repro.core.ucie import UCIE_A_48G_45U, UCIE_S_32G

DENSE_BACKLOGS = (1.0, 2.0, 8.0, 64.0)


def _dense_mixes(n=13):
    fr = np.linspace(0.0, 1.0, n)
    return list(zip((100.0 * fr).tolist(), (100.0 - 100.0 * fr).tolist()))


class TestSimConfig:
    def test_validation(self):
        with pytest.raises(ValueError, match="mode"):
            SimConfig(mode="turbo")
        with pytest.raises(ValueError, match="max_cycles"):
            SimConfig(max_cycles=0)
        # the loop constants are the engine's, not options
        assert [f.name for f in dataclasses.fields(SimConfig)] == \
            ["mode", "max_cycles", "trace_cycles"]

    def test_cache_keys_distinguish_configs(self):
        assert FIXED_SIM.key() == ("fixed",)
        assert ADAPTIVE_SIM.key() != FIXED_SIM.key()
        assert SimConfig(mode="adaptive", max_cycles=512).key() != \
            ADAPTIVE_SIM.key()

    def test_horizon_override(self):
        assert FIXED_SIM.horizon(2048) == 2048
        assert SimConfig(max_cycles=512).horizon(2048) == 512

    def test_divisor_chunk_lands_on_horizon(self):
        for horizon in (2048, 4096, 512, 1000):
            c = flitsim._divisor_chunk(horizon, 128)
            assert horizon % c == 0
            assert horizon // c >= 8

    def test_divisor_chunk_prefers_warm_window_alignment(self):
        # a chunk count divisible by 4 makes the reconstructed warm
        # window start exactly at horizon // 4
        for horizon in (2048, 4096, 512, 1024, 1100):
            c = flitsim._divisor_chunk(horizon, 128)
            assert (horizon // c) % 4 == 0, (horizon, c)

    def test_prime_horizon_falls_back_to_fixed(self):
        # 1021 is prime: no usable chunk divisor — adaptive must degrade
        # to the fixed engine at that horizon, not to per-cycle chunking
        assert flitsim._divisor_chunk(1021, 128) < 8
        cfg = SimConfig(mode="adaptive", max_cycles=1021)
        a = sweep(protocols=["chi"], mixes=[(1, 1)], sim=cfg)
        f = sweep(protocols=["chi"], mixes=[(1, 1)], n_flits=1021)
        np.testing.assert_array_equal(np.asarray(a.efficiency),
                                      np.asarray(f.efficiency))

    def test_chunk_larger_than_horizon(self):
        # configured chunk above the horizon: the cap clamps to
        # horizon // 8, so short horizons still get >= 8 checks
        for horizon in (64, 128, 256):
            c = flitsim._divisor_chunk(horizon, 1024)
            assert horizon % c == 0 and horizon // c >= 8, (horizon, c)

    def test_divisor_poor_small_horizon_bit_identical_to_fixed(self):
        # 2 * 31: the only divisors <= horizon // 8 are 1 and 2 — below
        # the usable-chunk floor, so the runner must hand the run to the
        # fixed engine verbatim (bit-identity, not merely within tol)
        assert flitsim._divisor_chunk(62, 128) < 8
        cfg = SimConfig(mode="adaptive", max_cycles=62)
        a = sweep(protocols=["cxl_opt"], mixes=[(2, 1), (0, 1)], sim=cfg)
        f = sweep(protocols=["cxl_opt"], mixes=[(2, 1), (0, 1)], n_flits=62)
        np.testing.assert_array_equal(np.asarray(a.efficiency),
                                      np.asarray(f.efficiency))

    def test_chunk_count_not_divisible_by_4_still_lands(self):
        # 162 = 2 * 81 carries a single factor of 2, so NO divisor can
        # make the chunk count a multiple of 4 — _divisor_chunk must
        # still take the best usable divisor (18 -> 9 chunks) rather
        # than fall back to the fixed engine
        c162 = flitsim._divisor_chunk(162, 128)
        assert c162 == 18 and (162 // c162) % 4 != 0
        cfg = SimConfig(mode="adaptive", max_cycles=162)
        a = sweep(protocols=["chi"], mixes=[(1, 1)], sim=cfg)
        f = sweep(protocols=["chi"], mixes=[(1, 1)], n_flits=162)
        # a usable divisor exists, so this runs the ADAPTIVE engine
        # (within tol), not the fixed fall-back
        assert float(np.max(np.abs(np.asarray(a.efficiency)
                                   - np.asarray(f.efficiency)))) <= 1e-3
        assert flitsim.last_run_info()["flitsim.symmetric"]["chunk"] == c162


class TestFixedModeUnchanged:
    def test_default_is_fixed_and_bit_identical(self):
        base = sweep(mixes=[(2, 1), (1, 1)])
        explicit = sweep(mixes=[(2, 1), (1, 1)], sim=FIXED_SIM)
        np.testing.assert_array_equal(np.asarray(base.efficiency),
                                      np.asarray(explicit.efficiency))

    def test_fixed_warm_after_adaptive_run(self):
        """Alternating configs must not invalidate each other's entries —
        enforced both by the shared-cache counters and by the runtime
        retrace sanitizer (zero compile events on the warm replay)."""
        from repro.lint import runtime

        flitsim.clear_compile_cache()
        mixes = [(3, 2), (1, 1)]
        sweep(mixes=mixes)                      # fixed: 2 compiles
        sweep(mixes=mixes, sim=ADAPTIVE_SIM)    # adaptive: 2 more
        after_both = flitsim.compile_cache_stats()
        assert after_both.misses == 4
        with runtime.no_retrace():              # any compile -> RetraceError
            sweep(mixes=mixes)                  # fixed again: warm
            sweep(mixes=mixes, sim=ADAPTIVE_SIM)  # adaptive again: warm
        final = flitsim.compile_cache_stats()
        assert final.misses == after_both.misses, \
            "switching SimConfig invalidated a warm cache entry"
        assert final.hits > after_both.hits


class TestAdaptiveMatchesFixed:
    def test_canonical_sweep(self):
        f = np.asarray(sweep().efficiency)
        a = np.asarray(sweep(sim=ADAPTIVE_SIM).efficiency)
        assert float(np.max(np.abs(f - a))) <= 1e-3

    def test_dense_mix_backlog_grid(self):
        mixes = _dense_mixes()
        f = np.asarray(sweep(mixes=mixes,
                             backlogs=list(DENSE_BACKLOGS)).efficiency)
        a = np.asarray(sweep(mixes=mixes, backlogs=list(DENSE_BACKLOGS),
                             sim=ADAPTIVE_SIM).efficiency)
        assert float(np.max(np.abs(f - a))) <= 1e-3

    def test_adaptive_runs_fewer_cycles(self):
        sweep(sim=ADAPTIVE_SIM)
        info = flitsim.last_run_info()
        assert set(info) >= {"flitsim.symmetric", "flitsim.asymmetric"}
        # scope to the families THIS sweep ran — other tests may leave
        # run info (e.g. a pipelining grid that legitimately hit horizon)
        for fam in ("flitsim.symmetric", "flitsim.asymmetric"):
            v = info[fam]
            assert v["cycles_run"] < v["horizon"], (fam, v)
            assert sum(v["converged_cycles"].values()) == v["cells"]

    def test_pipelining_adaptive(self):
        ks = [1, 2, 3, 4, 6]
        f = np.asarray(sweep_pipelining(ks))
        a = np.asarray(sweep_pipelining(ks, sim=ADAPTIVE_SIM))
        assert float(np.max(np.abs(f - a))) <= 1e-3
        # the k=4 saturation claim survives the adaptive engine
        assert a[3] == pytest.approx(1.0, abs=2e-3)

    def test_joint_pipelining_adaptive(self):
        f = np.asarray(sweep_pipelining((1, 2, 4), ucie_line_ui=(8.0, 16.0),
                                        device_line_ui=(32.0, 64.0)))
        a = np.asarray(sweep_pipelining((1, 2, 4), ucie_line_ui=(8.0, 16.0),
                                        device_line_ui=(32.0, 64.0),
                                        sim=ADAPTIVE_SIM))
        assert float(np.max(np.abs(f - a))) <= 1e-3

    def test_straggler_escalation_on_large_grid(self):
        """A grid above the escalation floor may strand stragglers; they
        must be re-simulated exactly (match the fixed engine ~exactly,
        not just within tol)."""
        mixes = _dense_mixes(41)
        backlogs = [1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0]
        f = np.asarray(sweep(protocols=tuple(SYMMETRIC_PARAMS),
                             mixes=mixes, backlogs=backlogs).efficiency)
        a = np.asarray(sweep(protocols=tuple(SYMMETRIC_PARAMS),
                             mixes=mixes, backlogs=backlogs,
                             sim=ADAPTIVE_SIM).efficiency)
        info = flitsim.last_run_info()["flitsim.symmetric"]
        assert info["cells"] == 3 * len(backlogs) * len(mixes)
        assert float(np.max(np.abs(f - a))) <= 1e-3
        if info["stragglers"]:
            # straggler cells ran the full fixed horizon — their rows in
            # the histogram count under "horizon"
            assert info["converged_cycles"].get("horizon", 0) >= \
                info["stragglers"]

    def test_perturbations_adaptive(self):
        perts = [{}, {"credit_lines": 0.5}, {"g_slots": 0.8}]
        f = flitsim.sweep_perturbed(perts, protocols=("cxl_opt", "chi"),
                                    mixes=[(2, 1), (1, 1)])
        a = flitsim.sweep_perturbed(perts, protocols=("cxl_opt", "chi"),
                                    mixes=[(2, 1), (1, 1)],
                                    sim=ADAPTIVE_SIM)
        dev = np.max(np.abs(f["sim_efficiency"].values
                            - a["sim_efficiency"].values))
        assert float(dev) <= 1e-3


@pytest.mark.parametrize("protocol", sorted(flitsim.SIMULATORS))
def test_adaptive_property_per_protocol(protocol):
    """Deterministic per-protocol spot check (the hypothesis sweep below
    covers random combinations)."""
    mixes = [(1, 0), (5, 3), (1, 1), (2, 7), (0, 1)]
    f = np.asarray(sweep(protocols=[protocol], mixes=mixes,
                         backlogs=[2.0, 64.0]).efficiency)
    a = np.asarray(sweep(protocols=[protocol], mixes=mixes,
                         backlogs=[2.0, 64.0],
                         sim=ADAPTIVE_SIM).efficiency)
    assert float(np.max(np.abs(f - a))) <= 1e-3, protocol


class TestAdaptiveHypothesis:
    """Property-based fixed-vs-adaptive agreement (needs hypothesis)."""

    @classmethod
    def setup_class(cls):
        pytest.importorskip(
            "hypothesis",
            reason="property tests need hypothesis; the deterministic "
                   "grids above cover the bare environment")

    def test_random_mixes_backlogs_perturbations(self):
        from hypothesis import given, settings, strategies as st

        mix = st.tuples(st.integers(0, 8), st.integers(0, 8)).filter(
            lambda t: t[0] + t[1] > 0)
        backlog = st.sampled_from([1.0, 2.0, 4.0, 16.0, 64.0, 128.0])
        pert = st.sampled_from([{}, {"credit_lines": 0.5},
                                {"write_buffer_lines": 0.5},
                                {"g_slots": 0.8}, {"read_lanes": 0.8},
                                {"total_lanes": 1.2}])

        @settings(max_examples=10, deadline=None)
        @given(mix=mix, bl=backlog, pert=pert)
        def inner(mix, bl, pert):
            perts = [{}, pert] if pert else [{}]
            kw = dict(mixes=[mix], backlogs=[bl])
            f = flitsim.sweep_perturbed(perts, **kw)
            a = flitsim.sweep_perturbed(perts, sim=ADAPTIVE_SIM, **kw)
            dev = np.max(np.abs(f["sim_efficiency"].values
                                - a["sim_efficiency"].values))
            assert float(dev) <= 1e-3, (mix, bl, pert)

        inner()


class TestDesignSpaceSimThreading:
    def test_space_and_evaluate_override(self):
        axes = [axis("mix", [(2, 1), (1, 1)]), axis("backlog", [4.0, 64.0])]
        fixed = DesignSpace(axes).evaluate(metrics=("sim_efficiency",))
        adapt = DesignSpace(axes, sim=ADAPTIVE_SIM).evaluate(
            metrics=("sim_efficiency",))
        override = DesignSpace(axes).evaluate(
            metrics=("sim_efficiency",), sim=ADAPTIVE_SIM)
        assert fixed.sim.mode == "fixed"
        assert adapt.sim.mode == "adaptive"
        dev = np.max(np.abs(fixed["sim_efficiency"].values
                            - adapt["sim_efficiency"].values))
        assert float(dev) <= 1e-3
        np.testing.assert_array_equal(adapt["sim_efficiency"].values,
                                      override["sim_efficiency"].values)

    def test_bridge_accepts_sim(self):
        from repro.roofline.analysis import (
            RooflineReport, bridge_design_space,
        )
        rep = RooflineReport(
            arch="w", shape="s", mesh="m", chips=16,
            hlo_flops_per_chip=1e12, hlo_bytes_per_chip=1e10,
            collective_bytes_per_chip=1e9, compute_s=1e-3, memory_s=1e-2,
            collective_s=1e-2, dominant="memory", model_flops=1e13,
            useful_flops_ratio=0.5, read_bytes_per_chip=7e9,
            write_bytes_per_chip=3e9)
        base = bridge_design_space({"w": rep}, n_fracs=5)
        adap = bridge_design_space({"w": rep}, n_fracs=5,
                                   sim=ADAPTIVE_SIM)
        # analytic closed forms are sim-independent -> identical report
        assert base["workloads"]["w"]["best"] == \
            adap["workloads"]["w"]["best"]

    def test_joint_frontier_accepts_sim(self):
        f = space_mod.joint_frontier(n_fracs=5, backlogs=(2.0, 64.0),
                                     shorelines=(8.0,), n_flits=1024)
        a = space_mod.joint_frontier(n_fracs=5, backlogs=(2.0, 64.0),
                                     shorelines=(8.0,), n_flits=1024,
                                     sim=SimConfig(mode="adaptive",
                                                   max_cycles=1024))
        assert f["keys"] == a["keys"]


class TestSimPhyMetric:
    def test_values_and_dims(self):
        phys = [UCIE_S_32G, UCIE_A_48G_45U]
        res = DesignSpace([
            axis("phy", phys),
            axis("read_fraction", [0.0, 0.5, 1.0]),
            axis("backlog", [64.0]),
        ]).evaluate(metrics=("sim_efficiency", "sim_bandwidth_gbs"))
        eff = res["sim_efficiency"]
        bw = res["sim_bandwidth_gbs"]
        assert bw.dims == ("protocol", "phy", "backlog", "read_fraction")
        assert bw.coord("phy") == tuple(p.name for p in phys)
        for i, p in enumerate(phys):
            np.testing.assert_allclose(
                bw.values[:, i], eff.values * p.raw_bandwidth_gbs,
                rtol=1e-6)

    def test_phy_kwarg_drops_dim(self):
        res = DesignSpace([axis("read_fraction", [0.5]),
                           axis("backlog", [64.0])],
                          phy=UCIE_S_32G).evaluate(
            metrics=("sim_efficiency", "sim_bandwidth_gbs"))
        assert "phy" not in res["sim_bandwidth_gbs"].dims

    def test_requires_phy(self):
        with pytest.raises(ValueError, match="phy"):
            DesignSpace([axis("read_fraction", [0.5])]).evaluate(
                metrics=("sim_bandwidth_gbs",))

    def test_default_metrics_include_sim_phy(self):
        space = DesignSpace([axis("phy", [UCIE_S_32G]),
                             axis("read_fraction", [0.5]),
                             axis("backlog", [64.0])])
        assert "sim_bandwidth_gbs" in space._default_metrics()

    def test_48g_scales_simulated_bandwidth(self):
        res = DesignSpace([
            axis("phy", [UCIE_S_32G, UCIE_A_48G_45U]),
            axis("read_fraction", [0.7]),
            axis("backlog", [64.0]),
        ]).evaluate(metrics=("sim_bandwidth_gbs",))
        bw = res["sim_bandwidth_gbs"]
        g32 = bw.sel(phy=UCIE_S_32G.name).values
        g48 = bw.sel(phy=UCIE_A_48G_45U.name).values
        # 48G advanced package carries more absolute GB/s at identical
        # simulated efficiency
        assert (g48 > g32).all()


class TestWriteBufferLines:
    def test_default_aliases_credit_lines(self):
        p = SymmetricFlitParams.cxl_opt()
        assert float(p.write_buffer_lines) == float(p.credit_lines)
        deep = SymmetricFlitParams.cxl_opt()
        import dataclasses
        custom = dataclasses.replace(deep, credit_lines=4.0,
                                     write_buffer_lines=None)
        assert float(custom.write_buffer_lines) == 4.0

    def test_default_numerics_preserved(self):
        """The split field must not change the engine's outputs — the
        pinned seed goldens in test_flitsim_sweep.py double-cover this."""
        eff = simulate_symmetric(SymmetricFlitParams.cxl_opt(), 2, 1)
        assert eff == pytest.approx(0.68565327, abs=1e-6)

    def test_field_is_perturbable(self):
        assert "write_buffer_lines" in flitsim.PERTURBABLE_FIELDS
        res = flitsim.sweep_perturbed(
            [{}, {"write_buffer_lines": 0.05}], protocols=("cxl_opt",),
            mixes=[(0, 1), (1, 0)], backlogs=[64.0])
        eff = res["sim_efficiency"].values      # [pert, proto, bl, mix]
        # squeezing the write buffer throttles the write-heavy mix...
        assert eff[1, 0, 0, 0] < eff[0, 0, 0, 0] - 0.01
        # ...and leaves the pure-read mix untouched
        assert eff[1, 0, 0, 1] == pytest.approx(eff[0, 0, 0, 1], abs=1e-6)

    def test_credit_perturbation_no_longer_moves_write_path(self):
        """Pre-fix, credit_lines doubled as the write-buffer bound; now a
        pure-write mix is insensitive to it."""
        res = flitsim.sweep_perturbed(
            [{}, {"credit_lines": 0.05}], protocols=("cxl_opt",),
            mixes=[(0, 1)], backlogs=[64.0])
        eff = res["sim_efficiency"].values
        assert eff[1, 0, 0, 0] == pytest.approx(eff[0, 0, 0, 0], abs=1e-6)


# -- the adaptive runner's paths: probe limits, gates, programs, launches ----

#: cells of the probe-limit grids; the probe keeps its answer while at
#: most max(cells // 4, 8) cells go uncertified (10 of 40)
LIMIT_CELLS = 40
PROBE_LIMIT = max(LIMIT_CELLS // 4, 8)


def _probe_limit_grid(family: str, undetected: int) -> dict:
    """One protocol x 40 mixes: ``40 - undetected`` mixes whose reduced
    read fraction has a denominator <= PERIOD_MAX (the probe certifies
    them) and ``undetected`` of denominator 127 (it never can)."""
    aperiodic = [(a, 127 - a) for a in range(1, undetected + 1)]
    if family == "symmetric":
        periodic = [(i, 32 - i) for i in range(LIMIT_CELLS - undetected)]
        return dict(protocols=("chi",), backlogs=[1.0],
                    mixes=periodic + aperiodic, n_flits=512)
    periodic = [(i, 40 - i) for i in range(LIMIT_CELLS - undetected)]
    return dict(protocols=("lpddr6_asym",), mixes=periodic + aperiodic,
                n_accesses=1024)


def _module_names(family: str) -> set:
    return {re.search(r"HloModule (\w+)", exe.as_text()).group(1)
            for exe in space_mod.programs([family]).values()}


def _trace(protocol: str):
    return flitsim.simulate_trace_grid(
        (protocol,), np.asarray([[50.0, 80.0]]), np.asarray([[50.0, 20.0]]),
        np.asarray([[2.0, 8.0]]), sim=SimConfig(trace_cycles=64))


#: (family, path) -> a small run that compiles that path's program
PATH_RUNS = {
    ("symmetric", "fixed"): lambda: sweep(
        protocols=("chi",), mixes=[(2, 1)], n_flits=256),
    ("symmetric", "core"): lambda: sweep(
        protocols=("chi",), mixes=[(2, 1)], backlogs=[64.0], n_flits=256,
        sim=ADAPTIVE_SIM),
    ("symmetric", "cells"): lambda: sweep(
        sim=ADAPTIVE_SIM, **_probe_limit_grid("symmetric", PROBE_LIMIT)),
    ("symmetric", "trace"): lambda: _trace("chi"),
    ("asymmetric", "fixed"): lambda: sweep(
        protocols=("lpddr6_asym",), mixes=[(2, 1)], n_accesses=256),
    ("asymmetric", "probe"): lambda: sweep(
        protocols=("lpddr6_asym",), mixes=[(2, 1)], n_accesses=1024,
        sim=ADAPTIVE_SIM),
    ("asymmetric", "trace"): lambda: _trace("lpddr6_asym"),
    ("pipelining", "fixed"): lambda: sweep_pipelining((1, 2), n_lines=64),
    ("pipelining", "core"): lambda: sweep_pipelining(
        (1, 2), n_lines=64, sim=ADAPTIVE_SIM),
}


class TestAdaptivePaths:
    @pytest.mark.parametrize("undetected", [PROBE_LIMIT, PROBE_LIMIT + 1])
    @pytest.mark.parametrize("family", ["symmetric", "asymmetric"])
    def test_probe_fallback_limit(self, family, undetected):
        kw = _probe_limit_grid(family, undetected)
        f = np.asarray(sweep(**kw).efficiency)
        a = np.asarray(sweep(sim=ADAPTIVE_SIM, **kw).efficiency)
        info = flitsim.last_run_info()[f"flitsim.{family}"]
        if undetected <= PROBE_LIMIT:
            # the probe keeps its answer; its misses escalate exactly
            assert sum(info["periods"].values()) == \
                LIMIT_CELLS - undetected
            assert info["stragglers"] == undetected
            np.testing.assert_allclose(a, f, atol=1e-6)
        else:
            # one miss too many: the chunked core runs the whole grid
            assert "periods" not in info
            assert float(np.max(np.abs(a - f))) <= 1e-3

    @pytest.mark.parametrize("above", [False, True])
    def test_symmetric_probe_backlog_gate(self, above):
        from repro.kernels.flit_sim.ref import SYM_PERIODIC_MAX_BACKLOG
        top = np.float32(SYM_PERIODIC_MAX_BACKLOG)
        if above:
            top = np.nextafter(top, np.float32(np.inf))
        flitsim.clear_compile_cache()
        sweep(protocols=("chi",), mixes=[(1, 1), (3, 1)],
              backlogs=[1.0, float(top)], n_flits=512, sim=ADAPTIVE_SIM)
        probes = [k for k in space_mod.programs(["flitsim.symmetric"])
                  if "periodic" in k]
        assert bool(probes) is not above

    @pytest.mark.parametrize("family, path", sorted(PATH_RUNS))
    def test_program_module_name(self, family, path):
        flitsim.clear_compile_cache()
        PATH_RUNS[family, path]()
        assert f"jit_flitsim_{family}_{path}" in _module_names(
            f"flitsim.{family}")

    @pytest.mark.parametrize("run, launches", [
        ("probe", 1), ("probe_escalated", 2), ("core", 1)])
    def test_launches(self, run, launches):
        if run == "core":       # saturated: the probe is skipped
            PATH_RUNS["symmetric", "core"]()
            info = flitsim.last_run_info()["flitsim.symmetric"]
        else:
            undetected = 1 if run == "probe_escalated" else 0
            sweep(sim=ADAPTIVE_SIM,
                  **_probe_limit_grid("asymmetric", undetected))
            info = flitsim.last_run_info()["flitsim.asymmetric"]
            assert info["stragglers"] == undetected
        assert info["launches"] == launches
        assert "engine" not in info
