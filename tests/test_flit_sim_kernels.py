"""Fused flit-simulator kernels (repro.kernels.flit_sim) + the
``SimConfig(engine="pallas")`` execution path and the period-exact
asymmetric convergence detector.

Contracts:

  * the Pallas kernels (interpret mode on CPU — the exact kernel bodies
    traced to XLA) agree with the jnp reference computes bit-for-bit,
    and the reference computes are what the XLA engine itself runs.
  * ``engine="pallas"`` tracks the XLA adaptive engine to float-noise
    and the fixed engine within the adaptive 1e-3 contract, for all
    three simulator families, with identical design-space winners.
  * the period detector finds a period that DIVIDES the true rational
    credit period ``(x + y) / gcd(x, y)``, and its ~2-period
    extrapolated report matches the full-horizon fixed engine to 1e-6.
  * the symmetric period detector (PR 10) certifies an exact f32
    pool-state period over a short observation window and extrapolates
    the warm-window delivery sum BITWISE to the fixed horizon; grids it
    cannot certify (saturated backlogs) fall back to the chunked core.
  * ``last_run_info()`` reports the engine, launch count and retired
    cycle rate; the periodic run adds the detected-period histogram.

Everything here is deterministic — the hypothesis property test at the
bottom is skipped (not the module) when hypothesis is missing, so this
coverage exists in the bare container unlike the flash/ssd kernel suite.
"""
from fractions import Fraction

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import flitsim
from repro.core.flitsim import (
    ADAPTIVE_SIM, ASYMMETRIC_PARAMS, FIXED_SIM, PALLAS_SIM,
    SYMMETRIC_PARAMS, AsymmetricLaneParams, SimConfig,
    SymmetricFlitParams,
)
from repro.core.flitsim import _sweep_impl as sweep
from repro.core.flitsim import _sweep_pipelining_impl as sweep_pipelining
from repro.core.traffic import mix_grid
from repro.kernels.flit_sim import kernel as fs_kernel
from repro.kernels.flit_sim import ops as fs_ops
from repro.kernels.flit_sim import ref as fs_ref


def _dense_mixes(n=13):
    fr = np.linspace(0.0, 1.0, n)
    return list(zip((100.0 * fr).tolist(), (100.0 - 100.0 * fr).tolist()))


class TestEngineConfig:
    def test_engine_validation(self):
        with pytest.raises(ValueError, match="engine"):
            SimConfig(engine="cuda")
        with pytest.raises(ValueError, match="adaptive"):
            SimConfig(mode="fixed", engine="pallas")

    def test_engine_in_cache_key(self):
        assert PALLAS_SIM.key() != ADAPTIVE_SIM.key()
        assert "pallas" in PALLAS_SIM.key()
        # fixed keys stay pinned — the goldens' cache entries survive
        assert FIXED_SIM.key() == ("fixed",)

    def test_engines_do_not_evict_each_other(self):
        flitsim.clear_compile_cache()
        mixes = [(3, 2), (1, 1)]
        sweep(mixes=mixes, sim=ADAPTIVE_SIM)
        sweep(mixes=mixes, sim=PALLAS_SIM)
        misses = flitsim.compile_cache_stats().misses
        sweep(mixes=mixes, sim=ADAPTIVE_SIM)
        sweep(mixes=mixes, sim=PALLAS_SIM)
        assert flitsim.compile_cache_stats().misses == misses


class TestKernelMatchesRef:
    """interpret=True pallas_call vs the shared jnp compute — the
    BlockSpec/grid plumbing must be value-neutral."""

    def _asym_rows(self, n_mixes=25):
        gx, gy = mix_grid(n_mixes)
        pstack = AsymmetricLaneParams.stack(
            [ASYMMETRIC_PARAMS[k] for k in ("lpddr6_asym", "hbm_asym")])
        rows = flitsim._asym_param_rows(pstack, jnp.asarray(gx),
                                        jnp.asarray(gy))
        return rows, 2 * n_mixes

    def test_asymmetric_periodic_bit_exact(self):
        rows, cells = self._asym_rows()
        tile, cpad = fs_ops.tile_for(cells)
        padded = fs_ops.pad_cells(rows, cpad)
        out_k = fs_kernel.asymmetric_periodic(padded, n_accesses=4096,
                                              tile=tile, interpret=True)
        out_r = fs_ref.asymmetric_periodic_compute(padded, n_accesses=4096)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))

    def _sym_rows(self, backlogs=(1.0, 1.5, 2.0), n_mixes=9):
        gx, gy = mix_grid(n_mixes)
        pstack = SymmetricFlitParams.stack(
            [SYMMETRIC_PARAMS[k] for k in ("cxl_opt", "chi")])
        rows = flitsim._sym_param_rows(
            pstack, jnp.asarray(gx), jnp.asarray(gy),
            jnp.asarray(backlogs, jnp.float32))
        return rows, 2 * len(backlogs) * n_mixes

    def test_symmetric_periodic_bit_exact(self):
        rows, cells = self._sym_rows()
        tile, cpad = fs_ops.tile_for(cells, fs_ops.SYM_PERIODIC_MAX_TILE)
        padded = fs_ops.pad_cells(rows, cpad)
        out_k = fs_kernel.symmetric_periodic(padded, n_flits=2048,
                                             tile=tile, interpret=True)
        out_r = fs_ref.symmetric_periodic_compute(padded, n_flits=2048)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))

    def test_pad_cells_replicates_cell_zero(self):
        rows = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)
        padded = fs_ops.pad_cells(rows, 6)
        assert padded.shape == (2, 6)
        np.testing.assert_array_equal(np.asarray(padded[:, 4:]),
                                      np.asarray(rows[:, :1]).repeat(2, 1))

    def test_tile_for_lane_aligned(self):
        for cells in (1, 127, 128, 129, 50, 9000, 1_000_072):
            tile, pad = fs_ops.tile_for(cells)
            assert pad >= cells and pad % tile == 0
            assert tile % fs_kernel.LANE == 0 or tile == pad


class TestPallasEngineMatches:
    def test_symmetric_family(self):
        mixes = _dense_mixes()
        kw = dict(protocols=("cxl_unopt", "cxl_opt", "chi"), mixes=mixes,
                  backlogs=[2.0, 8.0, 64.0])
        f = np.asarray(sweep(**kw).efficiency)
        a = np.asarray(sweep(sim=ADAPTIVE_SIM, **kw).efficiency)
        p = np.asarray(sweep(sim=PALLAS_SIM, **kw).efficiency)
        assert float(np.max(np.abs(p - f))) <= 1e-3
        # the engines share the report math — only op scheduling differs
        assert float(np.max(np.abs(p - a))) <= 1e-5
        info = flitsim.last_run_info()["flitsim.symmetric"]
        assert info["engine"] == "pallas"
        assert info["launches"] >= info["cycles_run"] // info["chunk"]

    def test_asymmetric_family_period_exact(self):
        mixes = _dense_mixes(25)
        kw = dict(protocols=("lpddr6_asym", "hbm_asym"), mixes=mixes)
        f = np.asarray(sweep(**kw).efficiency)
        for sim in (ADAPTIVE_SIM, PALLAS_SIM):
            a = np.asarray(sweep(sim=sim, **kw).efficiency)
            # rational mixes: the periodic extrapolation is EXACT, not
            # merely within the adaptive tolerance
            np.testing.assert_allclose(a, f, atol=1e-6)
            info = flitsim.last_run_info()["flitsim.asymmetric"]
            assert info["engine"] == sim.engine
            assert info["cycles_run"] == fs_ref.PERIOD_OBS
            assert info["periods"]

    def test_pipelining_family(self):
        kw = dict(ucie_line_ui=(8.0, 16.0), device_line_ui=(32.0, 64.0))
        f = np.asarray(sweep_pipelining((1, 2, 3, 4), **kw))
        a = np.asarray(sweep_pipelining((1, 2, 3, 4), sim=ADAPTIVE_SIM,
                                        **kw))
        p = np.asarray(sweep_pipelining((1, 2, 3, 4), sim=PALLAS_SIM,
                                        **kw))
        assert float(np.max(np.abs(p - f))) <= 1e-3
        np.testing.assert_array_equal(p, a)

    def test_identical_winner_labels(self):
        mixes = _dense_mixes(21)
        f = np.asarray(sweep(mixes=mixes).efficiency)
        p = np.asarray(sweep(mixes=mixes, sim=PALLAS_SIM).efficiency)
        np.testing.assert_array_equal(f.argmax(axis=0), p.argmax(axis=0))

    def test_run_info_telemetry_fields(self):
        sweep(mixes=[(2, 1), (1, 1)], sim=PALLAS_SIM)
        for fam, v in flitsim.last_run_info().items():
            if v.get("mode") != "adaptive":    # trace-scan runs ride along
                continue
            assert v["engine"] == "pallas", fam
            assert v["launches"] >= 1
            assert v["elapsed_s"] > 0.0


def _true_period(x, y):
    """Exact credit period: the reduced denominator of x / (x + y)."""
    if x + y == 0:
        return 1
    return Fraction(x / (x + y)).limit_denominator(4096).denominator


class TestPeriodDetector:
    def test_detected_period_divides_true_period(self):
        gx, gy = mix_grid(41)          # denominators divide 40 < PERIOD_MAX
        rows, cells = TestKernelMatchesRef()._asym_rows(41)
        out = np.asarray(
            fs_ref.asymmetric_periodic_compute(rows, n_accesses=4096))
        assert (out[1, :cells] > 0.5).all(), "i/40 grid must fully detect"
        periods = out[2, :cells].astype(int).reshape(2, -1)
        for j, (x, y) in enumerate(zip(np.asarray(gx), np.asarray(gy))):
            t = _true_period(float(x), float(y))
            for prot_row in periods:
                assert t % int(prot_row[j]) == 0, (x, y, t, prot_row[j])

    def test_two_period_report_matches_full_horizon(self):
        mixes = _dense_mixes(25)
        kw = dict(protocols=("lpddr6_asym", "hbm_asym"), mixes=mixes,
                  n_accesses=4096)
        full = np.asarray(sweep(**kw).efficiency)
        peri = np.asarray(sweep(sim=ADAPTIVE_SIM, **kw).efficiency)
        np.testing.assert_allclose(peri, full, atol=1e-6)
        info = flitsim.last_run_info()["flitsim.asymmetric"]
        assert info["stragglers"] == 0          # i/24 grid fully detects
        assert info["cycles_run"] == fs_ref.PERIOD_OBS

    def test_aperiodic_grid_falls_back_to_chunked_core(self):
        # irrational-ish mixes (large prime ratios): periods exceed
        # PERIOD_MAX for most cells -> the periodic cut must decline and
        # the chunked adaptive core must still honor the 1e-3 contract
        mixes = [(97, 31), (89, 53), (83, 71), (101, 97), (67, 61)]
        kw = dict(protocols=("lpddr6_asym", "hbm_asym"), mixes=mixes)
        f = np.asarray(sweep(**kw).efficiency)
        a = np.asarray(sweep(sim=ADAPTIVE_SIM, **kw).efficiency)
        assert float(np.max(np.abs(a - f))) <= 1e-3
        info = flitsim.last_run_info()["flitsim.asymmetric"]
        assert "periods" not in info     # chunked core, not the detector

    def test_partial_detection_escalates_exactly(self):
        # small-denominator mixes (detected) mixed with prime-ratio ones
        # (undetected, below the fall-back fraction): the undetected
        # cells re-run the exact fixed path, so the whole grid is exact
        mixes = ([(i, 40 - i) for i in range(0, 36, 4)]
                 + [(97, 31), (89, 53)])
        kw = dict(protocols=("lpddr6_asym", "hbm_asym"), mixes=mixes)
        f = np.asarray(sweep(**kw).efficiency)
        a = np.asarray(sweep(sim=ADAPTIVE_SIM, **kw).efficiency)
        info = flitsim.last_run_info()["flitsim.asymmetric"]
        if "periods" in info and info["stragglers"]:
            np.testing.assert_allclose(a, f, atol=1e-6)
            assert info["launches"] == 2
        else:       # chunked fall-back still honors the engine contract
            assert float(np.max(np.abs(a - f))) <= 1e-3


class TestSymmetricPeriodicDetector:
    """PR 10: exact-state symmetric period certificate + bitwise
    warm-window extrapolation, with chunked-core fall-back."""

    LOW = dict(protocols=tuple(SYMMETRIC_PARAMS),
               mixes=_dense_mixes(9), backlogs=[1.0, 1.5, 2.0])

    def test_low_backlog_grid_bitwise_vs_fixed(self):
        f = np.asarray(sweep(**self.LOW).efficiency)
        a = np.asarray(sweep(sim=ADAPTIVE_SIM, **self.LOW).efficiency)
        np.testing.assert_array_equal(a, f)     # BITWISE, not approx
        info = flitsim.last_run_info()["flitsim.symmetric"]
        assert info["cycles_run"] == fs_ref.SYM_PERIOD_OBS
        assert "periods" in info
        assert sum(info["periods"].values()) + info["stragglers"] == \
            3 * 3 * 9

    def test_pallas_engine_bitwise_vs_fixed(self):
        f = np.asarray(sweep(**self.LOW).efficiency)
        p = np.asarray(sweep(sim=PALLAS_SIM, **self.LOW).efficiency)
        np.testing.assert_array_equal(p, f)
        info = flitsim.last_run_info()["flitsim.symmetric"]
        assert info["engine"] == "pallas"
        assert info["cycles_run"] == fs_ref.SYM_PERIOD_OBS

    def test_saturated_grid_falls_back_to_chunked_core(self):
        # saturated pools re-round the proportional split every cycle,
        # so the exact-state certificate cannot fire; the detector must
        # decline and the chunked core must honor its 1e-3 contract
        kw = dict(protocols=tuple(SYMMETRIC_PARAMS),
                  mixes=_dense_mixes(9), backlogs=[8.0, 64.0])
        f = np.asarray(sweep(**kw).efficiency)
        for sim in (ADAPTIVE_SIM, PALLAS_SIM):
            a = np.asarray(sweep(sim=sim, **kw).efficiency)
            assert float(np.max(np.abs(a - f))) <= 1e-3
            info = flitsim.last_run_info()["flitsim.symmetric"]
            assert "periods" not in info    # chunked core, not detector
            assert info["cycles_run"] > fs_ref.SYM_PERIOD_OBS

    def test_short_horizon_skips_detector(self):
        # the observation window must fit inside the pre-warm quarter of
        # the horizon: 96 // 4 < SYM_PERIOD_OBS, so the gate declines
        kw = dict(self.LOW, n_flits=96)
        f = np.asarray(sweep(**kw).efficiency)
        a = np.asarray(sweep(sim=ADAPTIVE_SIM, **kw).efficiency)
        assert float(np.max(np.abs(a - f))) <= 1e-3
        assert "periods" not in flitsim.last_run_info()["flitsim.symmetric"]


class TestPeriodDetectorHypothesis:
    """Property form of the divides-true-period law (needs hypothesis;
    the deterministic 41-mix grid above covers the bare container)."""

    def test_random_rational_mixes(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=25, deadline=None)
        @given(x=st.integers(0, 24), y=st.integers(0, 24))
        def inner(x, y):
            if x + y == 0 or _true_period(x, y) > fs_ref.PERIOD_MAX:
                return
            pstack = AsymmetricLaneParams.stack(
                [AsymmetricLaneParams.lpddr6()])
            rows = flitsim._asym_param_rows(
                pstack, jnp.asarray([float(x)]), jnp.asarray([float(y)]))
            out = np.asarray(fs_ref.asymmetric_periodic_compute(
                rows, n_accesses=4096))
            assert out[1, 0] > 0.5, (x, y)
            assert _true_period(x, y) % int(out[2, 0]) == 0, (x, y)

        inner()
