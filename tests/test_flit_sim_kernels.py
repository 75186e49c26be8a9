"""The period-exact probes of the adaptive flit simulators
(repro.kernels.flit_sim.ref, compiled by repro.core.flitsim).

Contracts:

  * the asymmetric period detector finds a period that DIVIDES the true
    rational credit period ``(x + y) / gcd(x, y)``, and its ~2-period
    extrapolated report matches the full-horizon fixed engine to 1e-6.
  * the symmetric period detector (PR 10) certifies an exact f32
    pool-state period over a short observation window and extrapolates
    the warm-window delivery sum BITWISE to the fixed horizon; grids it
    cannot certify (saturated backlogs) fall back to the chunked core.
  * ``last_run_info()`` reports the launch count and the detected-period
    histogram of a periodic run.

Everything here is deterministic — the hypothesis property test at the
bottom is skipped (not the module) when hypothesis is missing.
"""
from fractions import Fraction

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import flitsim
from repro.core.flitsim import (
    ADAPTIVE_SIM, ASYMMETRIC_PARAMS, SYMMETRIC_PARAMS, AsymmetricLaneParams,
)
from repro.core.flitsim import _sweep_impl as sweep
from repro.core.traffic import mix_grid
from repro.kernels.flit_sim import ref as fs_ref


def _dense_mixes(n=13):
    fr = np.linspace(0.0, 1.0, n)
    return list(zip((100.0 * fr).tolist(), (100.0 - 100.0 * fr).tolist()))


def _asym_rows(n_mixes=25):
    """The probe's row stack of both asymmetric protocols over
    ``mix_grid(n_mixes)``, and its cell count."""
    gx, gy = mix_grid(n_mixes)
    pstack = AsymmetricLaneParams.stack(
        [ASYMMETRIC_PARAMS[k] for k in ("lpddr6_asym", "hbm_asym")])
    rows = flitsim._asym_param_rows(pstack, jnp.asarray(gx),
                                    jnp.asarray(gy))
    return rows, 2 * n_mixes


def _true_period(x, y):
    """Exact credit period: the reduced denominator of x / (x + y)."""
    if x + y == 0:
        return 1
    return Fraction(x / (x + y)).limit_denominator(4096).denominator


class TestPeriodDetector:
    def test_asymmetric_family_period_exact(self):
        mixes = _dense_mixes(25)
        kw = dict(protocols=("lpddr6_asym", "hbm_asym"), mixes=mixes)
        f = np.asarray(sweep(**kw).efficiency)
        a = np.asarray(sweep(sim=ADAPTIVE_SIM, **kw).efficiency)
        # rational mixes: the periodic extrapolation is EXACT, not
        # merely within the adaptive tolerance
        np.testing.assert_allclose(a, f, atol=1e-6)
        info = flitsim.last_run_info()["flitsim.asymmetric"]
        assert info["cycles_run"] == fs_ref.PERIOD_OBS
        assert info["periods"]

    def test_detected_period_divides_true_period(self):
        gx, gy = mix_grid(41)          # denominators divide 40 < PERIOD_MAX
        rows, cells = _asym_rows(41)
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

    def test_saturated_grid_falls_back_to_chunked_core(self):
        # saturated pools re-round the proportional split every cycle,
        # so the exact-state certificate cannot fire; the detector must
        # decline and the chunked core must honor its 1e-3 contract
        kw = dict(protocols=tuple(SYMMETRIC_PARAMS),
                  mixes=_dense_mixes(9), backlogs=[8.0, 64.0])
        f = np.asarray(sweep(**kw).efficiency)
        a = np.asarray(sweep(sim=ADAPTIVE_SIM, **kw).efficiency)
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
