"""Regression tests for the batched flit-simulation sweep engine.

The batched [P protocols, B backlogs, M mixes] grid must reproduce the
scalar simulator outputs, and identically-shaped sweeps must reuse the warm
compiled executable (no retrace).  No hypothesis dependency — these run
everywhere the bare tier-1 environment does.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import flitsim
from repro.core.flitsim import (
    ANALYTIC, ASYMMETRIC_PARAMS, CANONICAL_MIXES, SIMULATORS,
    SYMMETRIC_PARAMS, AsymmetricLaneParams, SymmetricFlitParams,
    simulate_asymmetric, simulate_lpddr6_pipelining, simulate_symmetric,
)
from repro.core.flitsim import _sweep_impl as sweep
from repro.core.flitsim import _sweep_pipelining_impl as sweep_pipelining


# Golden outputs of the SEED (pre-batching) scalar simulators at the five
# canonical mixes, captured by executing the original implementation
# (git c31bfce^..) on CPU.  The batched engine reproduces them bit-for-bit;
# the 1e-6 bound allows for backend-dependent float reassociation only.
SEED_GOLDEN = {
    "cxl_unopt": (0.41666749, 0.59208971, 0.62499517, 0.51138824,
                  0.37500000),
    "cxl_opt": (0.46875000, 0.68565327, 0.66666937, 0.54544550,
                0.40000045),
    "chi": (0.33333740, 0.47367275, 0.50005633, 0.40905342, 0.29999578),
    "lpddr6_asym": (0.43243244, 0.64880705, 0.57657659, 0.43237966,
                    0.28828830),
    "hbm_asym": (0.46376812, 0.69531268, 0.46376812, 0.34778363,
                 0.23188406),
}
SEED_GOLDEN_PIPELINING = {1: 0.25036675, 2: 0.50097847, 3: 0.75073314,
                          4: 1.0, 6: 1.0}


class TestSeedGoldenRegression:
    """The batched sweep reproduces the ORIGINAL scalar implementation's
    outputs — a true old-vs-new check, not new-vs-new."""

    def test_sweep_matches_seed_goldens(self):
        res = sweep()
        assert res.mixes == CANONICAL_MIXES
        for i, key in enumerate(res.protocols):
            np.testing.assert_allclose(
                np.asarray(res.efficiency[i]), SEED_GOLDEN[key],
                atol=1e-6, err_msg=key)

    def test_pipelining_matches_seed_goldens(self):
        ks = sorted(SEED_GOLDEN_PIPELINING)
        util = np.asarray(sweep_pipelining(ks))
        np.testing.assert_allclose(
            util, [SEED_GOLDEN_PIPELINING[k] for k in ks], atol=1e-6)


class TestBatchedMatchesScalar:
    """The batched sweep and the scalar wrappers stay consistent."""

    def test_all_protocols_all_canonical_mixes(self):
        res = sweep()       # all five SIMULATORS x five canonical mixes
        assert res.efficiency.shape == (len(SIMULATORS),
                                        len(CANONICAL_MIXES))
        assert tuple(res.protocols) == tuple(SIMULATORS)
        for i, key in enumerate(res.protocols):
            for j, (x, y) in enumerate(res.mixes):
                batched = float(res.efficiency[i, j])
                scalar = SIMULATORS[key](x, y)
                assert batched == pytest.approx(scalar, abs=1e-6), \
                    (key, x, y)

    def test_symmetric_backlog_axis(self):
        res = sweep(protocols=tuple(SYMMETRIC_PARAMS), mixes=[(2, 1)],
                    backlogs=[4, 64])
        assert res.efficiency.shape == (len(SYMMETRIC_PARAMS), 2, 1)
        for i, key in enumerate(res.protocols):
            for b, backlog in enumerate(res.backlogs):
                scalar = simulate_symmetric(SYMMETRIC_PARAMS[key], 2, 1,
                                            backlog=backlog)
                assert float(res.efficiency[i, b, 0]) == pytest.approx(
                    scalar, abs=1e-6), (key, backlog)

    def test_asymmetric_rows_backlog_invariant(self):
        res = sweep(protocols=tuple(ASYMMETRIC_PARAMS), mixes=[(1, 1)],
                    backlogs=[4, 64])
        e = np.asarray(res.efficiency)
        np.testing.assert_allclose(e[:, 0, :], e[:, 1, :], atol=0)

    def test_pipelining_batched_matches_scalar(self):
        util = np.asarray(sweep_pipelining([1, 2, 3, 4, 6]))
        for k, u in zip([1, 2, 3, 4, 6], util):
            assert float(u) == pytest.approx(
                simulate_lpddr6_pipelining(k), abs=1e-6), k

    def test_analytic_agreement(self):
        """The batched sweep stays within 2% of every closed form (the same
        bound the hypothesis property tests assert point-wise)."""
        res = sweep()
        for i, key in enumerate(res.protocols):
            for j, (x, y) in enumerate(res.mixes):
                a = float(ANALYTIC[key].bw_eff(x, y))
                assert abs(a - float(res.efficiency[i, j])) / a < 0.02, \
                    (key, x, y)


class TestCompileCache:
    def test_one_compile_per_family_and_no_retrace(self):
        flitsim.clear_compile_cache()
        sweep()
        first = flitsim.compile_cache_stats()
        assert first.misses == 2     # one symmetric + one asymmetric
        sweep()                      # identical shape -> warm executable
        second = flitsim.compile_cache_stats()
        assert second.misses == first.misses
        assert second.hits > first.hits

    def test_new_shape_compiles_once_then_caches(self):
        flitsim.clear_compile_cache()
        mixes = [(1, 0), (1, 1)]
        sweep(mixes=mixes)
        sweep(mixes=mixes)
        stats = flitsim.compile_cache_stats()
        assert stats.misses == 2 and stats.hits == 2

    def test_scalar_wrappers_share_cache(self):
        flitsim.clear_compile_cache()
        simulate_symmetric(SymmetricFlitParams.cxl_opt(), 2, 1)
        simulate_symmetric(SymmetricFlitParams.chi(), 1, 1)
        simulate_asymmetric(AsymmetricLaneParams.hbm(), 1, 0)
        simulate_asymmetric(AsymmetricLaneParams.lpddr6(), 0, 1)
        stats = flitsim.compile_cache_stats()
        assert stats.misses == 2 and stats.hits == 2


class TestSweepAPI:
    def test_traffic_mix_objects_accepted(self):
        from repro.core import TrafficMix
        res = sweep(protocols=["cxl_opt"],
                    mixes=[TrafficMix(2, 1), (1, 1)])
        assert res.efficiency.shape == (1, 2)
        assert res.mixes == ((2.0, 1.0), (1.0, 1.0))

    def test_for_protocol(self):
        res = sweep(protocols=["chi", "hbm_asym"])
        np.testing.assert_array_equal(np.asarray(res.for_protocol("chi")),
                                      np.asarray(res.efficiency[0]))

    def test_unknown_protocol_rejected(self):
        with pytest.raises(ValueError, match="unknown protocol"):
            sweep(protocols=["nope"])

    def test_empty_inputs_rejected(self):
        with pytest.raises(ValueError, match="at least one protocol"):
            sweep(protocols=[])
        with pytest.raises(ValueError, match="at least one traffic mix"):
            sweep(mixes=[])

    def test_numpy_backlogs_accepted(self):
        res = sweep(protocols=["chi"], mixes=[(1, 1)],
                    backlogs=np.array([8.0, 64.0]))
        assert res.efficiency.shape == (1, 2, 1)
        assert res.backlogs == (8.0, 64.0)

    def test_degenerate_mix_rejected(self):
        with pytest.raises(ValueError, match="invalid traffic mix"):
            sweep(mixes=[(0, 0)])
        with pytest.raises(ValueError, match="invalid traffic mix"):
            simulate_symmetric(SymmetricFlitParams.chi(), 0, 0)
        with pytest.raises(ValueError, match="invalid traffic mix"):
            simulate_asymmetric(AsymmetricLaneParams.hbm(), -1, 2)

    def test_param_stacking_roundtrip(self):
        stack = SymmetricFlitParams.stack(
            [SymmetricFlitParams.cxl_unopt(), SymmetricFlitParams.chi()])
        assert stack.g_slots.shape == (2,)
        assert float(stack.g_slots[1]) == 12.0

    @pytest.mark.parametrize("family", ["symmetric", "asymmetric"])
    def test_perturbed_stack_equals_stacked_perturbed_params(self, family):
        # the grids' and the stream's parameter stacks are built a field
        # at a time; every value must equal the per-row perturbed-dataclass
        # stack bit for bit
        cls, reg = {
            "symmetric": (SymmetricFlitParams, SYMMETRIC_PARAMS),
            "asymmetric": (AsymmetricLaneParams, ASYMMETRIC_PARAMS)}[family]
        perts = [{}, {"g_slots": 2.0}, {"g_slots": 1.0 / 3.0},
                 {"credit_lines": 0.37, "total_lanes": 1.3},
                 {"write_buffer_lines": 0.7, "slot_bits": 1.1111},
                 {"read_lanes": 0.55, "cmd_bits_per_access": 0.9}]
        bases = list(reg.values())
        want = cls.stack([b.perturbed(p) for p in perts for b in bases])
        got = cls.perturbed_stack(bases, perts)
        for w, g in zip(dataclasses.astuple(want), dataclasses.astuple(got)):
            w, g = np.asarray(w), np.asarray(g)
            assert g.dtype == w.dtype == np.float32
            assert g.shape == (len(perts) * len(bases),)
            np.testing.assert_array_equal(g.view(np.int32),
                                          w.view(np.int32))
