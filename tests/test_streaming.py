"""Streaming sharded sweep engine + unified report API (PR 9).

Bit-identity contract: streamed winner labels equal the materialized
``argbest`` on every grid — same dims, same coords, same labels — for
simulated and analytic metrics, with and without constraints, for any
chunk size / axis order — and, since PR 10, at any async ``prefetch``
depth (the double-buffered dispatch loop overlaps host marshalling with
in-flight device execution; the fold order is FIFO, so the running
reductions are bit-identical to the sequential loop).  Plus: chunk-size
edge cases, compile-cache accounting, ``cache_stats`` family validation,
the retired positional front-ends, and the ``report(spec)``
byte-identity guarantees.
"""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

from repro.core import (
    ADAPTIVE_SIM, DesignSpace, FIXED_SIM, ReportSpec, SelectionConstraints,
    StreamConfig, axis, build_report, cache_stats, clear_cache, flitsim,
    joint_frontier,
)
from repro.core.space import STREAM_FAMILIES
from repro.core.traffic import TrafficMix
from repro.core.ucie import UCIE_A_32G_55U, UCIE_S_32G

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: cheap fixed horizons — bit-identity holds at ANY horizon, so the
#: equality tests shrink the scan instead of the grid
FAST = dict(n_flits=96, n_accesses=96)


def assert_same_winners(stream_res, materialized):
    assert stream_res.winners.dims == materialized.dims
    assert stream_res.winners.coords == materialized.coords
    np.testing.assert_array_equal(
        np.asarray(stream_res.winners.values, dtype=object),
        np.asarray(materialized.values, dtype=object))


class TestStreamingSimEquality:
    def _space(self, **kw):
        base = dict(FAST)
        base.update(kw)
        return DesignSpace([
            axis("protocol_param", [{}, {"g_slots": 2.0}]),
            axis("phy", [UCIE_S_32G, UCIE_A_32G_55U]),
            axis("backlog", [2.0, 64.0]),
            axis("read_fraction", np.linspace(0.0, 1.0, 5)),
        ], **base)

    def test_sim_bandwidth_bit_equal(self):
        space = self._space()
        res = space.evaluate(metrics=("sim_bandwidth_gbs",))
        sr = space.evaluate(metrics=("sim_bandwidth_gbs",),
                            stream=StreamConfig(chunk_cells=3, devices=1))
        assert_same_winners(sr, res["sim_bandwidth_gbs"].argbest("protocol"))
        # dispatch accounting: 2 perts x 2 backlogs x 5 mixes = 20
        # streamed cells, x 2 phys broadcast in-kernel
        assert sr.n_stream_cells == 20 and sr.n_cells == 40
        assert sr.chunk_cells == 3 and sr.peak_cells_per_chunk == 6
        assert sr.n_dispatches == 7
        assert sum(sr.win_counts.values()) == sr.n_cells

    def test_chunk_larger_than_space(self):
        space = self._space()
        res = space.evaluate(metrics=("sim_efficiency",))
        sr = space.evaluate(metrics=("sim_efficiency",),
                            stream=StreamConfig(chunk_cells=10 ** 6,
                                                devices=1))
        assert_same_winners(sr, res["sim_efficiency"].argbest("protocol"))
        assert sr.n_dispatches == 1 and sr.chunk_cells == 20

    def test_non_divisor_chunk(self):
        space = self._space()
        res = space.evaluate(metrics=("sim_efficiency",))
        for chunk in (1, 3, 7, 19):
            sr = space.evaluate(metrics=("sim_efficiency",),
                                stream=StreamConfig(chunk_cells=chunk,
                                                    devices=1))
            assert_same_winners(sr,
                                res["sim_efficiency"].argbest("protocol"))

    def test_axis_order_invariance(self):
        space = self._space()
        ref = space.evaluate(metrics=("sim_efficiency",),
                             stream=StreamConfig(chunk_cells=4, devices=1))
        per = space.evaluate(metrics=("sim_efficiency",), stream=StreamConfig(
            chunk_cells=4, devices=1,
            axis_order=("read_fraction", "backlog", "protocol_param")))
        assert_same_winners(per, ref.winners)
        assert per.win_counts == ref.win_counts

    def test_bad_axis_order_raises(self):
        with pytest.raises(ValueError, match="permutation"):
            self._space().evaluate(
                metrics=("sim_efficiency",),
                stream=StreamConfig(chunk_cells=4, devices=1,
                                    axis_order=("backlog", "bogus")))

    def test_adaptive_sim_rejected(self):
        with pytest.raises(ValueError, match="fixed-horizon"):
            self._space(sim=ADAPTIVE_SIM).evaluate(
                metrics=("sim_efficiency",), stream=StreamConfig(devices=1))

    def test_constraints_rejected_for_sim_metrics(self):
        with pytest.raises(ValueError, match="analytic metrics only"):
            self._space().evaluate(
                metrics=("sim_efficiency",),
                stream=StreamConfig(
                    devices=1,
                    constraints=SelectionConstraints(max_power_w=5.0)))

    def test_single_metric_contract(self):
        with pytest.raises(ValueError, match="ONE metric"):
            self._space().evaluate(metrics=None, stream=StreamConfig())
        with pytest.raises(ValueError, match="ONE metric"):
            self._space().evaluate(
                metrics=("sim_efficiency", "sim_bandwidth_gbs"),
                stream=StreamConfig())
        with pytest.raises(ValueError, match="not streamable"):
            self._space().evaluate(metrics=("latency_ns",),
                                   stream=StreamConfig(devices=1))

    def test_uncovered_axis_raises(self):
        with pytest.raises(ValueError, match="'k' axis"):
            DesignSpace([axis("k", [1, 2, 4])]).evaluate(
                metrics=("utilization",), stream=StreamConfig(devices=1))


class TestStreamingCatalogEquality:
    def _space(self):
        return DesignSpace([
            axis("read_fraction", np.linspace(0.0, 1.0, 7)),
            axis("shoreline_mm", [4.0, 8.0, 16.0]),
        ])

    def test_bandwidth_bit_equal(self):
        space = self._space()
        res = space.evaluate(metrics=("bandwidth_gbs",))
        sr = space.evaluate(metrics=("bandwidth_gbs",),
                            stream=StreamConfig(chunk_cells=5, devices=1))
        assert_same_winners(sr, res.frontier("bandwidth_gbs"))
        assert sr.mode == "max" and sr.reduce_dim == "system"

    def test_min_mode_metric(self):
        space = self._space()
        res = space.evaluate(metrics=("power_w",))
        sr = space.evaluate(metrics=("power_w",),
                            stream=StreamConfig(chunk_cells=4, devices=1))
        assert sr.mode == "min"
        assert_same_winners(sr, res.frontier("power_w", mode="min"))

    @pytest.mark.parametrize("cons", [
        SelectionConstraints(packaging="UCIe-A", max_backlog_knee=32.0,
                             max_power_w=40.0),
        SelectionConstraints(max_relative_bit_cost=1.5,
                             required_bandwidth_gbs=200.0),
    ])
    def test_constrained_bit_equal(self, cons):
        space = self._space()
        res = space.evaluate(metrics=("bandwidth_gbs", "power_w"))
        ref = res.frontier("bandwidth_gbs", where=res.feasible(cons))
        sr = space.evaluate(metrics=("bandwidth_gbs",),
                            stream=StreamConfig(chunk_cells=4, devices=1,
                                                constraints=cons))
        assert_same_winners(sr, ref)

    def test_none_cells_counted(self):
        cons = SelectionConstraints(packaging="UCIe-S", max_power_w=1e-3)
        space = self._space()
        res = space.evaluate(metrics=("bandwidth_gbs", "power_w"))
        ref = res.frontier("bandwidth_gbs", where=res.feasible(cons))
        sr = space.evaluate(metrics=("bandwidth_gbs",),
                            stream=StreamConfig(chunk_cells=6, devices=1,
                                                constraints=cons))
        assert_same_winners(sr, ref)
        n_none = int(np.sum(np.asarray(ref.values, dtype=object)
                            == "(none)"))
        assert n_none > 0 and sr.win_counts["(none)"] == n_none
        assert sum(sr.win_counts.values()) == sr.n_cells
        # labels the constraints never admit report NaN bests
        assert any(np.isnan(v) for v in sr.best_by_label.values())

    def test_phy_axis_routed_to_materialized(self):
        with pytest.raises(ValueError, match="materialized"):
            DesignSpace([
                axis("phy", [UCIE_S_32G]),
                axis("read_fraction", [0.5]),
            ]).evaluate(metrics=("bandwidth_gbs",),
                        stream=StreamConfig(devices=1))


class TestStreamingCompileCache:
    def test_one_compile_per_shape_then_warm(self):
        clear_cache(STREAM_FAMILIES)
        space = DesignSpace([
            axis("read_fraction", np.linspace(0.0, 1.0, 9)),
            axis("shoreline_mm", [4.0, 8.0]),
        ])
        sr = space.evaluate(metrics=("bandwidth_gbs",),
                            stream=StreamConfig(chunk_cells=4, devices=1))
        assert sr.compiles == 1 and sr.n_dispatches > 1
        warm = space.evaluate(metrics=("bandwidth_gbs",),
                              stream=StreamConfig(chunk_cells=4, devices=1))
        assert warm.compiles == 0
        assert cache_stats(STREAM_FAMILIES).misses == 1

    def test_sim_program_shared_across_backlog_and_mix_counts(self):
        # the chunk program's shapes follow the perturbation count alone:
        # a smaller space over the same perturbations (as a warm-up
        # streams) compiles the one program the full space then reuses
        perts = axis("protocol_param", [{}, {"g_slots": 2.0},
                                        {"g_slots": 3.0}])
        stream = StreamConfig(chunk_cells=8, devices=1)

        def space(backlogs, fracs):
            return DesignSpace([
                perts, axis("phy", [UCIE_S_32G, UCIE_A_32G_55U]),
                axis("backlog", backlogs),
                axis("read_fraction", np.linspace(0.0, 1.0, fracs)),
            ], **FAST)

        clear_cache(STREAM_FAMILIES)
        small = space([4.0], 3).evaluate(metrics=("sim_bandwidth_gbs",),
                                         stream=stream)
        assert small.compiles == 1 and small.chunk_cells == 8
        full = space([2.0, 16.0, 64.0], 7)
        sr = full.evaluate(metrics=("sim_bandwidth_gbs",), stream=stream)
        assert sr.compiles == 0 and sr.chunk_cells == 8
        ref = full.evaluate(metrics=("sim_bandwidth_gbs",))
        assert_same_winners(sr, ref["sim_bandwidth_gbs"].argbest("protocol"))

    def test_cache_stats_unknown_family_raises(self):
        with pytest.raises(KeyError, match="choose from"):
            cache_stats(("stream.bogus",))
        with pytest.raises(KeyError, match="flitsim.symmetric"):
            cache_stats(("flitsim.symetric",))


class TestAsyncDispatch:
    """PR 10 async double-buffered dispatch: winners, win counts and
    running bests stay bit-identical at EVERY in-flight depth, and the
    ``stream.*`` telemetry reports the overlap accounting."""

    def _space(self, n_fracs=5):
        return DesignSpace([
            axis("protocol_param", [{}, {"g_slots": 2.0}]),
            axis("phy", [UCIE_S_32G, UCIE_A_32G_55U]),
            axis("backlog", [2.0, 64.0]),
            axis("read_fraction", np.linspace(0.0, 1.0, n_fracs)),
        ], **FAST)

    def _eval(self, space, **kw):
        return space.evaluate(metrics=("sim_efficiency",),
                              stream=StreamConfig(devices=1, **kw))

    def test_prefetch_depths_bit_identical(self):
        space = self._space()
        seq = self._eval(space, chunk_cells=3, prefetch=1)
        for prefetch in (2, 3, 8):
            sr = self._eval(space, chunk_cells=3, prefetch=prefetch)
            assert_same_winners(sr, seq.winners)
            assert sr.win_counts == seq.win_counts
            assert sr.best_by_label == seq.best_by_label

    def test_prefetch_one_is_sequential(self):
        # depth 1 retires each dispatch before the next marshal starts:
        # the FIFO never holds a chunk across a marshal, so no overlap
        space = self._space()
        self._eval(space, chunk_cells=3, prefetch=1)
        info = flitsim.last_run_info()["stream.sim"]
        assert info["mode"] == "stream" and info["prefetch"] == 1
        assert info["overlap_frac"] == 0.0

    def test_stream_telemetry_contents(self):
        space = self._space()
        sr = self._eval(space, chunk_cells=3, prefetch=2)
        info = flitsim.last_run_info()["stream.sim"]
        assert info["dispatches"] == sr.n_dispatches == 7
        assert info["prefetch"] == 2
        assert info["pad_cells"] == 7 * 3 - 20 and info["cells"] == 20
        assert 0.0 <= info["overlap_frac"] <= 1.0
        assert info["elapsed_s"] > 0.0
        assert 0.0 <= info["marshal_s"] <= info["elapsed_s"]

    def test_stream_dispatch_counters(self):
        # the parameter stacks and PHY bandwidths are placed once; each
        # dispatch sends a [step, 2] int32 (perturbation, valid) and a
        # [step, 3] float32 (x, y, backlog)
        space = self._space()
        sr = self._eval(space, chunk_cells=3, prefetch=2)
        info = flitsim.last_run_info()["stream.sim"]
        step = sr.chunk_cells * sr.devices
        assert info["dispatch_arrays"] <= 2
        assert info["dispatch_bytes"] == step * (2 * 4 + 3 * 4)
        # 2 perturbations x (3 symmetric x 11 + 2 asymmetric x 6 fields)
        # f32 rows, no PHY bandwidths for the plain efficiency
        assert info["resident_bytes"] == 4 * (2 * (3 * 11 + 2 * 6) + 1)

    def test_single_chunk_smaller_than_space(self):
        # n_cells < chunk_cells: ONE dispatch; the drain loop (not the
        # bounded-depth gate) retires it
        space = self._space()
        ref = space.evaluate(metrics=("sim_efficiency",))
        sr = self._eval(space, chunk_cells=10 ** 6, prefetch=4)
        assert sr.n_dispatches == 1
        assert_same_winners(sr, ref["sim_efficiency"].argbest("protocol"))

    def test_non_divisor_tails_under_prefetch(self):
        space = self._space()
        ref = space.evaluate(metrics=("sim_efficiency",))
        for chunk in (1, 3, 7, 19):
            sr = self._eval(space, chunk_cells=chunk, prefetch=3)
            assert_same_winners(sr,
                                ref["sim_efficiency"].argbest("protocol"))

    def test_catalog_engine_prefetch_bit_identical(self):
        space = DesignSpace([
            axis("read_fraction", np.linspace(0.0, 1.0, 9)),
            axis("shoreline_mm", [4.0, 8.0]),
        ])
        seq = space.evaluate(metrics=("bandwidth_gbs",),
                             stream=StreamConfig(chunk_cells=4, devices=1,
                                                 prefetch=1))
        for prefetch in (2, 5):
            sr = space.evaluate(metrics=("bandwidth_gbs",),
                                stream=StreamConfig(chunk_cells=4,
                                                    devices=1,
                                                    prefetch=prefetch))
            assert_same_winners(sr, seq.winners)
            assert sr.win_counts == seq.win_counts
        info = flitsim.last_run_info()["stream.catalog"]
        assert info["mode"] == "stream" and info["prefetch"] == 5

    def test_prefetch_validated(self):
        with pytest.raises(ValueError, match="prefetch"):
            StreamConfig(prefetch=0)

    def test_prefetch_participates_in_stream_key(self):
        assert StreamConfig(prefetch=1).key() != \
            StreamConfig(prefetch=2).key()
        # the constraints slot stays LAST (the catalog engine peels it)
        assert StreamConfig(prefetch=2).key()[-1] == \
            StreamConfig(chunk_cells=4, prefetch=3).key()[-1]


class TestRetiredFrontEnds:
    def test_positional_front_ends_are_gone(self):
        """PR 10 retired the deprecated positional wrappers; only the
        private ``_*_impl`` engines remain (axes-first API on top)."""
        from repro.core import memsys, selector
        for mod, gone, kept in [
            (flitsim, "sweep", "_sweep_impl"),
            (flitsim, "sweep_pipelining", "_sweep_pipelining_impl"),
            (memsys, "catalog_grid", "_catalog_grid_impl"),
            (selector, "rank_grid", "_rank_grid_impl"),
        ]:
            assert not hasattr(mod, gone), gone
            assert callable(getattr(mod, kept)), kept
        import repro.core as core
        assert not hasattr(core, "catalog_grid")
        assert not hasattr(core, "rank_grid")

    def test_internal_paths_warning_free(self):
        from repro.core import rank
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            flitsim.backlog_knees(mixes=[(50.0, 50.0)], n_flits=64)
            rank(TrafficMix(70.0, 30.0))
            DesignSpace([axis("k", [1, 2, 4])]).evaluate(
                metrics=("utilization",))
        ours = [w for w in caught
                if issubclass(w.category, DeprecationWarning)
                and "front-end" in str(w.message)]
        assert not ours, [str(w.message) for w in ours]


class TestUnifiedReportAPI:
    JOINT_OPTS = dict(n_fracs=5, backlogs=(2.0, 64.0), shorelines=(8.0,),
                      n_flits=96)

    def test_joint_section_byte_identical(self):
        legacy = joint_frontier(**self.JOINT_OPTS)
        rep = build_report(ReportSpec(
            sections=("joint",), options={"joint": self.JOINT_OPTS}))
        assert json.dumps(legacy, sort_keys=True) == \
            json.dumps(rep["joint"].payload, sort_keys=True)

    def test_joint_frontier_folds_sim_bandwidth(self):
        jf = joint_frontier(**self.JOINT_OPTS)
        sbs = jf["sim_bandwidth_gbs"]
        assert sbs["phys"] == ["UCIe-S-32G-110u", "UCIe-A-32G-55u",
                               "UCIe-S-48G-110u", "UCIe-A-48G-45u"]
        assert set(sbs["best_protocol_by_phy"]) == set(sbs["phys"])
        for phy, by_bl in sbs["regimes_by_phy_backlog"].items():
            assert set(by_bl) == {"2", "64"}
            for regs in by_bl.values():
                assert all(r["approach"].split(":")[0] in "ABCDE"
                           for r in regs)

    def test_frontier_section_materialized_vs_streaming(self):
        space = DesignSpace([
            axis("read_fraction", np.linspace(0.0, 1.0, 7)),
            axis("shoreline_mm", [4.0, 8.0]),
        ])
        rep = space.report(ReportSpec(sections=("frontier",)))
        pay = rep["frontier"].payload
        assert pay["engine"] == "materialized"
        ref = space.evaluate(metrics=("bandwidth_gbs",)) \
            .frontier("bandwidth_gbs")
        assert pay["winners"] == np.asarray(ref.values,
                                            dtype=object).tolist()
        srep = space.report(ReportSpec(sections=("frontier",), options={
            "frontier": {"stream": StreamConfig(chunk_cells=4,
                                                devices=1)}}))
        spay = srep["frontier"].payload
        assert spay["engine"] == "streaming"
        assert spay["winners"] == pay["winners"]
        assert spay["peak_cells_per_chunk"] == 4

    def test_report_validation(self):
        with pytest.raises(ValueError, match="unknown report sections"):
            build_report(ReportSpec(sections=("bogus",)))
        with pytest.raises(ValueError, match="DesignSpace instance"):
            build_report(ReportSpec(sections=("frontier",)))


class TestStreamingDistributed:
    """8 virtual CPU devices (set before jax initializes — subprocess)."""

    def _run(self, body: str, devices: int = 8, timeout: int = 900) -> str:
        prog = textwrap.dedent(f"""
            import os
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count={devices}"
            import numpy as np
        """) + textwrap.dedent(body)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
        out = subprocess.run([sys.executable, "-c", prog],
                             capture_output=True, text=True,
                             timeout=timeout, env=env)
        assert out.returncode == 0, \
            f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
        return out.stdout

    def test_eight_device_sharding_bit_equal(self):
        self._run("""
        from repro.core import DesignSpace, StreamConfig, axis
        from repro.core.space import STREAM_FAMILIES, cache_stats

        space = DesignSpace([
            axis("protocol_param", [{}, {"g_slots": 2.0}, {}]),
            axis("backlog", [2.0, 8.0, 64.0, 128.0]),
            axis("read_fraction", np.linspace(0.0, 1.0, 11)),
        ], n_flits=96, n_accesses=96)
        res = space.evaluate(metrics=("sim_efficiency",))
        ref = res["sim_efficiency"].argbest("protocol")
        sr = space.evaluate(metrics=("sim_efficiency",),
                            stream=StreamConfig(chunk_cells=7, devices=8))
        assert sr.devices == 8 and sr.chunk_cells == 7
        assert sr.winners.dims == ref.dims
        np.testing.assert_array_equal(
            np.asarray(sr.winners.values, dtype=object),
            np.asarray(ref.values, dtype=object))
        assert sum(sr.win_counts.values()) == sr.n_cells == 132
        assert cache_stats(STREAM_FAMILIES).misses == 1
        warm = space.evaluate(metrics=("sim_efficiency",),
                              stream=StreamConfig(chunk_cells=7,
                                                  devices=8))
        assert warm.compiles == 0
        one = space.evaluate(metrics=("sim_efficiency",),
                             stream=StreamConfig(chunk_cells=7, devices=1))
        np.testing.assert_array_equal(
            np.asarray(sr.winners.values, dtype=object),
            np.asarray(one.winners.values, dtype=object))
        assert sr.win_counts == one.win_counts
        assert sr.best_by_label == one.best_by_label
        print("OK 8-device sim streaming")
        """)

    def test_eight_device_catalog_constrained(self):
        self._run("""
        from repro.core import (DesignSpace, SelectionConstraints,
                                StreamConfig, axis)

        cons = SelectionConstraints(packaging="UCIe-A",
                                    max_relative_bit_cost=2.0)
        space = DesignSpace([
            axis("read_fraction", np.linspace(0.0, 1.0, 21)),
            axis("shoreline_mm", [4.0, 8.0, 16.0]),
        ])
        res = space.evaluate(metrics=("bandwidth_gbs",))
        ref = res.frontier("bandwidth_gbs", where=res.feasible(cons))
        sr = space.evaluate(metrics=("bandwidth_gbs",),
                            stream=StreamConfig(chunk_cells=4, devices=8,
                                                constraints=cons))
        np.testing.assert_array_equal(
            np.asarray(sr.winners.values, dtype=object),
            np.asarray(ref.values, dtype=object))
        print("OK 8-device catalog streaming")
        """)

    def test_devices_exceeding_local_raises(self):
        space = DesignSpace([axis("read_fraction", [0.0, 1.0])])
        with pytest.raises(ValueError, match="XLA_FLAGS"):
            space.evaluate(metrics=("bandwidth_gbs",),
                           stream=StreamConfig(devices=4096))
