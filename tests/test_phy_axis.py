"""Tests for the first-class ``phy`` axis, analytic ``catalog_param``
perturbations, and the constraint-aware ``feasible()`` / ``where=`` masks.

Acceptance contracts (ISSUE 4):

  * the full [phy x mix x shoreline] catalog evaluation compiles exactly
    once per engine family (shared-cache counters);
  * UCIe-A / UCIe-S rows of the PHY-stacked space are BIT-identical to the
    pre-axis flat catalog (``_catalog_grid_impl`` keys ``.../UCIe-A``);
  * ``SpaceResult.frontier(..., where=mask)`` reproduces the
    ``selector._rank_grid_impl`` feasible-set winners on the bridge layout;
  * UCIe-2.0 / 48G entries scale density linearly at constant pJ/b;
  * per-cell artifact consumers SKIP (not crash on) artifacts carrying the
    new ``phy`` / ``catalog_param`` dimensions.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.core import space as space_mod
from repro.core.memsys import (
    approach_catalog_items, approach_grid, default_catalog_items,
)
from repro.core.memsys import _catalog_grid_impl as catalog_grid
from repro.core.selector import (
    SelectionConstraints, grid_ranking, system_mask,
)
from repro.core.selector import _rank_grid_impl as rank_grid
from repro.core.space import DesignSpace, OWN_MIX, axis
from repro.core.traffic import TrafficMix
from repro.core.ucie import (
    PERTURBABLE_PHY_FIELDS, UCIE_A_32G_55U, UCIE_A_48G_45U, UCIE_S_32G,
    UCIE_S_48G_110U,
)

PHYS = (UCIE_S_32G, UCIE_A_32G_55U, UCIE_S_48G_110U, UCIE_A_48G_45U)

#: flat-catalog key suffix -> canonical phy label on the axis
TAG_TO_PHY = {"UCIe-A": UCIE_A_32G_55U.name, "UCIe-S": UCIE_S_32G.name}


class TestUcie2Entries:
    """UCIe 2.0 / 48G data points: §V bump-limited scaling — density grows
    linearly with data rate at constant power efficiency."""

    @pytest.mark.parametrize("g48,g32,lin_gain", [
        (UCIE_S_48G_110U, UCIE_S_32G, 1.5),
        # the 48G advanced point rides the 45um pitch: 1.5x rate on top of
        # the (55/45) linear pitch gain over the published 55um numbers
        (UCIE_A_48G_45U, UCIE_A_32G_55U, 1.5 * 55.0 / 45.0),
    ])
    def test_density_scales_at_constant_power(self, g48, g32, lin_gain):
        assert g48.data_rate_gtps == 48.0
        assert g48.linear_density_gbs_mm == pytest.approx(
            g32.linear_density_gbs_mm * lin_gain)
        assert g48.power_pj_per_bit == g32.power_pj_per_bit
        assert g48.lanes_per_direction == g32.lanes_per_direction
        assert g48.raw_bandwidth_gbs == pytest.approx(
            g32.raw_bandwidth_gbs * 1.5)

    def test_s48_exact_values(self):
        assert UCIE_S_48G_110U.linear_density_gbs_mm == pytest.approx(
            224.0 * 1.5)
        assert UCIE_S_48G_110U.areal_density_gbs_mm2 == pytest.approx(
            145.44 * 1.5)

    def test_catalog_monotone_in_data_rate(self):
        """Every approach's deliverable bandwidth is monotonically better
        on the 48G generation at every mix — the paper's §V claim."""
        fracs = np.linspace(0.0, 1.0, 9)
        res = DesignSpace([
            axis("phy", [UCIE_S_32G, UCIE_S_48G_110U]),
            axis("read_fraction", fracs),
        ]).evaluate(metrics=("bandwidth_gbs", "pj_per_bit"))
        bw = res["bandwidth_gbs"]
        assert (bw.sel(phy=UCIE_S_48G_110U.name).values
                >= bw.sel(phy=UCIE_S_32G.name).values).all()
        pj = res["pj_per_bit"]
        np.testing.assert_array_equal(
            pj.sel(phy=UCIE_S_48G_110U.name).values,
            pj.sel(phy=UCIE_S_32G.name).values)

    def test_phy_perturbed_validates_fields(self):
        with pytest.raises(ValueError, match="unknown catalog perturbation"):
            UCIE_S_32G.perturbed({"warp_drive": 2.0})
        p = UCIE_S_32G.perturbed({"power_pj_per_bit": 2.0})
        assert p.power_pj_per_bit == pytest.approx(1.0)
        assert p.linear_density_gbs_mm == UCIE_S_32G.linear_density_gbs_mm


class TestPhyAxisCompileOnce:
    """Acceptance: the full [phy x mix x shoreline] space compiles exactly
    once per engine family, then runs warm."""

    def _space(self):
        return DesignSpace([
            axis("phy", list(PHYS)),
            axis("read_fraction", np.linspace(0.0, 1.0, 5)),
            axis("shoreline_mm", [4.0, 8.0]),
        ])

    def test_one_compile_per_family(self):
        space_mod.clear_cache()
        res = self._space().evaluate()
        assert space_mod.cache_stats(("memsys.catalog",)).misses == 1
        assert space_mod.cache_stats(("memsys.approach",)).misses == 1
        assert res["bandwidth_gbs"].dims == (
            "system", "phy", "read_fraction", "shoreline_mm")
        assert res["linear_density_gbs_mm"].dims == (
            "approach", "phy", "read_fraction")
        assert res["bandwidth_gbs"].coord("phy") == tuple(
            p.name for p in PHYS)
        first = space_mod.cache_stats()
        self._space().evaluate()
        second = space_mod.cache_stats()
        assert second.misses == first.misses
        assert second.hits > first.hits

    def test_phy_axis_excludes_bus_baselines(self):
        res = self._space().evaluate(metrics=("bandwidth_gbs",))
        keys = res["bandwidth_gbs"].coord("system")
        assert keys == tuple(k for k, _ in approach_catalog_items())
        assert not any("/" in k or k in ("HBM4", "LPDDR6") for k in keys)

    def test_phy_axis_conflicts_rejected(self):
        with pytest.raises(ValueError, match="not both"):
            DesignSpace([axis("phy", [UCIE_S_32G]),
                         axis("read_fraction", [0.5])],
                        phy=UCIE_A_32G_55U)
        with pytest.raises(ValueError, match="custom catalog"):
            DesignSpace([axis("phy", [UCIE_S_32G]),
                         axis("read_fraction", [0.5])],
                        catalog=dict(default_catalog_items()))
        with pytest.raises(ValueError, match="UCIePhy"):
            axis("phy", ["UCIe-A"])
        with pytest.raises(ValueError, match="duplicate phy"):
            axis("phy", [UCIE_S_32G, UCIE_S_32G])


class TestPhyAxisBitIdentity:
    """Acceptance: UCIe-A / UCIe-S rows of the PHY-stacked space are
    bit-identical to the pre-axis flat catalog and approach grids."""

    FRACS = np.linspace(0.0, 1.0, 7)

    def test_catalog_rows_match_flat_catalog(self):
        res = DesignSpace([
            axis("phy", list(PHYS)),
            axis("read_fraction", self.FRACS),
            axis("shoreline_mm", [4.0, 8.0]),
        ]).evaluate(metrics=("bandwidth_gbs", "pj_per_bit", "power_w"))
        x = (100.0 * self.FRACS)[:, None]
        flat = catalog_grid(x, 100.0 - x, np.asarray([4.0, 8.0]))
        sys_keys = res["bandwidth_gbs"].coord("system")
        checked = 0
        for i, key in enumerate(flat.keys):
            if "/" not in key:
                continue            # bus baselines have no phy
            app, tag = key.split("/")
            sub = res.sel(phy=TAG_TO_PHY[tag])
            s = sys_keys.index(app)
            for metric, legacy in (("bandwidth_gbs", flat.bandwidth_gbs),
                                   ("pj_per_bit", flat.pj_per_bit),
                                   ("power_w", flat.power_w)):
                np.testing.assert_array_equal(
                    sub[metric].values[s], np.asarray(legacy)[i],
                    err_msg=f"{key}/{metric}")
            checked += 1
        assert checked == 12        # 6 approaches x 2 packages

    def test_approach_rows_match_approach_grid(self):
        res = DesignSpace([
            axis("phy", list(PHYS)),
            axis("read_fraction", self.FRACS),
        ]).evaluate(metrics=("linear_density_gbs_mm",
                             "areal_density_gbs_mm2",
                             "approach_pj_per_bit"))
        x = 100.0 * self.FRACS
        for p in PHYS:
            ag = approach_grid(p, x, 100.0 - x)
            sub = res.sel(phy=p)            # UCIePhy selects by name
            np.testing.assert_array_equal(
                sub["linear_density_gbs_mm"].values, np.asarray(ag.linear))
            np.testing.assert_array_equal(
                sub["areal_density_gbs_mm2"].values, np.asarray(ag.areal))
            np.testing.assert_array_equal(
                sub["approach_pj_per_bit"].values,
                np.asarray(ag.pj_per_bit))

    def test_single_phy_axis_matches_phy_kwarg(self):
        """A one-entry phy axis and the legacy DesignSpace(phy=...) are the
        same program (same cache key), so bit-identical."""
        res_axis = DesignSpace([
            axis("phy", [UCIE_A_32G_55U]),
            axis("read_fraction", self.FRACS),
        ]).evaluate(metrics=("linear_density_gbs_mm",))
        res_kw = DesignSpace([axis("read_fraction", self.FRACS)],
                             phy=UCIE_A_32G_55U).evaluate(
            metrics=("linear_density_gbs_mm",))
        np.testing.assert_array_equal(
            res_axis["linear_density_gbs_mm"].sel(
                phy=UCIE_A_32G_55U.name).values,
            res_kw["linear_density_gbs_mm"].values)


class TestCatalogParam:
    """Analytic perturbation axis mirroring flitsim's protocol_param."""

    def test_baseline_row_identical_to_unperturbed(self):
        res = DesignSpace([
            axis("catalog_param", [{}, {"power_pj_per_bit": 2.0}]),
            axis("read_fraction", [0.25, 0.75]),
        ]).evaluate(metrics=("bandwidth_gbs", "pj_per_bit"))
        plain = DesignSpace([axis("read_fraction", [0.25, 0.75])]).evaluate(
            metrics=("bandwidth_gbs",))
        assert res["bandwidth_gbs"].dims == (
            "catalog_param", "system", "read_fraction")
        assert res["bandwidth_gbs"].coord("catalog_param")[0] == "baseline"
        np.testing.assert_array_equal(
            res["bandwidth_gbs"].sel(catalog_param="baseline").values,
            plain["bandwidth_gbs"].values)

    def test_perturbations_bind_ucie_only(self):
        """Scaling PHY pJ/b or shoreline density perturbs every UCIe
        system and leaves the (phy-less) bus baselines untouched."""
        res = DesignSpace([
            axis("catalog_param", [{}, {"power_pj_per_bit": 2.0},
                                   {"linear_density_gbs_mm": 0.5}]),
            axis("read_fraction", [0.5]),
        ]).evaluate(metrics=("bandwidth_gbs", "pj_per_bit"))
        keys = res["bandwidth_gbs"].coord("system")
        pj = res["pj_per_bit"].values
        bw = res["bandwidth_gbs"].values
        for s, key in enumerate(keys):
            if "/" in key:          # UCIe-attached
                assert pj[1, s, 0] == pytest.approx(2.0 * pj[0, s, 0]), key
                assert bw[2, s, 0] == pytest.approx(0.5 * bw[0, s, 0]), key
            else:                   # bus baseline: no PHY to perturb
                assert pj[1, s, 0] == pj[0, s, 0], key
                assert bw[2, s, 0] == bw[0, s, 0], key

    def test_composes_with_phy_axis(self):
        res = DesignSpace([
            axis("catalog_param", [{}, ("half_density",
                                        {"linear_density_gbs_mm": 0.5})]),
            axis("phy", [UCIE_S_32G, UCIE_A_32G_55U]),
            axis("read_fraction", [0.5]),
        ]).evaluate(metrics=("bandwidth_gbs",))
        bw = res["bandwidth_gbs"]
        assert bw.dims == ("catalog_param", "system", "phy",
                           "read_fraction")
        assert bw.coord("catalog_param") == ("baseline", "half_density")
        np.testing.assert_allclose(
            bw.sel(catalog_param="half_density").values,
            0.5 * bw.sel(catalog_param="baseline").values, rtol=1e-6)

    def test_unknown_field_rejected_at_axis_build(self):
        with pytest.raises(ValueError, match="unknown catalog perturbation"):
            axis("catalog_param", [{"g_slots": 0.5}])

    def test_compile_once_with_catalog_param(self):
        space_mod.clear_cache()
        DesignSpace([
            axis("catalog_param", [{}, {"power_pj_per_bit": 1.5}]),
            axis("read_fraction", [0.0, 0.5, 1.0]),
        ]).evaluate(metrics=("bandwidth_gbs",))
        assert space_mod.cache_stats(("memsys.catalog",)).misses == 1


class TestFeasibleWhere:
    """First-class feasibility: boolean SpaceArray masks composable with
    arbitrary axes via where=."""

    FRACS = np.linspace(0.0, 1.0, 11)

    @pytest.fixture(scope="class")
    def res(self):
        return DesignSpace([
            axis("read_fraction", self.FRACS),
            axis("shoreline_mm", [4.0, 8.0]),
        ]).evaluate()

    def test_static_mask_composition(self, res):
        """packaging + bit-cost masks equal the legacy selector
        system_mask (ex-_static_mask), broadcast over the grid."""
        cons = SelectionConstraints(packaging="UCIe-A",
                                    max_relative_bit_cost=2.0)
        m = res.feasible(cons)
        assert m.dims == res["bandwidth_gbs"].dims
        static = system_mask(default_catalog_items(), cons)
        np.testing.assert_array_equal(
            m.values, np.broadcast_to(static[:, None, None], m.shape))

    @pytest.mark.parametrize("cons", [
        SelectionConstraints(),
        SelectionConstraints(packaging="UCIe-S"),
        SelectionConstraints(max_relative_bit_cost=2.0),
        SelectionConstraints(max_power_w=5.0),
        SelectionConstraints(required_bandwidth_gbs=500.0),
    ])
    def test_frontier_where_matches_rank_grid(self, res, cons):
        front = res.frontier("bandwidth_gbs", where=res.feasible(cons))
        g = rank_grid((100.0 * self.FRACS)[:, None],
                      (100.0 - 100.0 * self.FRACS)[:, None],
                      constraints=cons,
                      shoreline_mm=np.asarray([4.0, 8.0]))
        np.testing.assert_array_equal(front.values, g.best_keys())

    def test_none_sentinel_matches_rank_grid(self, res):
        cons = SelectionConstraints(required_bandwidth_gbs=1e9)
        front = res.frontier("bandwidth_gbs", where=res.feasible(cons))
        assert (front.values == "(none)").all()

    def test_where_broadcasts_extra_dims(self, res):
        """A grid-shaped mask applied to the per-system latency column
        broadcasts the frontier over the mask's extra dims."""
        mask = res.feasible(SelectionConstraints(packaging="UCIe-S"))
        front = res.frontier("latency_ns", mode="min", where=mask)
        assert front.dims == ("read_fraction", "shoreline_mm")
        assert all("UCIe-S" in k for k in front.values.ravel())

    def test_sel_where_masks_to_nan(self, res):
        mask = res.feasible(SelectionConstraints(packaging="UCIe-A"))
        bw = res["bandwidth_gbs"].sel(where=mask, shoreline_mm=8.0)
        keys = res["bandwidth_gbs"].coord("system")
        for s, key in enumerate(keys):
            if "UCIe-A" in key:
                assert np.isfinite(bw.values[s]).all(), key
            else:
                assert np.isnan(bw.values[s]).all(), key

    def test_knee_budget_is_per_mix_on_a_mix_axis(self, res):
        """On a dense mix axis the knee budget follows each mix POINT —
        a strict refinement of rank_grid's canonical-mix envelope."""
        from repro.core import flitsim
        per = flitsim.backlog_knees(
            mixes=[(100.0 * r, 100.0 - 100.0 * r) for r in self.FRACS],
            per_mix=True)
        budget = float(np.min(per["cxl_opt"]))
        mask = res.feasible(SelectionConstraints(max_backlog_knee=budget))
        keys = res["bandwidth_gbs"].coord("system")
        e_row = mask.values[keys.index("E:cxl-mem-opt/UCIe-A")]
        np.testing.assert_array_equal(
            e_row[:, 0], per["cxl_opt"] <= budget)
        # the envelope (rank_grid semantics) would exclude E everywhere
        assert system_mask(
            default_catalog_items(),
            SelectionConstraints(max_backlog_knee=budget))[
            keys.index("E:cxl-mem-opt/UCIe-A")] == (
            float(np.max(per["cxl_opt"])) <= budget)

    def test_bridge_layout_matches_legacy_grid_ranking(self):
        """Acceptance: frontier(where=feasible) reproduces the legacy
        grid_ranking + valid_mask plumbing on the bridge layout
        [workload_config x mix(OWN+grid) x shoreline]."""
        from repro.core import flitsim
        from repro.core.memsys import CatalogGrid
        from repro.core.selector import sim_key_for
        configs = {"pure_read": TrafficMix(100, 0),
                   "balanced": TrafficMix(50, 50)}
        fracs = np.linspace(0.0, 1.0, 5)
        space = DesignSpace([
            axis("workload_config", configs),
            axis("mix", [OWN_MIX] + [(100.0 * r, 100.0 - 100.0 * r)
                                     for r in fracs]),
            axis("shoreline_mm", [4.0, 8.0]),
        ])
        res = space.evaluate()
        per = flitsim.backlog_knees(
            mixes=[(m.x, m.y) for m in configs.values()], per_mix=True)
        budget = float(per["cxl_opt"][0])
        cons = SelectionConstraints(max_backlog_knee=budget)
        front = res.frontier("bandwidth_gbs", where=res.feasible(cons))

        # legacy path: grid_ranking with the hand-built [S, C, 1, 1] mask
        items = default_catalog_items()
        grid = CatalogGrid(
            keys=res["bandwidth_gbs"].coord("system"),
            bandwidth_gbs=res["bandwidth_gbs"].values,
            pj_per_bit=res["pj_per_bit"].values,
            power_w=res["power_w"].values,
            gbs_per_watt=res["gbs_per_watt"].values,
            latency_ns=res["latency_ns"].values,
            relative_bit_cost=res["relative_bit_cost"].values)
        valid = np.ones((len(items), len(configs), 1, 1), dtype=bool)
        for i, (key, _) in enumerate(items):
            sim = sim_key_for(key)
            if sim is not None:
                valid[i, :, 0, 0] = per[sim] <= budget
        g = grid_ranking(items, grid, SelectionConstraints(),
                         objective="bandwidth", valid_mask=valid)
        np.testing.assert_array_equal(front.values, g.best_keys())

    def test_typo_dim_still_rejected(self, res):
        with pytest.raises(KeyError, match="not present on any array"):
            res.sel(backlogs=64.0)


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestArtifactConsumersSkipNewDims:
    """Per-cell artifact consumers must SKIP aggregate/axes-first exports
    (phy / catalog_param dimensions) instead of crashing."""

    CELL = {"arch": "a", "shape": "s", "mesh": "16x16", "chips": 256,
            "compile_s": 1.0, "num_microbatches": 4,
            "memory_analysis": {"argument_size_in_bytes": 1e9,
                                "temp_size_in_bytes": 1e9},
            "roofline": {"hlo_flops_per_chip": 1e12, "compute_s": 1.0,
                         "memory_s": 2.0, "collective_s": 0.5,
                         "dominant": "memory", "useful_flops_ratio": 0.5},
            "memsys_bridge": {"mix": "70R30W", "read_fraction": 0.7,
                              "hbm_baseline_memory_s": 2.0, "systems": {}}}
    PHY_EXPORT = {"arch": "x", "shape": "s", "mesh": "m",
                  "roofline": {}, "axes": ["phy", "read_fraction"]}
    AGGREGATE = {"keys": [], "workloads": {}}

    def test_is_cell_artifact_predicate(self):
        from repro.roofline.analysis import is_cell_artifact
        assert is_cell_artifact(self.CELL)
        assert not is_cell_artifact(self.PHY_EXPORT)
        assert not is_cell_artifact(self.AGGREGATE)
        assert not is_cell_artifact(
            {**self.CELL, "axes": ["catalog_param"]})
        assert not is_cell_artifact([1, 2, 3])

    def _write_artifacts(self, d):
        os.makedirs(d, exist_ok=True)
        for fname, payload in (("cell.json", self.CELL),
                               ("phy_export.json", self.PHY_EXPORT),
                               ("design_space.json", self.AGGREGATE),
                               ("broken.json", None)):
            with open(os.path.join(d, fname), "w") as f:
                if payload is None:
                    f.write("{not json")
                else:
                    json.dump(payload, f)

    def test_make_experiments_tables_skips(self, tmp_path, monkeypatch):
        mod = _load_module(
            os.path.join(REPO, "tools", "make_experiments_tables.py"),
            "make_experiments_tables")
        self._write_artifacts(str(tmp_path / "experiments" / "dryrun"))
        monkeypatch.setattr(mod, "ROOT", str(tmp_path))
        cells = mod.load("dryrun")
        assert list(cells) == [("a", "s", "16x16")]
        # and the table renders from the surviving cell without crashing
        assert "| a | s |" in mod.dryrun_table(cells, "16x16")

    def test_explorer_cell_files_skip(self, tmp_path):
        mod = _load_module(
            os.path.join(REPO, "examples", "memsys_explorer.py"),
            "memsys_explorer")
        self._write_artifacts(str(tmp_path))
        paths = sorted(str(p) for p in tmp_path.iterdir())
        files = [f for f, _ in mod._cell_artifacts(paths)]
        assert [os.path.basename(f) for f in files] == ["cell.json"]


class TestSummaryTool:
    def test_summary_is_drift_stable_fields_only(self):
        mod = _load_module(
            os.path.join(REPO, "tools", "design_space_summary.py"),
            "design_space_summary")
        ds = {"keys": ["A", "B"], "objective": "bandwidth",
              "shorelines": [4.0, 8.0],
              "workloads": {"w": {
                  "mix": "70R30W", "best": "A", "feasible": True,
                  "crossovers": [
                      {"read_fraction_lo": 0.0, "read_fraction_hi": 0.6,
                       "best": "A"},
                      {"read_fraction_lo": 0.6, "read_fraction_hi": 1.0,
                       "best": "B"}],
                  "shoreline_frontier": {"4mm": "A", "8mm": "A"},
                  "shoreline_sensitive": False}},
              "joint_frontier": {
                  "keys": ["A", "B"],
                  "disagreement_regions": [
                      {"backlog": 2.0, "analytic_best": "A",
                       "simulated_best": "B"}]},
              "phy_frontier": {
                  "phys": ["P1"], "best_approach_by_phy": {"P1": "A"},
                  "regimes_by_phy": {"P1": [{"best": "A"}]}}}
        out = mod.summarize(ds)
        w = out["workloads"]["w"]
        assert w["crossover_winners"] == ["A", "B"]
        assert w["crossover_count"] == 2
        assert out["joint_frontier"]["disagreement_region_count"] == 1
        assert out["joint_frontier"]["disagreeing_backlogs"] == [2.0]
        assert out["phy_frontier"]["regime_winners_by_phy"] == {"P1": ["A"]}
        # no floating-point METRICS leak into the gate (grid coordinates
        # like shorelines/backlogs are exact, version-independent inputs)
        assert "read_fractions" not in out
        assert "disagreement_fraction" not in str(out)
