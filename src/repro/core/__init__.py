"""repro.core — the paper's models behind one axes-first design-space API.

The primary contribution: analytical models of on-package memory over UCIe
(approaches A-E), incumbent-bus baselines, latency/power/cost models, and a
flit-level discrete-event simulator that validates the closed forms.

The design-space surface is AXES-FIRST (:mod:`repro.core.space`): declare
named axes — ``phy``, ``read_fraction`` / ``mix``, ``backlog``,
``shoreline_mm``, ``workload_config``, ``protocol``, ``protocol_param``,
``catalog_param``, and the pipelining axes ``k`` / ``ucie_line_ui`` /
``device_line_ui`` — and a :class:`DesignSpace` lowers any combination
onto the batched engines through ONE shared shape-keyed compile cache,
returning a named-axis :class:`SpaceResult` with ``sel()`` /
``frontier()`` / ``argbest()`` queries and a first-class
``feasible(constraints)`` mask composable via ``where=``:

    from repro.core import DesignSpace, SelectionConstraints, axis
    from repro.core import UCIE_A_32G_55U, UCIE_S_32G, UCIE_A_48G_45U
    res = DesignSpace([
        axis("phy", [UCIE_A_32G_55U, UCIE_S_32G, UCIE_A_48G_45U]),
        axis("read_fraction", [0.0, 0.5, 1.0]),
        axis("shoreline_mm", [4.0, 8.0]),
    ]).evaluate()
    res["bandwidth_gbs"].argbest("system")      # frontier labels
    mask = res.feasible(SelectionConstraints(max_relative_bit_cost=2.0))
    res.frontier("bandwidth_gbs", where=mask)   # feasible-set winners

Flit-simulated metrics run under a :class:`repro.core.space.SimConfig`
(``sim=`` on ``DesignSpace`` and every legacy wrapper).  Migration table
— pick the row matching what you need; every row shares the same compile
cache and the same report numerics:

    ==================  =========================================  =======
    config              engine / guarantee                         use for
    ==================  =========================================  =======
    ``FIXED_SIM``       full-horizon XLA scan; bit-identical to    goldens,
    (default)           the seed goldens                           CI gates
    ``ADAPTIVE_SIM``    period-exact probes, then chunked XLA      sweeps
                        cores with batched early exit; <= 1e-3
                        deviation, several-x fewer sequential
                        cycles
    ``SimConfig(        trace-scan cores for the ``trace`` axis:   serving
    trace_cycles=C)``   C cycles per phase, state carried across   traces
                        phase boundaries; ``None`` = full horizon
                        per phase (single phase bit-identical to
                        the static cell)
    ``StreamConfig(     STREAMING shards: chunk the cell space,    10^6 -
    chunk_cells=...,    one cached executable per chunk shape      10^8
    devices=N)`` via    (``STREAM_FAMILIES``), ``shard_map`` the   cell
    ``evaluate(...,     chunk batch across N devices, reduce       joint
    stream=cfg)``       frontier/argbest/feasibility on-device —   spaces
                        per-cell tensors never materialize; winner
                        labels bit-identical to the materialized
                        engine (``FIXED_SIM`` cores)
    ==================  =========================================  =======

Streaming keeps peak memory at ``chunk_cells x stacked-protocol rows``
regardless of space size: each dispatch carries running argmax codes,
per-label win counts, and the running best value; constraints stream
through the same reduction (``StreamConfig(constraints=...)``, with
``"(none)"`` cells counted).  See ``docs/streaming.md`` for chunking
semantics and the reduction contracts.

The five frontier builders (``SpaceResult.frontier``,
:func:`joint_frontier` — which now folds the PHY-absolute
``sim_bandwidth_gbs`` subsection — the explorer's phy / sim-phy
frontier reports, and :meth:`DesignSpace.serving_frontier`) converge on
ONE report API: :meth:`DesignSpace.report` /
:func:`repro.core.report.build_report` resolve a
:class:`~repro.core.report.ReportSpec` into typed
:class:`~repro.core.report.FrontierReport` sections (``"frontier"``,
``"joint"``, ``"phy"``, ``"sim_phy"``, ``"serving"``) whose payloads
are byte-identical to the legacy ``design_space.json`` sections.

Time-varying serving traffic rides the ``trace`` axis
(:mod:`repro.traces`): a :class:`~repro.traces.trace.TrafficTrace` is a
sequence of (duration, read_fraction, backlog) phases — recorded live
from :class:`repro.serve.engine.ServingEngine` via
:class:`~repro.traces.recorder.TraceRecorder`, or synthesized from model
config shapes alone (no weights) by
:func:`~repro.traces.synthetic.synthetic_serving_trace`.  Trace cells
run through dedicated trace-scan simulator cores that CARRY queue and
credit state across phase boundaries (a warm phase 2 differs from a cold
steady-state run — that is the point), report duration-weighted
``trace_efficiency`` / per-phase ``trace_phase_efficiency`` /
PHY-absolute ``trace_bandwidth_gbs``, and share the same shape-keyed
compile cache (trace VALUES are traced, so same-shaped trace sets reuse
warm executables).  A single-phase trace is bit-identical to the static
(mix, backlog) cell.  :meth:`DesignSpace.serving_frontier` maps the
winning protocol per (model, QPS) point to its catalog approach — the
``serving_frontier`` section of the CI design-space artifact.

``flitsim.last_run_info()`` reports per-family telemetry for the last
adaptive run: ``engine``, ``launches``, ``cycles_run``, ``elapsed_s``,
and the detected-period histogram when a periodic probe closed the
run.  Trace-scan runs report
under ``<family>.trace`` with ``phases``, ``cycles_per_phase``, and
``state_carry_depth`` instead.

The positional legacy front-ends (``flitsim.sweep`` /
``sweep_pipelining``, ``memsys.catalog_grid``, ``selector.rank_grid``)
were RETIRED in PR 10 after warning since PR 9; the migration table in
:mod:`repro.core.space` maps each retired idiom to its axes-first
replacement, and the engines live on as the private ``_*_impl``
functions the unified API lowers onto (identical numerics, shared warm
executables).

``flitsim.last_run_info()["stream.sim" / "stream.catalog"]`` reports the
streaming engine's async dispatch telemetry — ``dispatches``,
``prefetch`` (bounded in-flight depth), ``pad_cells``, and
``overlap_frac`` (marshal time overlapped with in-flight device work).
:func:`joint_frontier` is the first capability only the unified API can
express: the (mix x backlog x shoreline) frontier marking where the flit
simulation and the closed forms disagree about the best memory system.
"""
from repro.core.ucie import (
    UCIePhy, Packaging, UCIE_S_32G, UCIE_A_32G_55U, UCIE_A_32G_45U,
    UCIE_S_48G_110U, UCIE_A_48G_45U, PERTURBABLE_PHY_FIELDS,
    IDLE_POWER_FRACTION, table1,
)
from repro.core.traffic import TrafficMix, PAPER_MIXES, mix_grid, mixes_named
from repro.core.protocols import (
    MemoryProtocol, APPROACH_A, APPROACH_A_NATIVE, APPROACH_B, APPROACH_C,
    APPROACH_D, APPROACH_E, ALL_APPROACHES, BASELINES,
    LPDDR5, LPDDR6, HBM3, HBM4,
)
from repro.core.latency import (
    UCIeMemoryLatency, MEASURED_FRONTEND_LATENCY_NS, latency_speedup,
)
from repro.core.space import (
    ADAPTIVE_SIM, Axis, AxisSet, DesignSpace, FIXED_SIM, OWN_MIX,
    STREAM_FAMILIES, SimConfig, SpaceArray, SpaceResult, StreamConfig,
    axis, cache_stats, clear_cache, joint_frontier, regimes,
)
from repro.core.report import FrontierReport, ReportSpec, build_report
from repro.core.streaming import StreamResult
from repro.core.memsys import (
    CatalogGrid, MemorySystem, grid_cache_stats, standard_catalog,
)
from repro.core.selector import (
    GridRanking, RankedSystem, SelectionConstraints, best, rank,
)
from repro.core import cost, flitsim, space
