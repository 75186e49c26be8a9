"""Axes-first design-space API over one shared batched engine.

The paper's headline claims come from sweeping protocol, PHY, traffic mix,
backlog and shoreline dimensions *jointly*.  This module is the single
front door to those sweeps:

  * :func:`axis` / :class:`Axis` / :class:`AxisSet` — a declarative spec of
    named design-space axes (``phy``, ``read_fraction``, ``mix``,
    ``backlog``, ``shoreline_mm``, ``workload_config``, ``protocol``,
    ``protocol_param``, ``catalog_param``, and the pipelining axes ``k`` /
    ``ucie_line_ui`` / ``device_line_ui``).
  * :class:`DesignSpace` — lowers any requested axis combination onto the
    existing batched ``lax.scan``/``vmap`` cores (flit simulators, analytic
    catalog, Fig-13 pipelining) through one shared shape-keyed compile
    cache, so the full joint space resolves in one compiled program per
    engine family.
  * :class:`SpaceResult` / :class:`SpaceArray` — named-axis outputs with
    label coordinates and ``sel()`` / ``isel()`` / ``argbest()`` /
    ``frontier()`` queries, replacing the four bespoke result dataclasses
    the legacy front-ends returned.
  * :func:`joint_frontier` — the first capability only expressible here:
    the joint (mix x backlog x shoreline) frontier that merges the
    flit-simulated efficiency grid with the analytic catalog grid and
    reports where simulation and the closed forms disagree.

The deprecated positional front-ends (``flitsim.sweep`` /
``sweep_pipelining``, ``memsys.catalog_grid``, ``selector.rank_grid``)
were retired in PR 10 after a deprecation cycle; their engines live on
as the private ``_sweep_impl`` / ``_sweep_pipelining_impl`` /
``_catalog_grid_impl`` / ``_rank_grid_impl`` functions this module
lowers onto, sharing the cache below — the migration table further down
maps each retired idiom to its axes-first replacement.

Shared compile cache
--------------------
Every batched engine memoizes its compiled executable here, keyed on
``(family, *static_key)`` where the static key encodes the catalog / param
stack and every grid shape and static length.  ``cache_stats()`` exposes
hit/miss counters globally or per family — one miss == one trace+compile;
tests assert the full joint space compiles exactly once per engine family
and that the ``_*_impl`` engines run warm against a space-primed cache.

Migration: PHY sweeps and feasibility masking
---------------------------------------------
The PHY is a first-class ``phy`` axis and feasibility is a first-class
mask; the pre-axis idioms map onto them as follows:

=====================================================  ======================
legacy idiom                                           axes-first equivalent
=====================================================  ======================
``approach_grid(phy, x, y).linear``                    ``DesignSpace([axis("phy", [phy]), axis("mix", ...)]).evaluate()`` →
                                                       ``res["linear_density_gbs_mm"].sel(phy=phy.name)``
two ``approach_grid`` calls (UCIe-A, UCIe-S)           one ``axis("phy", [UCIE_A_32G_55U, UCIE_S_32G, UCIE_A_48G_45U, ...])``
catalog keys ``"E:cxl-mem-opt/UCIe-A"``                system ``"E:cxl-mem-opt"`` x phy coordinate ``"UCIe-A-32G-55u"``
``rank_grid(x, y, constraints).best_keys()``           ``mask = res.feasible(constraints)`` then
                                                       ``res.frontier("bandwidth_gbs", where=mask)``
``grid_ranking(..., valid_mask=...)`` (bridge)         ``res.feasible(constraints)`` — the backlog-knee budget follows the
                                                       ``workload_config`` axis automatically
``flitsim.sweep_perturbed({field: scale})``            ``axis("protocol_param", [...])`` (flit params) /
                                                       ``axis("catalog_param", [...])`` (PHY pJ/b + densities)
``flitsim.sweep(mixes, backlogs)``                     ``DesignSpace([axis("backlog", ...), axis("mix", ...)],
                                                       sim=...).evaluate(metrics=("sim_efficiency",))``
``flitsim.sweep_pipelining(ks, ...)``                  ``axis("k", ks)`` [x ``axis("ucie_line_ui", ...)`` x
                                                       ``axis("device_line_ui", ...)``] → ``res["utilization"]``
``memsys.catalog_grid(x, y, shorelines)``              ``axis("read_fraction", ...)`` [x ``axis("shoreline_mm",
                                                       ...)``] → ``res["bandwidth_gbs"]`` etc.
whole-space materialize at 10^6+ cells                 ``evaluate(metrics=(m,), stream=StreamConfig(...))`` —
                                                       streamed chunks, running on-device frontier reductions
                                                       (:mod:`repro.core.streaming`)
explorer ``phy_frontier_report()`` / ``joint_frontier  ``space.report(ReportSpec(sections=...))`` /
(...)`` / ``serving_frontier(...)`` call sites         :func:`repro.core.report.build_report` — typed
                                                       ``FrontierReport`` sections, one API
=====================================================  ======================

Feasible-set masks are plain boolean :class:`SpaceArray` values:
``res.feasible(constraints)`` composes with ANY axis combination, and
``sel()`` / ``argbest()`` / ``frontier()`` accept them via ``where=``
(masked-out cells become NaN under ``sel``, are excluded from ``argbest``
/ ``frontier``, and grid points with no admissible system read
``"(none)"``, matching ``GridRanking.best_keys()``).

Simulation execution config (:class:`SimConfig`)
------------------------------------------------
The flit simulators run in one of two modes, selected by a
:class:`SimConfig` threaded through ``DesignSpace(sim=...)`` /
``evaluate(sim=...)`` and every engine entry point (``_sweep_impl``,
``backlog_knees``, ``joint_frontier``, ``bridge_design_space``):

* ``mode="fixed"`` (default) — the full fixed-horizon ``lax.scan``
  (n_flits=2048 / n_accesses=4096 / n_lines=512), bit-identical to the
  pre-config engine.  All pinned goldens are produced in this mode.
* ``mode="adaptive"`` — chunked ``lax.while_loop`` cores with batched
  early exit: the whole vmapped grid stops as soon as every cell's
  reconstructed fixed-window estimate has converged (see
  :mod:`repro.core.flitsim` for the algorithm).  Deviates from fixed by
  <= ``tol``-scale amounts while cutting the sequential depth several-x.

The config participates in the shared compile-cache key
(:meth:`SimConfig.key`), so switching between configs never invalidates
warm executables of other configs — each (family, grid shape, config)
triple compiles once and stays warm.
"""
from __future__ import annotations

import dataclasses
from typing import (
    Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple, Union,
)

import jax
import numpy as np

# =========================================================================
# Shared shape-keyed compile cache
# =========================================================================

#: cache families owned by the flit-simulation engine
FLITSIM_FAMILIES: Tuple[str, ...] = (
    "flitsim.symmetric", "flitsim.asymmetric", "flitsim.pipelining")
#: cache families owned by the analytic memory-system engine
MEMSYS_FAMILIES: Tuple[str, ...] = ("memsys.catalog", "memsys.approach")
#: cache families owned by the streaming chunk engine
#: (:mod:`repro.core.streaming`): ONE executable per chunk shape, reused
#: across every chunk and every dispatch of a streamed evaluation
STREAM_FAMILIES: Tuple[str, ...] = ("stream.sim", "stream.catalog")
#: every registered engine family — ``cache_stats(families=...)``
#: validates against this set (plus any ad-hoc family already counted)
KNOWN_FAMILIES: Tuple[str, ...] = (
    FLITSIM_FAMILIES + MEMSYS_FAMILIES + STREAM_FAMILIES)


@dataclasses.dataclass
class CacheStats:
    """Compile-cache counters: one miss == one trace+compile."""

    hits: int = 0
    misses: int = 0


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Execution config for the flit-simulation engines.

    ``mode="fixed"`` runs the full fixed-horizon ``lax.scan`` — bit-identical
    to the pre-config engine and to every pinned golden.  ``mode="adaptive"``
    runs the chunked early-exit cores: a ``lax.while_loop`` over chunks of
    ``chunk`` cycles (inner ``lax.scan`` with ``unroll=``) that stops as
    soon as every grid cell's reconstructed fixed-window estimate is stable
    to within ``tol`` (relative), or the horizon is hit.

    ``max_cycles`` overrides the per-family horizon (defaults: the caller's
    ``n_flits`` / ``n_accesses`` / ``n_lines``); ``chunk`` is shrunk per
    family to an exact divisor of the horizon (>= 8 chunks per run).  The
    config participates in the shared compile-cache key (:meth:`key`), so
    alternating configs never invalidates other configs' warm executables.

    ``engine`` picks the adaptive execution backend: ``"xla"`` (default)
    runs the chunked ``lax.while_loop`` cores; ``"pallas"`` runs the fused
    single-launch-per-chunk Pallas kernels from
    :mod:`repro.kernels.flit_sim` (``interpret=True`` off-TPU, real
    lowering on TPU).  The fixed mode is engine-independent by design —
    it must stay bit-identical to every pinned golden — so
    ``engine="pallas"`` requires ``mode="adaptive"``.
    """

    mode: str = "fixed"
    chunk: int = 128
    unroll: int = 4
    tol: float = 1e-3
    max_cycles: Optional[int] = None
    engine: str = "xla"
    #: cycles simulated per trace PHASE (``trace``-axis evaluations only);
    #: ``None`` uses the family's static horizon, which makes a default
    #: single-phase trace bit-identical to its static (mix, backlog) cell
    trace_cycles: Optional[int] = None

    def __post_init__(self):
        if self.mode not in ("fixed", "adaptive"):
            raise ValueError(f"SimConfig.mode must be 'fixed' or "
                             f"'adaptive', got {self.mode!r}")
        if self.engine not in ("xla", "pallas"):
            raise ValueError(f"SimConfig.engine must be 'xla' or "
                             f"'pallas', got {self.engine!r}")
        if self.engine == "pallas" and self.mode != "adaptive":
            raise ValueError(
                "SimConfig(engine='pallas') requires mode='adaptive': the "
                "fixed mode is pinned bit-identical to the golden numerics "
                "and always runs the XLA scan core")
        if int(self.chunk) < 8:
            raise ValueError(f"SimConfig.chunk must be >= 8, got "
                             f"{self.chunk}")
        if int(self.unroll) < 1:
            raise ValueError(f"SimConfig.unroll must be >= 1, got "
                             f"{self.unroll}")
        if not self.tol > 0.0:
            raise ValueError(f"SimConfig.tol must be > 0, got {self.tol}")
        if self.max_cycles is not None and int(self.max_cycles) < 1:
            raise ValueError(f"SimConfig.max_cycles must be >= 1, got "
                             f"{self.max_cycles}")
        if self.trace_cycles is not None and int(self.trace_cycles) < 8:
            raise ValueError(f"SimConfig.trace_cycles must be >= 8, got "
                             f"{self.trace_cycles}")

    def horizon(self, default: int) -> int:
        """Resolved horizon for a family whose fixed length is ``default``.

        The adaptive runner shrinks ``chunk`` to an exact divisor of the
        horizon (at least 8 chunks per run) so the chunked loop can always
        reproduce the fixed window exactly at full depth.
        """
        return int(self.max_cycles) if self.max_cycles is not None \
            else int(default)

    def key(self) -> Tuple:
        """Static cache-key component — distinct configs get distinct
        compiled executables; re-using a config re-uses its executable.

        ``trace_cycles`` appends only when set, keeping the default keys
        (and every golden pinned on them) unchanged."""
        trace = () if self.trace_cycles is None \
            else (int(self.trace_cycles),)
        if self.mode == "fixed":
            return ("fixed",) + trace
        return ("adaptive", int(self.chunk), int(self.unroll),
                float(self.tol), self.max_cycles, self.engine) + trace


#: the default config: bit-identical fixed-horizon simulation
FIXED_SIM = SimConfig()
#: convergence-adaptive early-exit simulation (benchmarks / explorer
#: default; <= tol-scale deviation from FIXED_SIM)
ADAPTIVE_SIM = SimConfig(mode="adaptive")
#: convergence-adaptive simulation on the fused Pallas kernels — one
#: launch per chunk instead of ~chunk dispatched ops (<= tol-scale
#: deviation from FIXED_SIM, same gate as ADAPTIVE_SIM)
PALLAS_SIM = SimConfig(mode="adaptive", engine="pallas")


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Execution config for the tiled/streaming evaluation mode.

    ``DesignSpace.evaluate(..., stream=StreamConfig(...))`` switches from
    the materialized engines to the streaming engine
    (:mod:`repro.core.streaming`): the cell space is flattened along
    ``axis_order``, cut into chunks of at most ``chunk_cells`` cells per
    device, and every chunk runs through ONE cached executable that is
    ``shard_map``-ped over ``devices`` devices.  Frontier / argbest /
    feasibility resolve as running on-device reductions, so full per-cell
    metric tensors never exist on host or device — only the reduced
    winner codes (one small integer per cell) come back.

    * ``chunk_cells`` — the per-device, per-dispatch cell budget (the
      peak number of cells resident at once, asserted by the streaming
      benchmarks).  Clamped down when the space is smaller.
    * ``axis_order`` — the chunked cell-axis order (default: canonical
      :data:`AXIS_ORDER`).  Must be a permutation of the space's cell
      axes; it changes the dispatch order only, never the result.
    * ``devices`` — shard width (default: every local device; CPU runs
      expose more via ``XLA_FLAGS=--xla_force_host_platform_device_count``).
    * ``mode`` — argbest direction; ``None`` picks the metric's natural
      direction (``min`` for ``pj_per_bit`` / ``power_w``, else ``max``).
    * ``constraints`` — optional
      :class:`repro.core.selector.SelectionConstraints` folded into the
      on-device reduction for analytic metrics (cells with no admissible
      system read ``"(none)"``, matching the materialized frontier).
    * ``prefetch`` — bounded in-flight dispatch depth of the async
      double-buffered loop: the host marshals chunk ``t+1``'s cell
      indices (pure numpy) while up to ``prefetch`` earlier chunks are
      still executing on the device, and retires results strictly FIFO
      so the running reductions fold in the SAME order as the
      sequential loop (``prefetch=1``) — winners stay bit-identical at
      every depth.
    """

    chunk_cells: int = 4096
    axis_order: Optional[Tuple[str, ...]] = None
    devices: Optional[int] = None
    mode: Optional[str] = None
    constraints: Any = None
    prefetch: int = 2

    def __post_init__(self):
        if int(self.chunk_cells) < 1:
            raise ValueError(f"StreamConfig.chunk_cells must be >= 1, got "
                             f"{self.chunk_cells}")
        if int(self.prefetch) < 1:
            raise ValueError(f"StreamConfig.prefetch must be >= 1, got "
                             f"{self.prefetch}")
        if self.devices is not None and int(self.devices) < 1:
            raise ValueError(f"StreamConfig.devices must be >= 1, got "
                             f"{self.devices}")
        if self.mode not in (None, "max", "min"):
            raise ValueError(f"StreamConfig.mode must be None, 'max' or "
                             f"'min', got {self.mode!r}")
        if self.axis_order is not None:
            object.__setattr__(self, "axis_order",
                               tuple(str(a) for a in self.axis_order))

    def key(self) -> Tuple:
        """Static cache-key component (constraint VALUES are traced
        inputs, so changing a threshold reuses the warm executable; the
        constraint STRUCTURE — which checks are active — is static)."""
        cons = self.constraints
        cons_key = None if cons is None else (
            cons.packaging, cons.max_relative_bit_cost is not None,
            cons.max_backlog_knee is not None,
            cons.max_power_w is not None,
            cons.required_bandwidth_gbs is not None)
        return (int(self.chunk_cells), self.axis_order,
                None if self.devices is None else int(self.devices),
                self.mode, int(self.prefetch), cons_key)


_PROGRAMS: Dict[Tuple, Any] = {}
_FAMILY_STATS: Dict[str, CacheStats] = {}
#: executables retained per engine family; oldest-inserted evicted beyond
#: this (an interactive loop minting fresh catalogs/shapes must not pin
#: every compiled program forever)
MAX_PROGRAMS_PER_FAMILY = 32


def cached_program(family: str, key: Tuple, build_fn: Callable,
                   example_args: Tuple):
    """Return a compiled executable for ``build_fn`` memoized on
    ``(family, *key)``.

    The program is compiled ahead of time (``lower().compile()``), so a
    program the backend's compiler refuses raises here, at its first
    request.  A second identically-keyed request is a cache hit and runs
    the warm executable with zero retracing.  Each family keeps
    at most :data:`MAX_PROGRAMS_PER_FAMILY` executables (FIFO eviction).
    """
    stats = _FAMILY_STATS.setdefault(family, CacheStats())
    full_key = (family,) + tuple(key)
    entry = _PROGRAMS.get(full_key)
    if entry is not None:
        stats.hits += 1
        return entry
    stats.misses += 1
    entry = jax.jit(build_fn).lower(*example_args).compile()
    family_keys = [k for k in _PROGRAMS if k[0] == family]
    if len(family_keys) >= MAX_PROGRAMS_PER_FAMILY:
        del _PROGRAMS[family_keys[0]]        # dict order == insertion order
    _PROGRAMS[full_key] = entry
    return entry


def cache_stats(families: Optional[Sequence[str]] = None) -> CacheStats:
    """Aggregate hit/miss counters, optionally restricted to ``families``.

    Unknown family names raise ``KeyError`` — they used to aggregate
    nothing, so a typo like ``"flitsim.symetric"`` silently reported zero
    compiles instead of failing the assertion that cited it.
    """
    if families is not None:
        known = set(KNOWN_FAMILIES) | set(_FAMILY_STATS)
        bad = sorted(set(families) - known)
        if bad:
            raise KeyError(f"unknown cache families {bad}; choose from "
                           f"{sorted(known)}")
    out = CacheStats()
    for fam, st in _FAMILY_STATS.items():
        if families is None or fam in families:
            out.hits += st.hits
            out.misses += st.misses
    return out


def programs(families: Sequence[str]) -> Dict[Tuple, Any]:
    """The cached executables of ``families``, keyed ``(family, *key)``
    (what ``compiled.as_text()`` shows is what the device runs)."""
    return {k: v for k, v in _PROGRAMS.items() if k[0] in families}


def clear_cache(families: Optional[Sequence[str]] = None) -> None:
    """Drop cached executables (all, or only ``families``) and reset the
    matching counters."""
    for key in list(_PROGRAMS):
        if families is None or key[0] in families:
            del _PROGRAMS[key]
    for fam in list(_FAMILY_STATS):
        if families is None or fam in families:
            del _FAMILY_STATS[fam]


# =========================================================================
# Axes
# =========================================================================

#: sentinel mix value: resolve to each workload config's own HLO-derived mix
OWN_MIX = "own"

#: canonical axis order — result dims always follow this order (with the
#: implicit ``system`` / ``protocol`` / ``approach`` dims leading; the
#: ``phy`` axis trails the stack dim, mirroring how ``protocol`` leads
#: ``backlog``)
AXIS_ORDER: Tuple[str, ...] = (
    "catalog_param", "phy", "protocol_param", "protocol", "backlog",
    "trace", "workload_config", "mix", "read_fraction", "shoreline_mm",
    "k", "ucie_line_ui", "device_line_ui")

_MIX_LIKE = ("mix", "read_fraction")


def _mix_label(x: float, y: float) -> str:
    return f"{x:g}R{y:g}W"


def _as_mix_tuple(v) -> Tuple[float, float]:
    if hasattr(v, "x") and hasattr(v, "y"):         # TrafficMix
        x, y = float(v.x), float(v.y)
    else:
        x, y = v
        x, y = float(x), float(y)
    if x < 0 or y < 0 or x + y <= 0:
        raise ValueError(f"invalid traffic mix x={x} y={y}: need x, y >= 0 "
                         "and x + y > 0")
    return x, y


def _as_workload(v) -> Tuple[str, Any]:
    """Normalize a workload_config entry to (name, TrafficMix)."""
    from repro.core.traffic import TrafficMix
    name, w = v
    if hasattr(w, "read_bytes_per_chip"):           # RooflineReport-like
        w = TrafficMix.from_bytes(w.read_bytes_per_chip,
                                  w.write_bytes_per_chip)
    elif not (hasattr(w, "x") and hasattr(w, "y")):
        x, y = _as_mix_tuple(w)
        w = TrafficMix(x, y)
    return str(name), w


def _as_perturbation(v) -> Tuple[str, Tuple[Tuple[str, float], ...]]:
    """Normalize a protocol_param entry to (label, sorted field->scale)."""
    if isinstance(v, Mapping):
        label, pert = None, v
    else:
        label, pert = v
    items = tuple(sorted((str(k), float(s)) for k, s in pert.items()))
    if label is None:
        # "+"-joined (not ","): labels land in CSV benchmark columns
        label = "+".join(f"{k}x{s:g}" for k, s in items) or "baseline"
    return str(label), items


@dataclasses.dataclass(frozen=True)
class Axis:
    """One named design-space axis: canonical values plus display labels."""

    name: str
    values: Tuple[Any, ...]
    labels: Tuple[Any, ...]

    def __len__(self) -> int:
        return len(self.values)

    def index(self, label) -> int:
        """Position of ``label`` (accepts raw values for mix-like axes and
        ``UCIePhy`` objects for the ``phy`` axis)."""
        if label in self.labels:
            return self.labels.index(label)
        if self.name == "phy" and label in self.values:
            return self.values.index(label)
        if self.name == "mix" and label != OWN_MIX:
            return self.labels.index(_mix_label(*_as_mix_tuple(label)))
        if self.name in ("backlog", "shoreline_mm", "read_fraction",
                         "ucie_line_ui", "device_line_ui"):
            return self.labels.index(float(label))
        if self.name == "k":
            return self.labels.index(int(label))
        raise KeyError(f"label {label!r} not on axis {self.name!r}: "
                       f"{self.labels}")


def axis(name: str, values: Sequence[Any],
         labels: Optional[Sequence[Any]] = None) -> Axis:
    """Build a validated :class:`Axis`; values are normalized per axis kind.

    ``mix`` accepts ``(x, y)`` tuples, ``TrafficMix`` objects, or the
    :data:`OWN_MIX` sentinel (resolved per ``workload_config``).
    ``workload_config`` accepts a mapping or ``(name, mix-or-report)``
    pairs.  ``protocol_param`` accepts ``{field: scale}`` dicts or
    ``(label, dict)`` pairs — multiplicative perturbations applied to the
    flit-simulator parameter stacks; ``catalog_param`` is its analytic
    twin (PHY pJ/b and shoreline/areal density scales).  ``phy`` accepts
    :class:`repro.core.ucie.UCIePhy` instances (labels: their names).
    """
    vals = list(values.items()) if isinstance(values, Mapping) else \
        list(values)
    if not vals:
        raise ValueError(f"axis {name!r} needs at least one value")
    if name == "phy":
        from repro.core.ucie import UCIePhy
        bad = [v for v in vals if not isinstance(v, UCIePhy)]
        if bad:
            raise ValueError(f"axis 'phy' values must be UCIePhy "
                             f"instances, got {bad}")
        norm = list(vals)
        labs = [p.name for p in vals]
        if len(set(labs)) != len(labs):
            raise ValueError(f"duplicate phy names on the axis: {labs}")
    elif name == "catalog_param":
        from repro.core.ucie import PERTURBABLE_PHY_FIELDS
        norm = [_as_perturbation(v) for v in vals]
        for _, items in norm:
            unknown = sorted(k for k, _ in items
                             if k not in PERTURBABLE_PHY_FIELDS)
            if unknown:
                raise ValueError(
                    f"unknown catalog perturbation fields {unknown}; "
                    f"choose from {PERTURBABLE_PHY_FIELDS}")
        labs = [lab for lab, _ in norm]
    elif name == "mix":
        norm = [OWN_MIX if (isinstance(v, str) and v == OWN_MIX)
                else _as_mix_tuple(v) for v in vals]
        labs = [OWN_MIX if v == OWN_MIX else _mix_label(*v) for v in norm]
    elif name == "read_fraction":
        norm = [float(v) for v in vals]
        for r in norm:
            if not 0.0 <= r <= 1.0:
                raise ValueError(f"read_fraction {r} outside [0, 1]")
        labs = list(norm)
    elif name == "workload_config":
        norm = [_as_workload(v) for v in vals]
        labs = [n for n, _ in norm]
    elif name == "protocol":
        norm = [str(v) for v in vals]
        labs = list(norm)
    elif name == "trace":
        from repro.traces.trace import TrafficTrace, pad_traces
        bad = [v for v in vals if not isinstance(v, TrafficTrace)]
        if bad:
            raise ValueError(f"axis 'trace' values must be TrafficTrace "
                             f"instances, got {bad}")
        # pad to one shared phase count so the whole axis runs as ONE
        # [T, N] grid through one compiled executable
        norm = list(pad_traces(vals))
        labs = [t.name for t in norm]
        if len(set(labs)) != len(labs):
            raise ValueError(f"duplicate trace names on the axis: {labs}")
    elif name == "protocol_param":
        norm = [_as_perturbation(v) for v in vals]
        labs = [lab for lab, _ in norm]
    elif name == "k":
        norm = [int(v) for v in vals]
        labs = list(norm)
    elif name in ("backlog", "shoreline_mm", "ucie_line_ui",
                  "device_line_ui"):
        norm = [float(v) for v in vals]
        labs = list(norm)
    else:
        raise ValueError(f"unknown axis name {name!r}; choose from "
                         f"{AXIS_ORDER}")
    if labels is not None:
        if len(labels) != len(norm):
            raise ValueError(f"axis {name!r}: {len(labels)} labels for "
                             f"{len(norm)} values")
        labs = list(labels)
    return Axis(name=name, values=tuple(norm), labels=tuple(labs))


class AxisSet:
    """Ordered, validated collection of axes (canonical order, unique
    names, ``mix``/``read_fraction`` mutually exclusive)."""

    def __init__(self, *axes: Union[Axis, Sequence[Axis]]):
        flat: List[Axis] = []
        for a in axes:
            if isinstance(a, Axis):
                flat.append(a)
            else:
                flat.extend(a)
        names = [a.name for a in flat]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate axis names in {names}")
        if "mix" in names and "read_fraction" in names:
            raise ValueError("axes 'mix' and 'read_fraction' are mutually "
                             "exclusive — both name the traffic-mix axis")
        if "trace" in names:
            clash = sorted(set(names) & {"backlog", "mix", "read_fraction",
                                         "workload_config"})
            if clash:
                raise ValueError(
                    f"axis 'trace' is exclusive with {clash}: a trace's "
                    "phases already carry the mix and backlog trajectory")
        self._axes: Dict[str, Axis] = {
            name: next(a for a in flat if a.name == name)
            for name in sorted(names, key=AXIS_ORDER.index)}

    def __contains__(self, name: str) -> bool:
        return name in self._axes

    def __getitem__(self, name: str) -> Axis:
        return self._axes[name]

    def __iter__(self):
        return iter(self._axes.values())

    def __len__(self) -> int:
        return len(self._axes)

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(self._axes)

    def get(self, name: str) -> Optional[Axis]:
        return self._axes.get(name)

    def mix_axis(self) -> Optional[Axis]:
        return self._axes.get("mix") or self._axes.get("read_fraction")


# =========================================================================
# Named-axis results
# =========================================================================


def _union_layout(a: "SpaceArray", b: "SpaceArray"
                  ) -> Tuple[Tuple[str, ...], Tuple[Tuple[Any, ...], ...]]:
    """Union of two arrays' named dims (a's order first, b's extras
    appended), with coords reconciled — mismatched labels on a shared dim
    are an error, not a silent broadcast."""
    dims = list(a.dims) + [d for d in b.dims if d not in a.dims]
    coords = []
    for d in dims:
        ca = a.coord(d) if d in a.dims else None
        cb = b.coord(d) if d in b.dims else None
        if ca is not None and cb is not None and ca != cb:
            raise ValueError(f"dim {d!r} has mismatched coords: "
                             f"{ca} vs {cb}")
        coords.append(ca if ca is not None else cb)
    return tuple(dims), tuple(coords)


def _expand_to(dims: Tuple[str, ...], coords, arr: "SpaceArray"
               ) -> np.ndarray:
    """View of ``arr.values`` broadcastable over the ``dims`` layout."""
    unknown = [d for d in arr.dims if d not in dims]
    if unknown:
        raise ValueError(f"dims {unknown} of the operand are not in the "
                         f"target layout {dims}")
    perm = sorted(range(len(arr.dims)),
                  key=lambda i: dims.index(arr.dims[i]))
    v = np.transpose(arr.values, perm)
    shape = tuple(len(coords[j]) if dims[j] in arr.dims else 1
                  for j in range(len(dims)))
    return v.reshape(shape)


def _as_mask(where, like: "SpaceArray") -> "SpaceArray":
    """Normalize a ``where=`` operand to a boolean :class:`SpaceArray`
    (raw arrays are taken over ``like``'s layout)."""
    if isinstance(where, SpaceArray):
        return SpaceArray(where.dims, where.coords,
                          np.asarray(where.values, bool))
    return SpaceArray(like.dims, like.coords,
                      np.broadcast_to(np.asarray(where, bool), like.shape))


@dataclasses.dataclass(frozen=True)
class SpaceArray:
    """A metric array with named dims and label coordinates."""

    dims: Tuple[str, ...]
    coords: Tuple[Tuple[Any, ...], ...]      # labels, aligned with dims
    values: np.ndarray

    def __post_init__(self):
        if len(self.dims) != len(self.coords) or \
                tuple(len(c) for c in self.coords) != self.values.shape:
            raise ValueError(
                f"dims {self.dims} / coords "
                f"{tuple(len(c) for c in self.coords)} do not match value "
                f"shape {self.values.shape}")

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.values.shape

    def coord(self, dim: str) -> Tuple[Any, ...]:
        return self.coords[self.dims.index(dim)]

    def _label_index(self, dim: str, label) -> int:
        labels = self.coord(dim)
        if label in labels:
            return labels.index(label)
        # a UCIePhy (or anything named) selects by its name on a phy dim
        if getattr(label, "name", None) in labels:
            return labels.index(label.name)
        if dim == "mix" and label != OWN_MIX:
            try:
                return labels.index(_mix_label(*_as_mix_tuple(label)))
            except (TypeError, ValueError):
                pass
        try:
            return labels.index(float(label))
        except (TypeError, ValueError):
            raise KeyError(f"label {label!r} not on dim {dim!r}: {labels}")

    def isel(self, **indexers: int) -> "SpaceArray":
        """Integer selection; each selected dim is dropped."""
        out = self.values
        dims, coords = list(self.dims), list(self.coords)
        for dim in sorted(indexers, key=self.dims.index, reverse=True):
            ax = dims.index(dim)
            out = np.take(out, indexers[dim], axis=ax)
            del dims[ax], coords[ax]
        return SpaceArray(tuple(dims), tuple(coords), np.asarray(out))

    def sel(self, *, where=None, **labels) -> "SpaceArray":
        """Label-based selection; each selected dim is dropped.

        ``where`` (a boolean :class:`SpaceArray`, e.g. from
        :meth:`SpaceResult.feasible`, or a raw broadcastable array) masks
        the selected values: cells outside the mask become NaN.  A
        ``SpaceArray`` mask is label-selected alongside the data, so the
        same mask composes with any slicing.
        """
        out = self.isel(**{d: self._label_index(d, v)
                           for d, v in labels.items()})
        if where is None:
            return out
        w = _as_mask(where, self)
        w = w.isel(**{d: w._label_index(d, v) for d, v in labels.items()
                      if d in w.dims})
        dims, coords = _union_layout(out, w)
        if dims != out.dims:
            raise ValueError(
                f"where-mask dims {w.dims} are not a subset of the "
                f"selected array dims {out.dims}")
        wv = np.broadcast_to(_expand_to(dims, coords, w), out.shape)
        return SpaceArray(out.dims, out.coords,
                          np.where(wv, out.values, np.nan))

    def argbest(self, dim: str = "system", mode: str = "max",
                where=None) -> "SpaceArray":
        """Best label along ``dim`` per remaining point.

        ``where`` (boolean :class:`SpaceArray` or broadcastable array)
        restricts the candidates: masked-out entries never win, and points
        where NOTHING is admissible read ``"(none)"`` (the
        ``GridRanking.best_keys()`` sentinel).  A mask carrying extra dims
        (e.g. a per-shoreline feasibility mask applied to a per-system
        latency column) broadcasts the result over them.
        """
        if mode not in ("max", "min"):
            raise ValueError(f"mode must be 'max' or 'min', got {mode!r}")
        if where is None:
            ax = self.dims.index(dim)
            idx = (np.argmax if mode == "max" else np.argmin)(self.values,
                                                              axis=ax)
            labels = np.asarray(self.coord(dim), dtype=object)[idx]
            dims = self.dims[:ax] + self.dims[ax + 1:]
            coords = self.coords[:ax] + self.coords[ax + 1:]
            return SpaceArray(dims, coords, labels)
        w = _as_mask(where, self)
        dims, coords = _union_layout(self, w)
        if dim not in dims:
            raise KeyError(f"dim {dim!r} not in {dims}")
        shape = tuple(len(c) for c in coords)
        vals = np.broadcast_to(_expand_to(dims, coords, self), shape)
        wv = np.broadcast_to(_expand_to(dims, coords, w), shape)
        fill = -np.inf if mode == "max" else np.inf
        masked = np.where(wv, np.asarray(vals, np.float64), fill)
        ax = dims.index(dim)
        idx = (np.argmax if mode == "max" else np.argmin)(masked, axis=ax)
        labels = np.asarray(coords[ax], dtype=object)[idx]
        labels = np.where(wv.any(axis=ax), labels, "(none)")
        return SpaceArray(dims[:ax] + dims[ax + 1:],
                          coords[:ax] + coords[ax + 1:],
                          np.asarray(labels, dtype=object))

    def best(self, dim: str = "system", mode: str = "max",
             where=None) -> "SpaceArray":
        """Best value along ``dim`` per remaining point (NaN where the
        ``where`` mask admits nothing)."""
        if mode not in ("max", "min"):
            raise ValueError(f"mode must be 'max' or 'min', got {mode!r}")
        if where is None:
            ax = self.dims.index(dim)
            red = (np.max if mode == "max" else np.min)(self.values,
                                                        axis=ax)
            dims = self.dims[:ax] + self.dims[ax + 1:]
            coords = self.coords[:ax] + self.coords[ax + 1:]
            return SpaceArray(dims, coords, np.asarray(red))
        w = _as_mask(where, self)
        dims, coords = _union_layout(self, w)
        shape = tuple(len(c) for c in coords)
        vals = np.broadcast_to(_expand_to(dims, coords, self), shape)
        wv = np.broadcast_to(_expand_to(dims, coords, w), shape)
        fill = -np.inf if mode == "max" else np.inf
        masked = np.where(wv, np.asarray(vals, np.float64), fill)
        ax = dims.index(dim)
        red = (np.max if mode == "max" else np.min)(masked, axis=ax)
        red = np.where(wv.any(axis=ax), red, np.nan)
        return SpaceArray(dims[:ax] + dims[ax + 1:],
                          coords[:ax] + coords[ax + 1:], np.asarray(red))


@dataclasses.dataclass(frozen=True)
class SpaceResult:
    """Named-axis evaluation of a :class:`DesignSpace`.

    ``arrays`` maps metric name -> :class:`SpaceArray`; every array's dims
    are a subset of the implicit stack dims (``system`` / ``protocol`` /
    ``approach``) plus the requested axes, in canonical order.  ``sim``
    records the :class:`SimConfig` the flit-simulated metrics were
    evaluated under (``None`` for results predating the config).
    """

    axes: AxisSet
    arrays: Dict[str, SpaceArray]
    sim: Optional["SimConfig"] = None

    def __getitem__(self, metric: str) -> SpaceArray:
        return self.arrays[metric]

    def __contains__(self, metric: str) -> bool:
        return metric in self.arrays

    @property
    def metrics(self) -> Tuple[str, ...]:
        return tuple(self.arrays)

    def sel(self, *, where=None, **labels) -> "SpaceResult":
        """Label-select across every array carrying the named dims.

        Arrays without a requested dim pass through untouched, but a dim
        present on NO array is an error — a typo must not silently return
        the unfiltered result.  ``where`` (a boolean :class:`SpaceArray`,
        e.g. from :meth:`feasible`) NaN-masks every array that carries all
        of the mask's (post-selection) dims; arrays that don't pass
        through untouched.
        """
        known = {d for arr in self.arrays.values() for d in arr.dims}
        missing = [d for d in labels if d not in known]
        if missing:
            raise KeyError(f"dims {missing} not present on any array; "
                           f"available dims: {sorted(known)}")
        w_sel = None
        if where is not None:
            w_sel = _as_mask(where, next(iter(self.arrays.values())))
            w_sel = w_sel.isel(**{d: w_sel._label_index(d, v)
                                  for d, v in labels.items()
                                  if d in w_sel.dims})
        out = {}
        for name, arr in self.arrays.items():
            use = {d: v for d, v in labels.items() if d in arr.dims}
            a2 = arr.isel(**{d: arr._label_index(d, v)
                             for d, v in use.items()}) if use else arr
            if w_sel is not None and set(w_sel.dims) <= set(a2.dims):
                a2 = a2.sel(where=w_sel)
            out[name] = a2
        return SpaceResult(axes=self.axes, arrays=out, sim=self.sim)

    def argbest(self, metric: str, dim: str = "system",
                mode: str = "max", where=None) -> SpaceArray:
        return self.arrays[metric].argbest(dim, mode, where=where)

    def frontier(self, metric: str, dim: str = "system",
                 mode: str = "max", where=None) -> SpaceArray:
        """Alias of :meth:`argbest` — the winning label per grid point.

        ``where=res.feasible(constraints)`` restricts the frontier to the
        admissible set; points where nothing is admissible read
        ``"(none)"``.
        """
        return self.argbest(metric, dim, mode, where=where)

    def feasible(self, constraints=None, *,
                 catalog: Optional[Mapping[str, Any]] = None,
                 sim: Optional["SimConfig"] = None) -> SpaceArray:
        """First-class feasibility: a boolean :class:`SpaceArray` marking
        which (system, grid-point) cells satisfy ``constraints``
        (:class:`repro.core.selector.SelectionConstraints`).

        The mask composes with ARBITRARY axes — pass it to ``sel()`` /
        ``argbest()`` / ``frontier()`` via ``where=``.  Constraint
        semantics:

        * packaging / relative bit cost — per system; with a ``phy`` axis
          the packaging constraint masks along the phy dim instead of
          parsing ``/UCIe-A`` key suffixes.
        * ``max_backlog_knee`` — the queue-depth budget follows the most
          specific traffic information available: per ``workload_config``
          (each workload's OWN HLO-derived mix — the bridge semantics),
          else per mix point along the ``mix``/``read_fraction`` axis,
          else the canonical-mix envelope.
        * ``max_power_w`` / ``required_bandwidth_gbs`` — point-dependent,
          read from the evaluated ``power_w`` / ``bandwidth_gbs`` arrays.

        ``catalog`` must echo the ``DesignSpace(catalog=...)`` mapping when
        a custom one was evaluated (the result only carries keys).
        ``sim`` selects the :class:`SimConfig` the backlog-knee extraction
        runs under (default: this result's config, falling back to the
        fixed engine — the mode every pinned knee golden was produced in).
        """
        from repro.core import memsys
        from repro.core import selector as selector_mod
        if constraints is None:
            constraints = selector_mod.SelectionConstraints()
        base = None
        for m in ANALYTIC_METRICS:
            if m in self.arrays:
                base = self.arrays[m]
                break
        if base is None:
            raise ValueError(
                "feasible() needs at least one analytic catalog metric "
                f"({ANALYTIC_METRICS}) on the result; evaluate them first")
        dims, coords = base.dims, base.coords
        keys = base.coord("system")
        mask = np.ones(tuple(len(c) for c in coords), dtype=bool)

        def apply(sub_dims, sub_vals):
            sub = SpaceArray(tuple(sub_dims),
                             tuple(coords[dims.index(d)] for d in sub_dims),
                             np.asarray(sub_vals))
            return np.broadcast_to(_expand_to(dims, coords, sub),
                                   mask.shape)

        phy_ax = self.axes.get("phy")
        if phy_ax is not None and "phy" in dims:
            items = dict(memsys.approach_catalog_items())
            missing = [k for k in keys if k not in items]
            if missing:
                raise ValueError(f"unknown approach keys {missing} on the "
                                 "system axis of a phy-stacked result")
            items = tuple((k, items[k]) for k in keys)
            if constraints.packaging:
                mask &= apply(("phy",), [
                    p.packaging.value == constraints.packaging
                    for p in phy_ax.values])
            if constraints.max_relative_bit_cost is not None:
                mask &= apply(("system",), [
                    ms.relative_bit_cost <= constraints.max_relative_bit_cost
                    for _, ms in items])
        else:
            items = (memsys.default_catalog_items() if catalog is None
                     else tuple(catalog.items()))
            if tuple(k for k, _ in items) != tuple(keys):
                raise ValueError(
                    "catalog keys do not match the result's system axis; "
                    "pass feasible(catalog=...) matching the evaluated "
                    "DesignSpace(catalog=...)")
            static = selector_mod.system_mask(
                items, dataclasses.replace(constraints,
                                           max_backlog_knee=None))
            mask &= apply(("system",), static)

        if constraints.max_backlog_knee is not None:
            mask &= self._knee_mask(keys, constraints, apply,
                                    sim if sim is not None else self.sim)

        if constraints.max_power_w is not None:
            pw = self.arrays.get("power_w")
            if pw is None:
                raise ValueError("a max_power_w constraint needs the "
                                 "'power_w' metric on the result")
            mask &= apply(pw.dims, pw.values <= constraints.max_power_w)
        if constraints.required_bandwidth_gbs is not None:
            bw = self.arrays.get("bandwidth_gbs")
            if bw is None:
                raise ValueError("a required_bandwidth_gbs constraint "
                                 "needs the 'bandwidth_gbs' metric on the "
                                 "result")
            mask &= apply(bw.dims,
                          bw.values >= constraints.required_bandwidth_gbs)
        return SpaceArray(dims, coords, mask)

    def _knee_mask(self, keys, constraints, apply,
                   sim: Optional["SimConfig"] = None) -> np.ndarray:
        """Backlog-knee admissibility at the most specific mix available:
        per workload config, else per mix point, else the envelope."""
        from repro.core import flitsim
        from repro.core import selector as selector_mod
        budget = constraints.max_backlog_knee
        simkeys = [selector_mod.sim_key_for(k) for k in keys]
        cfg = self.axes.get("workload_config")
        mix_ax = self.axes.mix_axis()
        if cfg is not None:
            mixes = [(w.x, w.y) for _, w in cfg.values]
            per_dims = ("system", "workload_config")
        elif mix_ax is not None and OWN_MIX not in mix_ax.values:
            if mix_ax.name == "read_fraction":
                mixes = [(100.0 * r, 100.0 - 100.0 * r)
                         for r in mix_ax.values]
            else:
                mixes = list(mix_ax.values)
            per_dims = ("system", mix_ax.name)
        else:
            knees = selector_mod._default_knees()
            sub = [sk is None or knees[sk] <= budget for sk in simkeys]
            return apply(("system",), sub)
        per = flitsim.backlog_knees(mixes=mixes, per_mix=True, sim=sim)
        sub = np.ones((len(keys), len(mixes)), dtype=bool)
        for i, sk in enumerate(simkeys):
            if sk is not None:
                sub[i] = per[sk] <= budget
        return apply(per_dims, sub)


def regimes(labels: Sequence[Any], fracs: Sequence[float]
            ) -> List[Tuple[float, float, Any]]:
    """Contiguous (lo, hi, label) regimes along a fraction axis.

    Boundaries fall at the midpoint between the last grid sample of one
    winner and the first of the next; the regimes tile [0, 1] exactly.
    """
    labels = list(labels)
    fracs = [float(f) for f in fracs]
    out: List[Tuple[float, float, Any]] = []
    start, lo = 0, 0.0
    for j in range(1, len(labels) + 1):
        if j == len(labels) or labels[j] != labels[start]:
            hi = 1.0 if j == len(labels) else (fracs[j - 1] + fracs[j]) / 2.0
            out.append((lo, hi, labels[start]))
            start, lo = j, hi
    return out


# =========================================================================
# DesignSpace
# =========================================================================

#: analytic catalog metrics (dims: system [x configs] [x mix] [x shoreline])
ANALYTIC_METRICS: Tuple[str, ...] = (
    "bandwidth_gbs", "pj_per_bit", "power_w", "gbs_per_watt")
#: per-system static columns (dims: system)
SYSTEM_METRICS: Tuple[str, ...] = ("latency_ns", "relative_bit_cost")
#: flit-simulated metrics (dims: [pert x] protocol [x backlog] ...)
SIM_METRICS: Tuple[str, ...] = ("sim_efficiency", "analytic_efficiency")
#: PHY-absolute flit-simulated metric (needs a ``phy`` axis or
#: ``DesignSpace(phy=...)``): simulated efficiency x the PHY's raw link
#: bandwidth -> absolute GB/s, so the simulation-corrected frontier sweeps
#: PHY generations (32G/48G) like the closed forms do
SIM_PHY_METRICS: Tuple[str, ...] = ("sim_bandwidth_gbs",)
#: approach-density metrics on a PHY (dims: approach [x configs] [x mix])
APPROACH_METRICS: Tuple[str, ...] = (
    "linear_density_gbs_mm", "areal_density_gbs_mm2", "approach_pj_per_bit")
#: Fig-13 pipelining metric (dims: k [x ucie_line_ui] [x device_line_ui])
PIPELINE_METRICS: Tuple[str, ...] = ("utilization",)
#: trace-scan metrics (need a ``trace`` axis): duration-weighted
#: efficiency over the phase sequence (dims: [pert x] protocol x trace)
#: and the raw per-phase grid (... x phase) with state carried across
#: phase boundaries
TRACE_METRICS: Tuple[str, ...] = ("trace_efficiency",
                                  "trace_phase_efficiency")
#: PHY-absolute trace metric (needs a ``phy`` axis or
#: ``DesignSpace(phy=...)``): duration-weighted efficiency x raw link
#: bandwidth -> delivered GB/s over the serving trace
TRACE_PHY_METRICS: Tuple[str, ...] = ("trace_bandwidth_gbs",)


class DesignSpace:
    """A declarative, axes-first view of the paper's design space.

    ``DesignSpace(axes).evaluate()`` lowers the requested axis combination
    onto the batched engines — the analytic catalog program, the flit
    simulators, and the Fig-13 pipelining model — through the shared
    compile cache, and returns a :class:`SpaceResult`.

        space = DesignSpace([
            axis("workload_config", reports.items()),
            axis("mix", [OWN_MIX, (2, 1), (1, 1)]),
            axis("backlog", [4, 64]),
            axis("shoreline_mm", [4.0, 8.0]),
        ])
        res = space.evaluate()
        res["bandwidth_gbs"].argbest("system")      # frontier labels
        res["sim_efficiency"].sel(backlog=64)

    Every distinct (engine, stack, grid-shape, static-length) combination
    compiles exactly once; identically-shaped requests — from this class or
    from any legacy wrapper — run the warm executable.
    """

    def __init__(self, axes: Union[AxisSet, Sequence[Axis]], *,
                 catalog: Optional[Dict[str, Any]] = None,
                 phy: Any = None,
                 default_shoreline_mm: float = 8.0,
                 default_backlog: float = 64.0,
                 n_flits: int = 2048, n_accesses: int = 4096,
                 n_lines: int = 512,
                 sim: Optional[SimConfig] = None):
        self.axes = axes if isinstance(axes, AxisSet) else AxisSet(axes)
        self.catalog = catalog
        self.phy = phy
        self.default_shoreline_mm = float(default_shoreline_mm)
        self.default_backlog = float(default_backlog)
        self.n_flits = int(n_flits)
        self.n_accesses = int(n_accesses)
        self.n_lines = int(n_lines)
        self.sim = sim if sim is not None else FIXED_SIM
        mix_ax = self.axes.mix_axis()
        if mix_ax is not None and mix_ax.name == "mix":
            if OWN_MIX in mix_ax.values and \
                    "workload_config" not in self.axes:
                raise ValueError("mix axis uses OWN_MIX but no "
                                 "workload_config axis provides the mixes")
        if "phy" in self.axes:
            if self.phy is not None:
                raise ValueError("pass the PHY either as "
                                 "DesignSpace(phy=...) or as a 'phy' "
                                 "axis, not both")
            if self.catalog is not None:
                raise ValueError(
                    "a 'phy' axis stacks the per-approach templates "
                    "(memsys.approach_catalog_items) and is incompatible "
                    "with a custom catalog= of PHY-baked systems")

    # -- lowering helpers ---------------------------------------------------

    def _mix_arrays(self) -> Tuple[np.ndarray, np.ndarray, Tuple[str, ...]]:
        """x / y arrays over the present (workload_config, mix) axes.

        Returns float32 arrays shaped ``[C, M]`` / ``[C]`` / ``[M]`` (or
        ``[1]`` when neither axis is present) plus the dim names covered.
        """
        cfg = self.axes.get("workload_config")
        mix_ax = self.axes.mix_axis()
        if mix_ax is not None and mix_ax.name == "read_fraction":
            mixes = [(100.0 * r, 100.0 - 100.0 * r)
                     for r in mix_ax.values]
        elif mix_ax is not None:
            mixes = list(mix_ax.values)
        else:
            mixes = None
        if cfg is not None and mixes is not None:
            x = np.empty((len(cfg), len(mixes)), np.float32)
            y = np.empty_like(x)
            for c, (_, own) in enumerate(cfg.values):
                for m, mx in enumerate(mixes):
                    xx, yy = (own.x, own.y) if mx == OWN_MIX else mx
                    x[c, m], y[c, m] = xx, yy
            return x, y, ("workload_config", mix_ax.name)
        if cfg is not None:
            x = np.asarray([w.x for _, w in cfg.values], np.float32)
            y = np.asarray([w.y for _, w in cfg.values], np.float32)
            return x, y, ("workload_config",)
        if mixes is not None:
            if OWN_MIX in mixes:
                raise ValueError("OWN_MIX requires a workload_config axis")
            x = np.asarray([m[0] for m in mixes], np.float32)
            y = np.asarray([m[1] for m in mixes], np.float32)
            return x, y, (mix_ax.name,)
        return (np.asarray([100.0], np.float32),
                np.asarray([0.0], np.float32), ())

    def _default_metrics(self) -> Tuple[str, ...]:
        out: List[str] = []
        names = self.axes.names
        if self.axes.mix_axis() is not None or "workload_config" in names:
            if self.phy is not None:
                out += list(APPROACH_METRICS)
            elif "phy" in names:
                # a phy axis serves both views: the PHY-stacked catalog
                # and the Fig 10-12 approach-density sweeps
                out += (list(ANALYTIC_METRICS) + list(SYSTEM_METRICS)
                        + list(APPROACH_METRICS))
            else:
                out += list(ANALYTIC_METRICS) + list(SYSTEM_METRICS)
            if ("backlog" in names or "protocol" in names
                    or "protocol_param" in names):
                out += list(SIM_METRICS)
                if "phy" in names or self.phy is not None:
                    out += list(SIM_PHY_METRICS)
        if "trace" in names:
            out += list(TRACE_METRICS)
            if "phy" in names or self.phy is not None:
                out += list(TRACE_PHY_METRICS)
        if "k" in names:
            out += list(PIPELINE_METRICS)
        if not out:
            raise ValueError(
                f"no metric is evaluable over axes {names}; add a traffic "
                "axis (mix/read_fraction/workload_config), a trace axis, "
                "or a pipelining axis (k)")
        return tuple(out)

    # -- evaluation ---------------------------------------------------------

    def evaluate(self, metrics: Optional[Sequence[str]] = None, *,
                 sim: Optional[SimConfig] = None,
                 stream: Optional[StreamConfig] = None):
        """Resolve the requested metrics over the full joint axis space.

        ``sim`` overrides the ``DesignSpace(sim=...)`` config for this
        evaluation only — the flit-simulated metrics run fixed-horizon or
        convergence-adaptive accordingly (analytic metrics are closed
        forms and unaffected).

        ``stream`` (a :class:`StreamConfig`) switches to the tiled /
        streaming engine for 10^6–10^8-cell spaces: the cell space is
        chunked along the configured axis order, every chunk runs through
        ONE cached executable ``shard_map``-ped across devices, and
        frontier / argbest / feasibility resolve as running on-device
        reductions (full per-cell tensors never exist).  Streaming
        reduces exactly ONE metric per call and returns a
        :class:`repro.core.streaming.StreamResult` (winner labels
        bit-identical to the materialized path) instead of a
        :class:`SpaceResult`.
        """
        if stream is not None:
            from repro.core import streaming
            return streaming.stream_evaluate(
                self, metrics, sim if sim is not None else self.sim,
                stream)
        cfg = sim if sim is not None else self.sim
        wanted = tuple(metrics) if metrics is not None else \
            self._default_metrics()
        known = (ANALYTIC_METRICS + SYSTEM_METRICS + SIM_METRICS
                 + SIM_PHY_METRICS + APPROACH_METRICS + PIPELINE_METRICS
                 + TRACE_METRICS + TRACE_PHY_METRICS)
        unknown = [m for m in wanted if m not in known]
        if unknown:
            raise ValueError(f"unknown metrics {unknown}; choose from "
                             f"{known}")
        arrays: Dict[str, SpaceArray] = {}
        if any(m in wanted for m in ANALYTIC_METRICS + SYSTEM_METRICS):
            arrays.update(self._eval_catalog(wanted))
        if any(m in wanted for m in APPROACH_METRICS):
            arrays.update(self._eval_approaches(wanted))
        if any(m in wanted for m in SIM_METRICS + SIM_PHY_METRICS):
            arrays.update(self._eval_sim(wanted, cfg))
        if any(m in wanted for m in TRACE_METRICS + TRACE_PHY_METRICS):
            arrays.update(self._eval_trace(wanted, cfg))
        if any(m in wanted for m in PIPELINE_METRICS):
            arrays.update(self._eval_pipelining(wanted, cfg))
        return SpaceResult(axes=self.axes, arrays=arrays, sim=cfg)

    def _perturbations(self) -> List[Dict[str, float]]:
        cp_ax = self.axes.get("catalog_param")
        return ([dict(p) for _, p in cp_ax.values]
                if cp_ax is not None else [{}])

    def _eval_catalog(self, wanted) -> Dict[str, SpaceArray]:
        from repro.core import memsys
        phy_ax = self.axes.get("phy")
        cp_ax = self.axes.get("catalog_param")
        perts = self._perturbations()
        x, y, mix_dims = self._mix_arrays()
        sl_ax = self.axes.get("shoreline_mm")
        if sl_ax is not None:
            sl = np.asarray(sl_ax.values, np.float32)
            xb, yb = x[..., None], y[..., None]
        else:
            sl = np.float32(self.default_shoreline_mm)
            xb, yb = x, y
        if phy_ax is not None:
            # PHY-stacked engine: (catalog_param x phy) folded into the
            # phys stack, approaches as the system dim (no bus baselines)
            items = memsys.approach_catalog_items()
            phys = [phy.perturbed(p) for p in perts for phy in phy_ax.values]
            grids = memsys.run_catalog_phys_program(items, phys, xb, yb, sl)
            lead = (len(perts), len(phy_ax), len(items))
            # [Q*F, S, ...] -> [Q, S, F, ...] (system before phy)
            grids = [np.moveaxis(
                np.asarray(g).reshape(lead + np.asarray(g).shape[2:]), 2, 1)
                for g in grids]
            extra_dims: Tuple[str, ...] = ("phy",)
            extra_coords: Tuple[Tuple[Any, ...], ...] = (phy_ax.labels,)
        else:
            items = (memsys.default_catalog_items() if self.catalog is None
                     else tuple(self.catalog.items()))
            flat = (memsys.perturbed_catalog_items(items, perts)
                    if cp_ax is not None else items)
            grids = memsys.run_catalog_program(flat, xb, yb, sl)
            lead = (len(perts), len(items))
            grids = [np.asarray(g).reshape(lead + np.asarray(g).shape[1:])
                     for g in grids]
            extra_dims, extra_coords = (), ()
        bw, pjb, pw, gpw = grids
        keys = tuple(k for k, _ in items)
        dims = ("catalog_param", "system") + extra_dims + mix_dims + (
            ("shoreline_mm",) if sl_ax is not None else ())
        coords = ((cp_ax.labels if cp_ax is not None else ("baseline",)),
                  keys) + extra_coords \
            + tuple(self.axes[d].labels for d in mix_dims) \
            + ((sl_ax.labels,) if sl_ax is not None else ())
        if cp_ax is None:
            dims, coords = dims[1:], coords[1:]
        vals = {"bandwidth_gbs": bw, "pj_per_bit": pjb, "power_w": pw,
                "gbs_per_watt": gpw}
        out: Dict[str, SpaceArray] = {}
        for name in ANALYTIC_METRICS:
            if name in wanted:
                v = np.asarray(vals[name])
                if cp_ax is None:
                    v = v[0]
                # squeeze the placeholder mix point when no traffic axis
                v = v.reshape(tuple(len(c) for c in coords))
                out[name] = SpaceArray(dims, coords, v)
        if "latency_ns" in wanted:
            out["latency_ns"] = SpaceArray(
                ("system",), (keys,),
                np.asarray([ms.latency_ns for _, ms in items], np.float32))
        if "relative_bit_cost" in wanted:
            out["relative_bit_cost"] = SpaceArray(
                ("system",), (keys,),
                np.asarray([ms.relative_bit_cost for _, ms in items],
                           np.float32))
        return out

    def _eval_approaches(self, wanted) -> Dict[str, SpaceArray]:
        from repro.core import memsys
        phy_ax = self.axes.get("phy")
        cp_ax = self.axes.get("catalog_param")
        perts = self._perturbations()
        if self.phy is None and phy_ax is None:
            raise ValueError("approach metrics need DesignSpace(phy=...) "
                             "or a 'phy' axis")
        base_phys = (list(phy_ax.values) if phy_ax is not None
                     else [self.phy])
        phys = [p.perturbed(q) for q in perts for p in base_phys]
        x, y, mix_dims = self._mix_arrays()
        lin, areal, pjb = memsys.run_approach_phys_program(phys, x, y)
        from repro.core.protocols import ALL_APPROACHES
        keys = tuple(ALL_APPROACHES)
        lead = (len(perts), len(base_phys), len(keys))
        dims = ("catalog_param", "approach") + (
            ("phy",) if phy_ax is not None else ()) + mix_dims
        coords = ((cp_ax.labels if cp_ax is not None else ("baseline",)),
                  keys) + ((phy_ax.labels,) if phy_ax is not None else ()) \
            + tuple(self.axes[d].labels for d in mix_dims)
        out: Dict[str, SpaceArray] = {}
        vals = {"linear_density_gbs_mm": lin,
                "areal_density_gbs_mm2": areal,
                "approach_pj_per_bit": pjb}
        for name in APPROACH_METRICS:
            if name not in wanted:
                continue
            # [Q*F, A, ...] -> [Q, A, F, ...] (approach before phy)
            v = np.asarray(vals[name])
            v = np.moveaxis(v.reshape(lead + v.shape[2:]), 2, 1)
            if cp_ax is None:
                v = v[0]
            if phy_ax is None:
                # drop the singleton phy dim (after approach)
                v = np.take(v, 0, axis=2 if cp_ax is not None else 1)
            v = v.reshape(tuple(len(c) for c in
                                (coords if cp_ax is not None
                                 else coords[1:])))
            out[name] = SpaceArray(
                dims if cp_ax is not None else dims[1:],
                coords if cp_ax is not None else coords[1:], v)
        return out

    def _sim_protocols(self) -> Tuple[str, ...]:
        from repro.core import flitsim
        ax = self.axes.get("protocol")
        keys = tuple(ax.values) if ax is not None else \
            tuple(flitsim.SIMULATORS)
        unknown = [k for k in keys if k not in flitsim.SIMULATORS]
        if unknown:
            raise ValueError(f"unknown protocol keys {unknown}; choose "
                             f"from {sorted(flitsim.SIMULATORS)}")
        return keys

    def _eval_sim(self, wanted, sim: SimConfig) -> Dict[str, SpaceArray]:
        from repro.core import flitsim
        keys = self._sim_protocols()
        x, y, mix_dims = self._mix_arrays()
        mix_shape = x.shape
        xf = x.reshape(-1)
        yf = y.reshape(-1)
        if np.any(xf < 0) or np.any(yf < 0) or np.any(xf + yf <= 0):
            raise ValueError("invalid traffic mix in the lowered grid")
        bl_ax = self.axes.get("backlog")
        backlogs = np.asarray(bl_ax.values if bl_ax is not None
                              else [self.default_backlog], np.float32)
        pert_ax = self.axes.get("protocol_param")
        perts = ([dict(p) for _, p in pert_ax.values]
                 if pert_ax is not None else [{}])
        eff = np.asarray(flitsim.simulate_grid(
            keys, xf, yf, backlogs, perturbations=perts,
            n_flits=self.n_flits, n_accesses=self.n_accesses, sim=sim))
        # eff: [Q, P, B, Mf] -> named dims, dropping absent axes
        eff = eff.reshape(eff.shape[:3] + mix_shape)
        dims: List[str] = ["protocol_param", "protocol", "backlog"]
        coords: List[Tuple] = [
            pert_ax.labels if pert_ax is not None else ("baseline",),
            keys,
            bl_ax.labels if bl_ax is not None else (self.default_backlog,)]
        dims += list(mix_dims)
        coords += [self.axes[d].labels for d in mix_dims]
        if pert_ax is None:
            eff = eff[0]
            dims, coords = dims[1:], coords[1:]
        if bl_ax is None:
            ax_b = dims.index("backlog")
            eff = np.take(eff, 0, axis=ax_b)
            del dims[ax_b], coords[ax_b]
        if not mix_dims:                     # placeholder 100R0W point
            eff = eff[..., 0]
        out: Dict[str, SpaceArray] = {}
        if "sim_efficiency" in wanted:
            out["sim_efficiency"] = SpaceArray(
                tuple(dims), tuple(coords), np.asarray(eff))
        if "sim_bandwidth_gbs" in wanted:
            phy_ax = self.axes.get("phy")
            if phy_ax is not None:
                phys = list(phy_ax.values)
            elif self.phy is not None:
                phys = [self.phy]
            else:
                raise ValueError(
                    "the 'sim_bandwidth_gbs' metric threads the PHY's raw "
                    "link bandwidth into the simulated efficiency — add a "
                    "'phy' axis or pass DesignSpace(phy=...)")
            raw = np.asarray([p.raw_bandwidth_gbs for p in phys],
                             np.float32)
            ax_p = dims.index("protocol")
            v = (np.expand_dims(np.asarray(eff), ax_p + 1)
                 * raw.reshape((len(raw),)
                               + (1,) * (np.ndim(eff) - ax_p - 1)))
            bdims = tuple(dims[:ax_p + 1]) + ("phy",) \
                + tuple(dims[ax_p + 1:])
            bcoords = tuple(coords[:ax_p + 1]) \
                + (tuple(p.name for p in phys),) \
                + tuple(coords[ax_p + 1:])
            if phy_ax is None:          # DesignSpace(phy=...): no phy dim
                v = np.take(v, 0, axis=ax_p + 1)
                bdims = bdims[:ax_p + 1] + bdims[ax_p + 2:]
                bcoords = bcoords[:ax_p + 1] + bcoords[ax_p + 2:]
            out["sim_bandwidth_gbs"] = SpaceArray(bdims, bcoords, v)
        if "analytic_efficiency" in wanted:
            an = np.stack([np.asarray(flitsim.ANALYTIC[k].bw_eff(xf, yf),
                                      np.float32) for k in keys])
            an = an.reshape((len(keys),) + mix_shape)
            adims = ("protocol",) + mix_dims
            acoords = (keys,) + tuple(self.axes[d].labels
                                      for d in mix_dims)
            if not mix_dims:
                an = an[..., 0]
            out["analytic_efficiency"] = SpaceArray(adims, acoords, an)
        return out

    def _eval_trace(self, wanted, sim: SimConfig) -> Dict[str, SpaceArray]:
        from repro.core import flitsim
        tr_ax = self.axes.get("trace")
        if tr_ax is None:
            raise ValueError("trace metrics ('trace_efficiency', ...) "
                             "need a 'trace' axis")
        keys = self._sim_protocols()
        traces = tr_ax.values           # axis() padded them to a common N
        xs = np.asarray([[100.0 * r for r in t.read_fractions]
                         for t in traces], np.float32)
        ys = 100.0 - xs
        bls = np.asarray([t.backlogs for t in traces], np.float32)
        pert_ax = self.axes.get("protocol_param")
        perts = ([dict(p) for _, p in pert_ax.values]
                 if pert_ax is not None else [{}])
        eff = np.asarray(flitsim.simulate_trace_grid(
            keys, xs, ys, bls, perturbations=perts,
            n_flits=self.n_flits, n_accesses=self.n_accesses, sim=sim))
        # eff: per-phase [Q, P, T, N]; the duration-weighted aggregate is
        # computed host-side in f64 with per-trace normalized weights, so
        # a single-phase trace (w == d/d == 1.0 exactly) stays
        # bit-identical to its static cell through the f32 round-trip
        d = np.asarray([t.durations for t in traces], np.float64)
        w = d / d.sum(axis=1, keepdims=True)                    # [T, N]
        agg = np.einsum("qptn,tn->qpt", eff.astype(np.float64),
                        w).astype(np.float32)
        dims: List[str] = ["protocol_param", "protocol", "trace"]
        coords: List[Tuple] = [
            pert_ax.labels if pert_ax is not None else ("baseline",),
            keys, tr_ax.labels]
        if pert_ax is None:
            eff, agg = eff[0], agg[0]
            dims, coords = dims[1:], coords[1:]
        out: Dict[str, SpaceArray] = {}
        if "trace_efficiency" in wanted:
            out["trace_efficiency"] = SpaceArray(
                tuple(dims), tuple(coords), agg)
        if "trace_phase_efficiency" in wanted:
            out["trace_phase_efficiency"] = SpaceArray(
                tuple(dims) + ("phase",),
                tuple(coords) + (tuple(range(eff.shape[-1])),), eff)
        if "trace_bandwidth_gbs" in wanted:
            phy_ax = self.axes.get("phy")
            if phy_ax is not None:
                phys = list(phy_ax.values)
            elif self.phy is not None:
                phys = [self.phy]
            else:
                raise ValueError(
                    "the 'trace_bandwidth_gbs' metric threads the PHY's "
                    "raw link bandwidth into the trace-scan efficiency — "
                    "add a 'phy' axis or pass DesignSpace(phy=...)")
            raw = np.asarray([p.raw_bandwidth_gbs for p in phys],
                             np.float32)
            ax_p = dims.index("protocol")
            v = (np.expand_dims(np.asarray(agg), ax_p + 1)
                 * raw.reshape((len(raw),)
                               + (1,) * (np.ndim(agg) - ax_p - 1)))
            bdims = tuple(dims[:ax_p + 1]) + ("phy",) \
                + tuple(dims[ax_p + 1:])
            bcoords = tuple(coords[:ax_p + 1]) \
                + (tuple(p.name for p in phys),) \
                + tuple(coords[ax_p + 1:])
            if phy_ax is None:          # DesignSpace(phy=...): no phy dim
                v = np.take(v, 0, axis=ax_p + 1)
                bdims = bdims[:ax_p + 1] + bdims[ax_p + 2:]
                bcoords = bcoords[:ax_p + 1] + bcoords[ax_p + 2:]
            out["trace_bandwidth_gbs"] = SpaceArray(bdims, bcoords, v)
        return out

    def _eval_pipelining(self, wanted, sim: SimConfig
                         ) -> Dict[str, SpaceArray]:
        from repro.core import flitsim
        k_ax = self.axes.get("k")
        if k_ax is None:
            raise ValueError("the 'utilization' metric needs a 'k' axis")
        u_ax = self.axes.get("ucie_line_ui")
        d_ax = self.axes.get("device_line_ui")
        us = tuple(u_ax.values) if u_ax is not None else (16.0,)
        ds = tuple(d_ax.values) if d_ax is not None else (64.0,)
        util = np.asarray(flitsim._sweep_pipelining_impl(
            k_ax.values, n_lines=self.n_lines, ucie_line_ui=us,
            device_line_ui=ds, sim=sim))        # [K, U, D]
        dims: List[str] = ["k"]
        coords: List[Tuple] = [k_ax.labels]
        if u_ax is not None:
            dims.append("ucie_line_ui")
            coords.append(u_ax.labels)
        else:
            util = util[:, 0]
        if d_ax is not None:
            dims.append("device_line_ui")
            coords.append(d_ax.labels)
        else:
            util = util[..., 0]
        if "utilization" not in wanted:
            return {}
        return {"utilization": SpaceArray(tuple(dims), tuple(coords),
                                          util)}

    # -- unified frontier reports -------------------------------------------

    def report(self, spec=None) -> Dict[str, Any]:
        """ONE entry point for every frontier report.

        ``spec`` is a :class:`repro.core.report.ReportSpec` naming the
        sections to build — ``"joint"`` (:func:`joint_frontier`),
        ``"phy"`` / ``"sim_phy"`` (the PHY-stacked analytic and
        simulation-corrected frontiers), ``"serving"``
        (:meth:`serving_frontier`), and ``"frontier"`` (this instance's
        own metric frontier over its axes).  Returns ``{section:``
        :class:`repro.core.report.FrontierReport` ``}``; each payload is
        byte-identical to the legacy builder it replaces (the
        ``design_space.json`` sections are unchanged).
        """
        from repro.core.report import build_report
        return build_report(spec, space=self)

    # -- serving frontier ---------------------------------------------------

    @staticmethod
    def serving_frontier(models=None, qps_points=None,
                         **kwargs) -> Dict[str, Any]:
        """Per-(model, QPS) serving frontier: synthetic serving traces
        evaluated through the ``trace`` axis, winners mapped to catalog
        memory approaches.  Delegates to
        :func:`repro.traces.frontier.serving_frontier` (see there for the
        knobs); this is the entry point ``dryrun --all`` and the explorer
        ``--serving`` mode persist as the ``serving_frontier`` section of
        ``design_space.json``."""
        from repro.traces.frontier import (DEFAULT_MODELS, DEFAULT_QPS,
                                           serving_frontier)
        return serving_frontier(
            models if models is not None else DEFAULT_MODELS,
            qps_points if qps_points is not None else DEFAULT_QPS,
            **kwargs)


# =========================================================================
# Joint analytic-vs-simulated frontier (new capability)
# =========================================================================


def joint_frontier(n_fracs: int = 21,
                   backlogs: Sequence[float] = (2.0, 8.0, 64.0),
                   shorelines: Sequence[float] = (4.0, 8.0, 16.0),
                   catalog: Optional[Dict[str, Any]] = None,
                   n_flits: int = 2048,
                   constraints=None,
                   sim: Optional[SimConfig] = None,
                   phys: Optional[Sequence[Any]] = None) -> Dict[str, Any]:
    """Joint (mix x backlog x shoreline) frontier merging the flit-simulated
    efficiency grid with the analytic catalog grid.

    For every catalog system backed by a flit simulator, the analytic
    bandwidth is rescaled by the simulated/analytic efficiency ratio at
    each (mix, backlog) point; systems without a simulator (bus baselines)
    keep their closed-form bandwidth.  The report marks the read-fraction
    regions where the simulation-corrected winner differs from the analytic
    winner — i.e. where the paper's closed forms and the cycle-level
    simulation *disagree* about the best memory system — per (backlog,
    shoreline) cell, plus each protocol's worst simulated-vs-analytic
    relative error.

    This is the first capability only expressible in the unified axes-first
    API: it needs the analytic catalog axes and the flit-simulation axes
    resolved over one shared mix grid in a single evaluation.

    ``constraints`` (optional :class:`repro.core.selector.
    SelectionConstraints`) restricts BOTH frontiers to the feasible set
    via :meth:`SpaceResult.feasible` — infeasible cells never win, and
    cells with no admissible system read ``"(none)"``.

    ``sim`` selects the flit-simulation config (:data:`FIXED_SIM`
    default; pass :data:`ADAPTIVE_SIM` for the convergence-adaptive
    early-exit engine — what the benchmarks and the explorer use).

    The report folds in a ``sim_bandwidth_gbs`` section: the SAME
    simulated-efficiency grid threaded onto each PHY generation's raw
    link bandwidth (``phys`` — default UCIe-A/S at 32G plus the 48G
    points), so PHY generations, queue depths and simulation corrections
    land in ONE frontier section with zero extra compiles.
    """
    from repro.core.selector import sim_key_for
    fracs = np.linspace(0.0, 1.0, n_fracs)
    space = DesignSpace(
        [axis("read_fraction", fracs),
         axis("backlog", backlogs),
         axis("shoreline_mm", shorelines)],
        catalog=catalog, n_flits=n_flits, sim=sim)
    metrics = ANALYTIC_METRICS[:1] + SIM_METRICS
    if constraints is not None:
        metrics = metrics + ("power_w",)
    res = space.evaluate(metrics=metrics)
    bw = res["bandwidth_gbs"]                  # [S, M, L]
    sim = res["sim_efficiency"]                # [P, B, M]
    ana = res["analytic_efficiency"]           # [P, M]
    keys = bw.coord("system")
    protocols = sim.coord("protocol")
    ratio = sim.values / np.maximum(ana.values[:, None, :], 1e-9)
    rel_err = {p: float(np.max(np.abs(ratio[i] - 1.0)))
               for i, p in enumerate(protocols)}

    n_b = sim.values.shape[1]
    corrected = np.repeat(bw.values[:, None, :, :], n_b, axis=1)
    for s, key in enumerate(keys):
        simkey = sim_key_for(key)
        if simkey is not None and simkey in protocols:
            p = protocols.index(simkey)
            corrected[s] = bw.values[s][None] * ratio[p][:, :, None]

    feas = res.feasible(constraints, catalog=catalog) \
        if constraints is not None else None
    analytic_best = bw.argbest("system", where=feas).values    # [M, L]
    if feas is not None:
        corrected = np.where(feas.values[:, None, :, :], corrected,
                             -np.inf)
    sim_best_idx = np.argmax(corrected, axis=0)            # [B, M, L]
    sim_best = np.asarray(keys, dtype=object)[sim_best_idx]
    if feas is not None:
        none_cells = ~feas.values.any(axis=0)[None]        # [1, M, L]
        sim_best = np.where(np.broadcast_to(none_cells, sim_best.shape),
                            "(none)", sim_best)
    disagree = sim_best != analytic_best[None]
    regions: List[Dict[str, Any]] = []
    for b, bl in enumerate(sim.coord("backlog")):
        for l, sl in enumerate(bw.coord("shoreline_mm")):
            if not disagree[b, :, l].any():
                continue
            for lo, hi, pair in regimes(
                    [(a, s) for a, s in zip(analytic_best[:, l],
                                            sim_best[b, :, l])],
                    fracs):
                if pair[0] != pair[1]:
                    regions.append({
                        "backlog": float(bl), "shoreline_mm": float(sl),
                        "read_fraction_lo": lo, "read_fraction_hi": hi,
                        "analytic_best": str(pair[0]),
                        "simulated_best": str(pair[1])})
    # -- folded PHY-absolute section ------------------------------------
    # the same simulated-efficiency grid threaded onto each PHY's raw
    # link bandwidth: winner regimes per (phy, backlog) with no extra
    # simulation or compile (raw bandwidth is a per-PHY scale)
    from repro.core.selector import approach_key_for
    if phys is None:
        from repro.core.ucie import (
            UCIE_A_32G_55U, UCIE_A_48G_45U, UCIE_S_32G, UCIE_S_48G_110U)
        phys = [UCIE_S_32G, UCIE_A_32G_55U, UCIE_S_48G_110U,
                UCIE_A_48G_45U]
    proto_arr = np.asarray(protocols, dtype=object)
    sim_section: Dict[str, Any] = {
        "phys": [p.name for p in phys],
        "backlogs": [float(b) for b in backlogs],
        "read_fractions": fracs.tolist(),
        "peak_gbs_by_phy": {},
        "best_protocol_by_phy": {},
        "regimes_by_phy_backlog": {},
    }
    for p in phys:
        gbs = sim.values * np.float32(p.raw_bandwidth_gbs)   # [P, B, M]
        regs_by_bl = {}
        for b, bl in enumerate(sim.coord("backlog")):
            win = proto_arr[np.argmax(gbs[:, b, :], axis=0)]
            regs_by_bl[f"{bl:g}"] = [
                {"read_fraction_lo": lo, "read_fraction_hi": hi,
                 "best": str(lab), "approach": approach_key_for(str(lab))}
                for lo, hi, lab in regimes(win.tolist(), fracs)]
        sim_section["regimes_by_phy_backlog"][p.name] = regs_by_bl
        at70 = proto_arr[int(np.argmax(
            gbs[:, -1, int(round(0.7 * (n_fracs - 1)))]))]
        sim_section["best_protocol_by_phy"][p.name] = str(at70)
        sim_section["peak_gbs_by_phy"][p.name] = float(gbs.max())

    return {
        "read_fractions": fracs.tolist(),
        "backlogs": [float(b) for b in backlogs],
        "shorelines": [float(s) for s in shorelines],
        "keys": list(keys),
        "protocol_rel_err": rel_err,
        "analytic_best": analytic_best.astype(str).tolist(),
        "simulated_best": sim_best.astype(str).tolist(),
        "disagreement_fraction": float(disagree.mean()),
        "disagreement_regions": regions,
        "sim_bandwidth_gbs": sim_section,
    }
