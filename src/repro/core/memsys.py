"""MemorySystem — compose a protocol mapping with a UCIe PHY (or a bus
baseline) into a deployable on-package memory model.

This is the object the roofline bridge consumes: given a workload's traffic
mix it answers "what data bandwidth, pJ/b and latency does this memory
system deliver, for a given shoreline budget?".

Batched evaluation: :func:`run_catalog_program` stacks every system's
closed-form metrics into ``[S, ...]`` arrays produced by a single compiled
(and memoized) program — this is the analytic engine the axes-first
:class:`repro.core.space.DesignSpace` lowers onto.  Executables live in the
SHARED design-space compile cache (:mod:`repro.core.space`), keyed on
(catalog, grid shapes): any front-end — ``_catalog_grid_impl``,
``bridge_design_space``, or a ``DesignSpace`` evaluation — that requests an
identically-shaped grid runs the warm executable.  ``_catalog_grid_impl`` and
:func:`approach_grid` remain as compatibility wrappers returning the legacy
stacked dataclasses.

The PHY is an axis, not a key suffix: :func:`run_catalog_phys_program` /
:func:`run_approach_phys_program` stack (phy x system) pairs into the SAME
cache families, which is what ``axis("phy", [...])`` lowers onto —
:func:`approach_catalog_items` provides the PHY-less per-approach
templates, and :func:`perturbed_catalog_items` folds ``catalog_param``
perturbations (``UCIePhy.perturbed``) into the stack.

Relation to the flit-simulation ``sim=`` config: the analytic programs
here are closed forms (no cycle loop), so
:class:`repro.core.space.SimConfig` does not change their numerics — only
the flit-simulated metrics (``sim_efficiency`` / ``sim_bandwidth_gbs``)
riding next to them in a joint ``DesignSpace`` evaluation switch between
fixed-horizon and convergence-adaptive execution.  The PHY axis does feed
the simulators through ``sim_bandwidth_gbs`` (simulated efficiency x
``UCIePhy.raw_bandwidth_gbs``), which is how the simulation-corrected
frontier sweeps 32G/48G generations like the closed forms do.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from repro.core import latency as latency_mod
from repro.core import space as space_mod
from repro.core.space import CacheStats, cached_program
from repro.core.protocols import (
    ALL_APPROACHES, BASELINES, BidirectionalBusMemory, MemoryProtocol,
)
from repro.core.ucie import UCIE_A_32G_55U, UCIE_S_32G, UCIePhy


@dataclasses.dataclass(frozen=True)
class MemorySystem:
    name: str
    protocol: MemoryProtocol
    phy: Optional[UCIePhy] = None          # None for bus baselines
    latency_ns: float = 3.0
    #: relative $/bit of the DRAM behind the interface (LPDDR=1, HBM=7.5)
    relative_bit_cost: float = 1.0

    def _is_bus(self) -> bool:
        return isinstance(self.protocol, BidirectionalBusMemory)

    def bw_eff(self, x, y):
        return self.protocol.bw_eff(x, y)

    def linear_density(self, x, y):
        return self.protocol.bw_density_linear(x, y, self.phy)

    def areal_density(self, x, y):
        return self.protocol.bw_density_areal(x, y, self.phy)

    def pj_per_bit(self, x, y):
        return self.protocol.power_pj_per_bit(x, y, self.phy)

    def bandwidth_gbs(self, x, y, shoreline_mm: float):
        """Deliverable cache-line GB/s for a shoreline budget."""
        return self.linear_density(x, y) * shoreline_mm

    def power_w(self, x, y, shoreline_mm: float):
        """Interconnect power (W) at full utilization of the shoreline."""
        gbs = self.bandwidth_gbs(x, y, shoreline_mm)
        return gbs * 8.0 * self.pj_per_bit(x, y) / 1000.0   # GB/s * pJ/b -> W


def standard_catalog() -> Dict[str, MemorySystem]:
    """Every (approach x packaging) the paper evaluates + the baselines."""
    cat: Dict[str, MemorySystem] = {}
    lat = latency_mod.MEASURED_FRONTEND_LATENCY_NS
    for key, proto in ALL_APPROACHES.items():
        for phy, tag in ((UCIE_A_32G_55U, "UCIe-A"), (UCIE_S_32G, "UCIe-S")):
            bit_cost = 7.5 if "hbm" in key else 1.0
            cat[f"{key}/{tag}"] = MemorySystem(
                name=f"{proto.name}/{tag}",
                protocol=proto, phy=phy,
                latency_ns=lat["UCIe-Memory"],
                relative_bit_cost=bit_cost,
            )
    for bname, bus in BASELINES.items():
        cat[bname] = MemorySystem(
            name=bus.name, protocol=bus, phy=None,
            latency_ns=lat.get(bname, 6.0),
            relative_bit_cost=7.5 if "HBM" in bname else 1.0,
        )
    return cat


@functools.lru_cache(maxsize=1)
def default_catalog_items() -> Tuple[Tuple[str, MemorySystem], ...]:
    """The standard catalog as a hashable, cached tuple of items — the key
    the batched-grid compile cache is built on."""
    return tuple(standard_catalog().items())


@functools.lru_cache(maxsize=1)
def approach_catalog_items() -> Tuple[Tuple[str, MemorySystem], ...]:
    """Per-approach :class:`MemorySystem` templates WITHOUT a baked PHY.

    This is the catalog a ``phy`` axis stacks: the axes-first API pairs
    each template with every PHY on the axis
    (:func:`phy_stacked_items`), so the PHY is a queryable dimension of
    the result instead of a ``/UCIe-A`` key suffix.  Bus baselines are
    excluded — they do not attach over a UCIe PHY.
    """
    lat = latency_mod.MEASURED_FRONTEND_LATENCY_NS
    return tuple(
        (key, MemorySystem(
            name=proto.name, protocol=proto, phy=None,
            latency_ns=lat["UCIe-Memory"],
            relative_bit_cost=7.5 if "hbm" in key else 1.0))
        for key, proto in ALL_APPROACHES.items())


def phy_stacked_items(items: Tuple[Tuple[str, MemorySystem], ...],
                      phys) -> Tuple[Tuple[str, MemorySystem], ...]:
    """Flatten (phy x system) into one stacked catalog: PHY-major order,
    so program outputs reshape to ``[F, S, ...]``."""
    return tuple(
        (f"{key}@{phy.name}", dataclasses.replace(ms, phy=phy,
                                                  name=f"{ms.name}/{phy.name}"))
        for phy in phys for key, ms in items)


def perturbed_catalog_items(items: Tuple[Tuple[str, MemorySystem], ...],
                            perturbations
                            ) -> Tuple[Tuple[str, MemorySystem], ...]:
    """Flatten (catalog_param x system) into one stacked catalog.

    Each multiplicative ``{field: scale}`` perturbation is applied to every
    system's PHY (``UCIePhy.perturbed``); systems without a PHY (bus
    baselines) pass through unperturbed — mirroring how an asymmetric flit
    protocol ignores a symmetric-only ``protocol_param`` field.
    Perturbation-major order: program outputs reshape to ``[Q, S, ...]``.
    """
    out = []
    for pert in perturbations:
        for key, ms in items:
            if ms.phy is not None and pert:
                ms = dataclasses.replace(ms, phy=ms.phy.perturbed(pert))
            out.append((key, ms))
    return tuple(out)


# -- batched grid evaluation --------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CatalogGrid:
    """Stacked per-system metrics over a traffic-mix grid.

    Metric arrays are ``[S, *mix_shape]`` where ``S`` follows ``keys``;
    ``latency_ns`` / ``relative_bit_cost`` are per-system ``[S]`` scalars.
    """

    keys: Tuple[str, ...]
    bandwidth_gbs: jnp.ndarray
    pj_per_bit: jnp.ndarray
    power_w: jnp.ndarray
    gbs_per_watt: jnp.ndarray
    latency_ns: jnp.ndarray
    relative_bit_cost: jnp.ndarray


#: legacy alias — the shared-cache counters use one stats type now
GridCacheStats = CacheStats


def grid_cache_stats() -> CacheStats:
    """This module's slice of the SHARED design-space compile cache
    (families ``memsys.*``): one miss == one trace+compile of a stacked
    program (new catalog or new grid shape); hits run warm."""
    return space_mod.cache_stats(space_mod.MEMSYS_FAMILIES)


def clear_grid_cache() -> None:
    """Drop the memoized grid programs and reset the hit/miss counters."""
    space_mod.clear_cache(space_mod.MEMSYS_FAMILIES)


def run_catalog_program(items: Tuple[Tuple[str, MemorySystem], ...],
                        x, y, shoreline_mm):
    """Evaluate the stacked catalog program on (x, y, shoreline) arrays.

    The engine entry point ``DesignSpace`` lowers onto.  Returns
    ``(bandwidth_gbs, pj_per_bit, power_w, gbs_per_watt)``, each
    ``[S, *broadcast(x, y, shoreline)]``.  Compiled once per (catalog,
    grid-shape) into the shared design-space cache.
    """
    items = tuple(items)
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    sl = jnp.asarray(shoreline_mm, jnp.float32)
    systems = [ms for _, ms in items]

    def fn(x, y, sl):
        bw = jnp.stack([ms.bandwidth_gbs(x, y, sl) for ms in systems])
        pjb = jnp.stack([jnp.broadcast_to(ms.pj_per_bit(x, y), bw.shape[1:])
                         for ms in systems])
        pw = bw * 8.0 * pjb / 1000.0        # GB/s * pJ/b -> W
        gpw = jnp.where(pw > 0, bw / pw, jnp.inf)
        return bw, pjb, pw, gpw

    prog = cached_program("memsys.catalog",
                          (items, x.shape, y.shape, sl.shape),
                          fn, (x, y, sl), path="grid")
    return prog(x, y, sl)


def _catalog_grid_impl(x, y, shoreline_mm=8.0,
                       catalog: Optional[Dict[str, MemorySystem]] = None,
                       ) -> CatalogGrid:
    """Engine body of the retired ``catalog_grid`` front-end —
    internal callers (``selector.rank``, the roofline bridge) use this
    directly, warning-free."""
    items = (default_catalog_items() if catalog is None
             else tuple(catalog.items()))
    bw, pjb, pw, gpw = run_catalog_program(items, x, y, shoreline_mm)
    return CatalogGrid(
        keys=tuple(k for k, _ in items),
        bandwidth_gbs=bw, pj_per_bit=pjb, power_w=pw, gbs_per_watt=gpw,
        latency_ns=jnp.asarray([ms.latency_ns for _, ms in items],
                               jnp.float32),
        relative_bit_cost=jnp.asarray(
            [ms.relative_bit_cost for _, ms in items], jnp.float32),
    )


@dataclasses.dataclass(frozen=True)
class ApproachGrid:
    """Stacked ``[S, *mix_shape]`` density/power metrics for ALL_APPROACHES
    on a given PHY (the Figs 10-12 sweeps)."""

    keys: Tuple[str, ...]
    linear: jnp.ndarray
    areal: jnp.ndarray
    pj_per_bit: jnp.ndarray


def run_catalog_phys_program(items: Tuple[Tuple[str, MemorySystem], ...],
                             phys, x, y, shoreline_mm):
    """PHY-stacked variant of :func:`run_catalog_program`.

    ``items`` are PHY-less templates (:func:`approach_catalog_items`);
    every (phy, system) pair is flattened into ONE stacked catalog program
    (same ``memsys.catalog`` cache family — the full ``[phy x configs x
    mix x shoreline]`` space still compiles once), then reshaped to
    ``(bandwidth_gbs, pj_per_bit, power_w, gbs_per_watt)``, each
    ``[F, S, *grid]``.
    """
    phys = tuple(phys)
    items = tuple(items)
    flat = phy_stacked_items(items, phys)
    bw, pjb, pw, gpw = run_catalog_program(flat, x, y, shoreline_mm)
    lead = (len(phys), len(items))
    return tuple(a.reshape(lead + a.shape[1:]) for a in (bw, pjb, pw, gpw))


def run_approach_phys_program(phys, x, y):
    """PHY-stacked approach-density program on (x, y); shared-cache
    memoized (``memsys.approach`` family — one compile per (phys,
    grid-shape)).

    Returns ``(linear, areal, pj_per_bit)``, each ``[F, A, *x.shape]``.
    """
    phys = tuple(phys)
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    protos = tuple(ALL_APPROACHES.values())

    def fn(x, y):
        lin = jnp.stack([
            jnp.stack([p.bw_density_linear(x, y, phy) for p in protos])
            for phy in phys])
        areal = jnp.stack([
            jnp.stack([p.bw_density_areal(x, y, phy) for p in protos])
            for phy in phys])
        pjb = jnp.stack([
            jnp.stack([jnp.broadcast_to(p.power_pj_per_bit(x, y, phy),
                                        lin.shape[2:]) for p in protos])
            for phy in phys])
        return lin, areal, pjb

    prog = cached_program("memsys.approach", (phys, x.shape, y.shape),
                          fn, (x, y), path="grid")
    return prog(x, y)


def run_approach_program(phy: UCIePhy, x, y):
    """Stacked approach-density program on (x, y); shared-cache memoized.

    Single-PHY wrapper over :func:`run_approach_phys_program` — the same
    executable serves ``approach_grid``, ``DesignSpace(phy=...)`` and a
    one-entry ``phy`` axis.  Returns ``(linear, areal, pj_per_bit)``, each
    ``[A, *x.shape]``.
    """
    lin, areal, pjb = run_approach_phys_program((phy,), x, y)
    return lin[0], areal[0], pjb[0]


def approach_grid(phy: UCIePhy, x, y) -> ApproachGrid:
    """All approaches' bandwidth-density and pJ/b over a mix grid, stacked
    and computed in one compiled call per (phy, grid-shape) — a
    compatibility wrapper over :func:`run_approach_program`."""
    lin, areal, pjb = run_approach_program(phy, x, y)
    return ApproachGrid(keys=tuple(ALL_APPROACHES), linear=lin, areal=areal,
                        pj_per_bit=pjb)
