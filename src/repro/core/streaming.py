"""Tiled / sharded streaming evaluation for 10^6–10^8-cell design spaces.

The materialized engines (:meth:`repro.core.space.DesignSpace.evaluate`)
return whole per-cell metric tensors — fine up to ~10^6 cells, impossible
for the joint [phy x protocol_param x backlog x mix] spaces the ROADMAP
targets.  This module is the other execution mode behind the SAME axes:
``evaluate(..., stream=StreamConfig(...))`` flattens the cell space along
a configurable axis order, cuts it into per-device chunks, and pushes
every chunk through ONE executable (shared shape-keyed compile cache,
families ``stream.*``) that is ``shard_map``-ped across devices.
Frontier / argbest / feasibility resolve as RUNNING on-device
reductions:

* per-cell winner codes (one small int per cell — the only per-cell
  output that ever exists),
* per-label win counts and best metric values (``lax.psum`` /
  ``lax.pmax`` across the device mesh, accumulated across dispatches
  host-side).

Bit-identity contract: the streamed winner labels are bit-identical to
the materialized ``argbest`` on every grid — the chunk programs vmap the
EXACT scalar cell functions of the fixed-horizon cores
(:func:`repro.core.flitsim._symmetric_cells_grid` /
``_asymmetric_cells_grid``) and the closed-form
:class:`~repro.core.memsys.MemorySystem` methods, f32 arithmetic is
IEEE-deterministic, and ``jnp.argmax`` shares numpy's first-max
tie-break.  Constraint thresholds are compared through
:func:`_le_threshold_f32` / :func:`_ge_threshold_f32` so the f32 on-device
comparison admits exactly the cells the f64 host comparison admits.

Simulated metrics stream under the FIXED engine only (the adaptive cores'
early-exit schedule depends on batch shape, which would break the
bit-identity contract across chunk sizes); control cost via
``DesignSpace(n_flits=..., n_accesses=...)`` instead.

Async double-buffered dispatch: the per-dispatch loop marshals chunk
``t+1``'s cell indices (pure numpy — ``_chunk_ids`` plus the
mix/backlog/perturbation gathers) while up to ``StreamConfig.prefetch``
earlier chunks are still in flight on the device, and blocks only when
the in-flight window is full.  Results retire strictly FIFO, so the
running host-side folds (winner-code scatter, count sums, best maxima)
execute in EXACTLY the order of the sequential loop — ``prefetch=1``
reduces to the sequential schedule, and every depth produces
bit-identical ``StreamResult`` contents.  The FIFO retire is the one
audited host sync of the loop (see the RL004 suppressions); per-run
dispatch/overlap telemetry lands in
``flitsim.last_run_info()["stream.*"]``.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core import space as space_mod

__all__ = ["StreamResult", "stream_evaluate"]

#: streamable flit-simulated metrics (reduce dim: ``protocol``)
STREAM_SIM_METRICS: Tuple[str, ...] = ("sim_efficiency",
                                       "sim_bandwidth_gbs")

_MESHES: Dict[int, Any] = {}

#: ``shard_map`` with its varying-manual-axes check off.  The chunk bodies
#: vmap the fixed-horizon reference cores, whose ``lax.scan`` carries
#: start as unvarying constants and become varying over ``chunks`` after
#: one step; the check rejects that carry.  Marking the carries varying
#: would make the shared reference cores mesh-aware for every caller, and
#: nothing here needs the check: each body's only collectives are the
#: explicit ``psum`` / ``pmax`` of per-device partials, and ``out_specs``
#: already says which outputs are replicated.
_shard_map = functools.partial(jax.shard_map, check_vma=False)


def _mesh(devices: int):
    """Memoized 1-d ``("chunks",)`` device mesh of the leading devices."""
    cached = _MESHES.get(devices)
    if cached is not None:
        return cached
    avail = jax.local_device_count()
    if devices > avail:
        raise ValueError(
            f"StreamConfig(devices={devices}) exceeds the {avail} local "
            f"device(s); on CPU export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={devices} before "
            "importing jax to emulate a wider mesh")
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:devices]), ("chunks",))
    _MESHES[devices] = mesh
    return mesh


def _le_threshold_f32(t: float) -> np.float32:
    """Largest f32 ``t32`` with ``v <= t32  <=>  v <= t`` for every f32
    ``v`` — keeps the on-device f32 constraint comparison admitting
    exactly the cells the materialized f64 comparison admits."""
    t32 = np.float32(t)
    if np.float64(t32) > np.float64(t):
        t32 = np.nextafter(t32, np.float32(-np.inf))
    return t32


def _ge_threshold_f32(t: float) -> np.float32:
    """Smallest f32 ``t32`` with ``v >= t32  <=>  v >= t`` (see
    :func:`_le_threshold_f32`)."""
    t32 = np.float32(t)
    if np.float64(t32) < np.float64(t):
        t32 = np.nextafter(t32, np.float32(np.inf))
    return t32


def _cell_order(dims_all: Sequence[str], present: Sequence[bool],
                axis_order) -> Tuple[int, ...]:
    """Permutation of cell-dim positions realizing ``axis_order``.

    ``axis_order`` must be a permutation of the PRESENT cell axes; absent
    (size-1 placeholder) dims are appended at the end — they carry one
    index, so their position never changes the enumeration.
    """
    if axis_order is None:
        return tuple(range(len(dims_all)))
    avail = [d for d, p in zip(dims_all, present) if p]
    if sorted(axis_order) != sorted(avail):
        raise ValueError(
            f"StreamConfig.axis_order must be a permutation of the "
            f"space's cell axes {avail}, got {list(axis_order)}")
    order = [dims_all.index(d) for d in axis_order]
    order += [i for i, p in enumerate(present) if not p]
    return tuple(order)


@dataclasses.dataclass(frozen=True)
class StreamResult:
    """Reduced output of one streaming evaluation.

    ``winners`` is the ONLY per-cell artifact: a
    :class:`~repro.core.space.SpaceArray` of winner labels whose dims and
    coords are bit-identical to the materialized
    ``evaluate()[metric].argbest(reduce_dim, mode)`` (cells where the
    constraints admit nothing read ``"(none)"``).  ``win_counts`` /
    ``best_by_label`` are the running on-device reductions (win counts sum
    to ``n_cells``; bests are NaN for labels the constraints never
    admit).  ``peak_cells_per_chunk`` is the asserted memory budget: the
    maximum number of joint cells resident per device per dispatch.
    """

    metric: str
    reduce_dim: str                 # "protocol" | "system"
    mode: str                       # "max" | "min"
    labels: Tuple[str, ...]
    winners: Any                    # SpaceArray of winner labels
    win_counts: Dict[str, int]
    best_by_label: Dict[str, float]
    n_cells: int                    # total joint cells reduced
    n_stream_cells: int             # streamed (chunked) cell-space size
    n_dispatches: int
    chunk_cells: int                # streamed cells per device per dispatch
    peak_cells_per_chunk: int       # peak joint cells per device
    devices: int
    compiles: int                   # stream.* cache misses this evaluation

    def frontier(self) -> Any:
        """The winner-label array (argbest alias, mirroring
        :meth:`repro.core.space.SpaceResult.frontier`)."""
        return self.winners


def _dispatch_plan(n_cells: int, stream, shape_perm):
    """(devices, chunk, step, dispatches) for a flat cell space."""
    devices = (int(stream.devices) if stream.devices is not None
               else jax.local_device_count())
    mesh = _mesh(devices)
    chunk = max(1, min(int(stream.chunk_cells),
                       -(-n_cells // devices)))
    step = devices * chunk
    return mesh, devices, chunk, step, -(-n_cells // step)


def _chunk_ids(lo: int, step: int, n_cells: int):
    """Global cell ids + validity for dispatch window [lo, lo+step);
    the tail pads by repeating the last live cell."""
    live = min(step, n_cells - lo)
    ids = np.arange(lo, lo + step, dtype=np.int64)
    if live < step:
        ids[live:] = ids[live - 1]
    valid = np.zeros(step, np.int32)
    valid[:live] = 1
    return ids, valid, live


def _winner_array(codes: np.ndarray, shape_perm, order, full, labels_ext):
    """Reduced winner codes -> a SpaceArray bit-identical to the
    materialized argbest: reshape in dispatch order, transpose back to
    canonical order, gather labels, drop absent (size-1) dims."""
    trail = codes.shape[1:]         # broadcast dims appended after cells
    grid = codes.reshape(shape_perm + trail)
    inv = tuple(int(i) for i in np.argsort(np.asarray(order)))
    grid = np.transpose(grid, inv + tuple(len(order) + i
                                          for i in range(len(trail))))
    lab = labels_ext[grid.astype(np.int64)]
    if trail:                       # [cells..., F] -> [pert, F, rest...]
        lab = np.moveaxis(lab, -1, 1)
    for axpos in reversed(range(len(full))):
        if not full[axpos][1]:
            lab = np.take(lab, 0, axis=axpos)
    dims = tuple(n for n, p, _ in full if p)
    coords = tuple(c for _, p, c in full if p)
    return space_mod.SpaceArray(dims, coords,
                                np.asarray(lab, dtype=object))


# =========================================================================
# Simulated metrics (stream.sim family)
# =========================================================================


def _stream_sim(space, metric: str, sim, stream) -> StreamResult:
    from repro.core import flitsim
    if sim.mode != "fixed":
        raise ValueError(
            "streaming evaluation runs the fixed-horizon cores only (the "
            "adaptive early-exit schedule depends on batch shape, which "
            "would break chunk-size invariance); got "
            f"SimConfig(mode={sim.mode!r}).  Control cost via "
            "DesignSpace(n_flits=..., n_accesses=...) instead")
    if stream.mode not in (None, "max"):
        raise ValueError("simulated streaming frontiers maximize "
                         f"efficiency; got StreamConfig(mode="
                         f"{stream.mode!r})")
    keys = space._sim_protocols()
    x, y, mix_dims = space._mix_arrays()
    mix_shape = x.shape
    xf = np.asarray(x, np.float32).reshape(-1)
    yf = np.asarray(y, np.float32).reshape(-1)
    if np.any(xf < 0) or np.any(yf < 0) or np.any(xf + yf <= 0):
        raise ValueError("invalid traffic mix in the lowered grid")
    bl_ax = space.axes.get("backlog")
    backlogs = np.asarray(bl_ax.values if bl_ax is not None
                          else [space.default_backlog], np.float32)
    pert_ax = space.axes.get("protocol_param")
    perts = ([dict(p) for _, p in pert_ax.values]
             if pert_ax is not None else [{}])
    sym_keys = [k for k in keys if k in flitsim.SYMMETRIC_PARAMS]
    asym_keys = [k for k in keys if k in flitsim.ASYMMETRIC_PARAMS]
    # perturbation validation — mirror of flitsim.simulate_grid
    active_fields: set = set()
    if sym_keys:
        active_fields |= {f.name for f in dataclasses.fields(
            flitsim.SymmetricFlitParams)}
    if asym_keys:
        active_fields |= {f.name for f in dataclasses.fields(
            flitsim.AsymmetricLaneParams)}
    for p in perts:
        flitsim.check_perturbation(p)
        if p and not set(p) & active_fields:
            raise ValueError(
                f"perturbation {p} applies to no parameter of the "
                f"selected protocols {keys}; applicable fields: "
                f"{sorted(active_fields)}")

    phy_ax = space.axes.get("phy")
    if metric == "sim_bandwidth_gbs":
        if phy_ax is not None:
            phys = list(phy_ax.values)
            has_phy_dim = True
        elif space.phy is not None:
            phys = [space.phy]
            has_phy_dim = False
        else:
            raise ValueError(
                "the 'sim_bandwidth_gbs' metric threads the PHY's raw "
                "link bandwidth into the simulated efficiency — add a "
                "'phy' axis or pass DesignSpace(phy=...)")
        raw = np.asarray([p.raw_bandwidth_gbs for p in phys], np.float32)
        phy_names: Tuple[str, ...] = tuple(p.name for p in phys)
    else:
        phys, has_phy_dim, phy_names = None, False, ("-",)
        raw = np.ones(1, np.float32)
    n_phys = raw.shape[0]

    # -- flat cell space: [protocol_param x backlog x mix...] ------------
    dims_all = ["protocol_param", "backlog"] + list(mix_dims)
    sizes = [len(perts), backlogs.shape[0]]
    present = [pert_ax is not None, bl_ax is not None]
    if mix_dims:
        sizes += list(mix_shape)
        present += [True] * len(mix_dims)
    order = _cell_order(dims_all, present, stream.axis_order)
    shape_perm = tuple(sizes[i] for i in order)
    n_cells = int(np.prod(shape_perm))
    mesh, devices, chunk, step, n_dispatch = _dispatch_plan(
        n_cells, stream, shape_perm)

    # perturbation-major parameter stacks (row = q * P_fam + key index —
    # exactly simulate_grid's layout), gathered host-side per chunk
    p_sym, p_asym = len(sym_keys), len(asym_keys)
    sym_host = jax.tree_util.tree_map(np.asarray, flitsim.
                                      SymmetricFlitParams.stack(
                                          [flitsim.SYMMETRIC_PARAMS[k]
                                           .perturbed(p)
                                           for p in perts
                                           for k in sym_keys]))
    asym_host = jax.tree_util.tree_map(np.asarray, flitsim.
                                       AsymmetricLaneParams.stack(
                                           [flitsim.ASYMMETRIC_PARAMS[k]
                                            .perturbed(p)
                                            for p in perts
                                            for k in asym_keys]))
    col_src = [("sym", sym_keys.index(k)) if k in flitsim.SYMMETRIC_PARAMS
               else ("asym", asym_keys.index(k)) for k in keys]
    n_protocols = len(keys)
    n_flits, n_accesses = int(space.n_flits), int(space.n_accesses)
    spec_c, spec_r = PartitionSpec("chunks"), PartitionSpec()

    def chunk_fn(sym_cells, sxs, sys_, sbs, asym_cells, axs, ays, raw_in,
                 valid):
        def body(sym_cells, sxs, sys_, sbs, asym_cells, axs, ays, raw_in,
                 valid):
            eff_by = {}
            if p_sym:
                eff_by["sym"] = flitsim._symmetric_cells_grid(
                    sym_cells, sxs, sys_, sbs,
                    n_flits=n_flits).reshape(chunk, p_sym)
            if p_asym:
                eff_by["asym"] = flitsim._asymmetric_cells_grid(
                    asym_cells, axs, ays,
                    n_accesses=n_accesses).reshape(chunk, p_asym)
            eff = jnp.stack([eff_by[fam][:, i] for fam, i in col_src],
                            axis=1)                         # [C, P]
            m = eff[:, None, :] * raw_in[None, :, None]     # [C, F, P]
            codes = jnp.argmax(m, axis=2).astype(jnp.int32)
            ok = (valid > 0)[:, None, None]
            onehot = codes[..., None] == jnp.arange(n_protocols,
                                                    dtype=jnp.int32)
            counts = jnp.sum((onehot & ok).astype(jnp.int32),
                             axis=0)                        # [F, P]
            best = jnp.max(jnp.where(ok, m, -jnp.inf),
                           axis=(0, 1))                     # [P]
            counts = jax.lax.psum(counts, "chunks")
            best = jax.lax.pmax(best, "chunks")
            return codes, counts, best

        sharded = _shard_map(
            body, mesh=mesh,
            in_specs=(spec_c, spec_c, spec_c, spec_c,
                      spec_c, spec_c, spec_c, spec_r, spec_c),
            out_specs=(spec_c, spec_r, spec_r))
        return sharded(sym_cells, sxs, sys_, sbs, asym_cells, axs, ays,
                       raw_in, valid)

    key = ("sim", keys, chunk, devices, n_phys, n_flits, n_accesses,
           sim.key())
    misses0 = _stream_misses()
    codes_out = np.empty((n_cells, n_phys), np.int16)
    counts_total = np.zeros((n_phys, n_protocols), np.int64)
    best_total = np.full((n_protocols,), -np.inf, np.float64)
    a_sym = np.arange(p_sym, dtype=np.int64)
    a_asym = np.arange(p_asym, dtype=np.int64)
    prog = None
    prefetch = int(stream.prefetch)
    t0 = time.perf_counter()
    marshal_s = overlap_s = 0.0
    inflight: Any = collections.deque()    # FIFO of (lo, live, results)

    def retire():
        # the ONE audited host sync of the dispatch loop: the OLDEST
        # in-flight chunk blocks here, so folds run in sequential order
        lo, live, (codes, counts, best) = inflight.popleft()
        # repro-lint: disable=RL004  (audited FIFO retire sync)
        codes_np, counts_np, best_np = (np.asarray(codes),
                                        np.asarray(counts),
                                        np.asarray(best))
        codes_out[lo:lo + live] = codes_np[:live]
        counts_total[...] += counts_np.astype(np.int64)
        np.maximum(best_total, best_np.astype(np.float64),
                   out=best_total)

    for t in range(n_dispatch):
        m0 = time.perf_counter()
        lo = t * step
        ids, valid, live = _chunk_ids(lo, step, n_cells)
        multi = np.unravel_index(ids, shape_perm)
        by_dim = {dims_all[order[j]]: multi[j]
                  for j in range(len(order))}
        q_idx = by_dim["protocol_param"]
        b_idx = by_dim["backlog"]
        if mix_dims:
            m_idx = np.ravel_multi_index(
                tuple(by_dim[d] for d in mix_dims), mix_shape)
        else:
            m_idx = np.zeros(step, np.int64)
        rows_sym = (q_idx[:, None] * p_sym + a_sym).reshape(-1)
        rows_asym = (q_idx[:, None] * p_asym + a_asym).reshape(-1)
        args = (
            jax.tree_util.tree_map(lambda l: l[rows_sym], sym_host),
            np.repeat(xf[m_idx], p_sym), np.repeat(yf[m_idx], p_sym),
            np.repeat(backlogs[b_idx], p_sym),
            jax.tree_util.tree_map(lambda l: l[rows_asym], asym_host),
            np.repeat(xf[m_idx], p_asym), np.repeat(yf[m_idx], p_asym),
            raw, valid)
        dm = time.perf_counter() - m0
        marshal_s += dm
        if inflight:                # marshalled while a chunk was in flight
            overlap_s += dm
        if prog is None:
            prog = space_mod.cached_program("stream.sim", key, chunk_fn,
                                            args)
        inflight.append((lo, live, prog(*args)))
        while len(inflight) >= prefetch:
            retire()
    while inflight:
        retire()
    flitsim._record_stream(
        "stream.sim", dispatches=n_dispatch, prefetch=prefetch,
        pad_cells=n_dispatch * step - n_cells,
        overlap_frac=overlap_s / marshal_s if marshal_s else 0.0,
        cells=n_cells, elapsed_s=time.perf_counter() - t0,
        marshal_s=marshal_s)

    pert_labels = (tuple(pert_ax.labels) if pert_ax is not None
                   else ("baseline",))
    bl_labels = (tuple(bl_ax.labels) if bl_ax is not None
                 else (space.default_backlog,))
    full = [("protocol_param", pert_ax is not None, pert_labels),
            ("phy", has_phy_dim, phy_names),
            ("backlog", bl_ax is not None, bl_labels)]
    full += [(d, True, tuple(space.axes[d].labels)) for d in mix_dims]
    winners = _winner_array(codes_out, shape_perm, order, full,
                            np.asarray(keys, dtype=object))
    per_label = counts_total.sum(axis=0)
    return StreamResult(
        metric=metric, reduce_dim="protocol", mode="max", labels=keys,
        winners=winners,
        win_counts={k: int(per_label[i]) for i, k in enumerate(keys)},
        best_by_label={k: float(best_total[i])
                       for i, k in enumerate(keys)},
        n_cells=n_cells * n_phys, n_stream_cells=n_cells,
        n_dispatches=n_dispatch, chunk_cells=chunk,
        peak_cells_per_chunk=chunk * n_phys, devices=devices,
        compiles=_stream_misses() - misses0)


# =========================================================================
# Analytic catalog metrics (stream.catalog family)
# =========================================================================


def _knee_admissibility(space, items, cons, sim):
    """``[S, K]`` backlog-knee admissibility + the cell dim ``K`` indexes
    (``None`` = broadcast) — mirror of ``SpaceResult._knee_mask``."""
    from repro.core import flitsim
    from repro.core import selector as selector_mod
    keys = [k for k, _ in items]
    simkeys = [selector_mod.sim_key_for(k) for k in keys]
    budget = cons.max_backlog_knee
    if budget is None:
        return np.ones((len(keys), 1), bool), None
    cfg = space.axes.get("workload_config")
    mix_ax = space.axes.mix_axis()
    if cfg is not None:
        mixes = [(w.x, w.y) for _, w in cfg.values]
        dim = "workload_config"
    elif mix_ax is not None and space_mod.OWN_MIX not in mix_ax.values:
        if mix_ax.name == "read_fraction":
            mixes = [(100.0 * r, 100.0 - 100.0 * r)
                     for r in mix_ax.values]
        else:
            mixes = list(mix_ax.values)
        dim = mix_ax.name
    else:
        knees = selector_mod._default_knees()
        sub = np.asarray([sk is None or knees[sk] <= budget
                          for sk in simkeys], bool)
        return sub[:, None], None
    per = flitsim.backlog_knees(mixes=mixes, per_mix=True, sim=sim)
    sub = np.ones((len(keys), len(mixes)), bool)
    for i, sk in enumerate(simkeys):
        if sk is not None:
            sub[i] = per[sk] <= budget
    return sub, dim


def _stream_catalog(space, metric: str, sim, stream) -> StreamResult:
    from repro.core import memsys
    from repro.core import selector as selector_mod
    if (space.axes.get("catalog_param") is not None
            or space.axes.get("phy") is not None
            or space.phy is not None):
        raise ValueError(
            "streaming analytic evaluation covers the (workload_config, "
            "mix/read_fraction, shoreline_mm) cell axes over the default "
            "or custom catalog; catalog_param / phy axes run through the "
            "materialized evaluate() path")
    items = (memsys.default_catalog_items() if space.catalog is None
             else tuple(space.catalog.items()))
    keys = tuple(k for k, _ in items)
    systems = tuple(ms for _, ms in items)
    n_systems = len(items)
    mode = stream.mode if stream.mode is not None else (
        "min" if metric in ("pj_per_bit", "power_w") else "max")
    x, y, mix_dims = space._mix_arrays()
    mix_shape = x.shape
    xf = np.asarray(x, np.float32).reshape(-1)
    yf = np.asarray(y, np.float32).reshape(-1)
    sl_ax = space.axes.get("shoreline_mm")
    sls = np.asarray(sl_ax.values if sl_ax is not None
                     else [space.default_shoreline_mm], np.float32)

    dims_all = list(mix_dims) + ["shoreline_mm"]
    sizes = (list(mix_shape) if mix_dims else []) + [sls.shape[0]]
    present = [True] * len(mix_dims) + [sl_ax is not None]
    if not mix_dims:
        dims_all, sizes, present = (["shoreline_mm"], [sls.shape[0]],
                                    [sl_ax is not None])
    order = _cell_order(dims_all, present, stream.axis_order)
    shape_perm = tuple(sizes[i] for i in order)
    n_cells = int(np.prod(shape_perm))
    mesh, devices, chunk, step, n_dispatch = _dispatch_plan(
        n_cells, stream, shape_perm)

    cons = stream.constraints
    if cons is None:
        static = np.ones(n_systems, bool)
        knee_adm, knee_dim = np.ones((n_systems, 1), bool), None
        thr = np.asarray([np.inf, -np.inf], np.float32)
    else:
        static = np.asarray(selector_mod.system_mask(
            items, dataclasses.replace(cons, max_backlog_knee=None)),
            bool)
        knee_adm, knee_dim = _knee_admissibility(space, items, cons, sim)
        thr = np.asarray(
            [_le_threshold_f32(cons.max_power_w)
             if cons.max_power_w is not None else np.float32(np.inf),
             _ge_threshold_f32(cons.required_bandwidth_gbs)
             if cons.required_bandwidth_gbs is not None
             else np.float32(-np.inf)], np.float32)

    spec_c, spec_r = PartitionSpec("chunks"), PartitionSpec()
    is_max = mode == "max"
    fill = np.float32(-np.inf if is_max else np.inf)

    def chunk_fn(xs, ys, sls_c, adm, thr_in, valid):
        def body(xs, ys, sls_c, adm, thr_in, valid):
            bw = jnp.stack([ms.bandwidth_gbs(xs, ys, sls_c)
                            for ms in systems])             # [S, C]
            pjb = jnp.stack([jnp.broadcast_to(ms.pj_per_bit(xs, ys),
                                              bw.shape[1:])
                             for ms in systems])
            pw = bw * 8.0 * pjb / 1000.0        # GB/s * pJ/b -> W
            gpw = jnp.where(pw > 0, bw / pw, jnp.inf)
            vals = {"bandwidth_gbs": bw, "pj_per_bit": pjb,
                    "power_w": pw, "gbs_per_watt": gpw}[metric]
            ok = (adm.T > 0) & (pw <= thr_in[0]) & (bw >= thr_in[1])
            masked = jnp.where(ok, vals, fill)
            codes = (jnp.argmax if is_max else jnp.argmin)(
                masked, axis=0).astype(jnp.int32)           # [C]
            any_ok = jnp.any(ok, axis=0)
            codes = jnp.where(any_ok, codes, -1)
            vcell = valid > 0
            onehot = codes[:, None] == jnp.arange(n_systems,
                                                  dtype=jnp.int32)
            counts = jnp.sum((onehot & vcell[:, None]).astype(jnp.int32),
                             axis=0)                        # [S]
            none_ct = jnp.sum((vcell & ~any_ok).astype(jnp.int32))
            best = (jnp.max if is_max else jnp.min)(
                jnp.where(ok & vcell[None, :], vals, fill), axis=1)
            counts = jax.lax.psum(counts, "chunks")
            none_ct = jax.lax.psum(none_ct, "chunks")
            best = (jax.lax.pmax if is_max else jax.lax.pmin)(
                best, "chunks")
            return codes, counts, best, none_ct

        sharded = _shard_map(
            body, mesh=mesh,
            in_specs=(spec_c, spec_c, spec_c, spec_c, spec_r, spec_c),
            out_specs=(spec_c, spec_r, spec_r, spec_r))
        return sharded(xs, ys, sls_c, adm, thr_in, valid)

    key = ("catalog", items, chunk, devices, metric, mode,
           stream.key()[-1])           # constraint STRUCTURE is static
    misses0 = _stream_misses()
    codes_out = np.empty(n_cells, np.int16)
    counts_total = np.zeros(n_systems, np.int64)
    none_total = np.zeros((), np.int64)
    best_total = np.full(n_systems, -np.inf if is_max else np.inf,
                         np.float64)
    prog = None
    prefetch = int(stream.prefetch)
    t0 = time.perf_counter()
    marshal_s = overlap_s = 0.0
    inflight: Any = collections.deque()    # FIFO of (lo, live, results)
    acc = np.maximum if is_max else np.minimum

    def retire():
        # the ONE audited host sync of the dispatch loop: the OLDEST
        # in-flight chunk blocks here, so folds run in sequential order
        lo, live, (codes, counts, best, none_ct) = inflight.popleft()
        # repro-lint: disable=RL004  (audited FIFO retire sync)
        codes_np, counts_np, best_np, none_np = (
            np.asarray(codes), np.asarray(counts), np.asarray(best),
            np.asarray(none_ct))
        codes_out[lo:lo + live] = codes_np[:live]
        counts_total[...] += counts_np.astype(np.int64)
        none_total[...] += np.int64(none_np)
        acc(best_total, best_np.astype(np.float64), out=best_total)

    for t in range(n_dispatch):
        m0 = time.perf_counter()
        lo = t * step
        ids, valid, live = _chunk_ids(lo, step, n_cells)
        multi = np.unravel_index(ids, shape_perm)
        by_dim = {dims_all[order[j]]: multi[j]
                  for j in range(len(order))}
        l_idx = by_dim["shoreline_mm"]
        if mix_dims:
            m_idx = np.ravel_multi_index(
                tuple(by_dim[d] for d in mix_dims), mix_shape)
        else:
            m_idx = np.zeros(step, np.int64)
        k_idx = by_dim[knee_dim] if knee_dim is not None else \
            np.zeros(step, np.int64)
        adm = (static[None, :]
               & knee_adm[:, k_idx].T).astype(np.int32)     # [step, S]
        args = (xf[m_idx], yf[m_idx], sls[l_idx], adm, thr, valid)
        dm = time.perf_counter() - m0
        marshal_s += dm
        if inflight:                # marshalled while a chunk was in flight
            overlap_s += dm
        if prog is None:
            prog = space_mod.cached_program("stream.catalog", key,
                                            chunk_fn, args)
        inflight.append((lo, live, prog(*args)))
        while len(inflight) >= prefetch:
            retire()
    while inflight:
        retire()
    from repro.core import flitsim
    flitsim._record_stream(
        "stream.catalog", dispatches=n_dispatch, prefetch=prefetch,
        pad_cells=n_dispatch * step - n_cells,
        overlap_frac=overlap_s / marshal_s if marshal_s else 0.0,
        cells=n_cells, elapsed_s=time.perf_counter() - t0,
        marshal_s=marshal_s)

    full = [(d, True, tuple(space.axes[d].labels)) for d in mix_dims]
    sl_labels = (tuple(sl_ax.labels) if sl_ax is not None
                 else (space.default_shoreline_mm,))
    full += [("shoreline_mm", sl_ax is not None, sl_labels)]
    winners = _winner_array(codes_out, shape_perm, order, full,
                            np.asarray(keys + ("(none)",), dtype=object))
    win_counts = {k: int(counts_total[i]) for i, k in enumerate(keys)}
    if cons is not None:
        win_counts["(none)"] = int(none_total)
    fill64 = np.float64(fill)
    return StreamResult(
        metric=metric, reduce_dim="system", mode=mode, labels=keys,
        winners=winners, win_counts=win_counts,
        best_by_label={k: (float(best_total[i])
                           if best_total[i] != fill64 else float("nan"))
                       for i, k in enumerate(keys)},
        n_cells=n_cells, n_stream_cells=n_cells,
        n_dispatches=n_dispatch, chunk_cells=chunk,
        peak_cells_per_chunk=chunk, devices=devices,
        compiles=_stream_misses() - misses0)


def _stream_misses() -> int:
    return space_mod.cache_stats(space_mod.STREAM_FAMILIES).misses


def stream_evaluate(space, metrics, sim, stream) -> StreamResult:
    """Dispatch one streamed metric reduction (the ``stream=`` path of
    :meth:`repro.core.space.DesignSpace.evaluate`)."""
    if metrics is None:
        raise ValueError(
            "streaming evaluation reduces exactly ONE metric per call; "
            "pass metrics=('sim_efficiency',) (or another single metric) "
            "explicitly")
    if isinstance(metrics, str):
        metric = metrics
    else:
        wanted = tuple(metrics)
        if len(wanted) != 1:
            raise ValueError(
                "streaming evaluation reduces exactly ONE metric per "
                f"call, got {wanted}; run one stream per metric "
                "(executables are cached per chunk shape, so repeats "
                "reuse the warm program)")
        metric = wanted[0]
    sim = sim if sim is not None else space_mod.FIXED_SIM
    for name in ("trace", "k", "ucie_line_ui", "device_line_ui"):
        if space.axes.get(name) is not None:
            raise ValueError(
                f"streaming evaluation does not cover the {name!r} axis "
                "yet; use the materialized evaluate() path")
    if metric in STREAM_SIM_METRICS:
        if stream.constraints is not None:
            raise ValueError(
                "StreamConfig.constraints stream through the analytic "
                "metrics only; the simulated frontier mirrors the "
                "materialized unconstrained argbest")
        return _stream_sim(space, metric, sim, stream)
    if metric in space_mod.ANALYTIC_METRICS:
        return _stream_catalog(space, metric, sim, stream)
    raise ValueError(
        f"metric {metric!r} is not streamable; choose from "
        f"{STREAM_SIM_METRICS + space_mod.ANALYTIC_METRICS}")
