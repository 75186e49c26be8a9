"""Fused flit-simulator chunk kernels — Pallas.

One ``pallas_call`` advances every cell of a ``[rows, cells]`` tile a
whole chunk of cycles and re-evaluates the report / drift / convergence
summaries in-kernel, so the host sees ONE launch per chunk instead of the
~chunk dispatched ops of the XLA ``lax.scan`` cores.  The per-cell core
state (queues, credit pools, lane clocks, the asymmetric observation
window) stays on-chip for the whole chunk as the ``fori_loop`` carry —
it never round-trips through HBM between cycles; only the chunk-boundary
state/report rows are written back.

The compute bodies are shared verbatim with the pure-jnp oracle
(:mod:`repro.kernels.flit_sim.ref`), so kernel-vs-ref agreement is by
construction; the grid/BlockSpec plumbing here only tiles the cell axis.

Cells are padded to a multiple of the 128-lane tile (`pad_cells`); pad
cells replicate cell 0 so they converge identically and never gate an
early exit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flit_sim.ref import (
    ASYM_ROWS, PIPE_ROWS, SCAL_COLS, SYM_PERIODIC_ROWS, SYM_ROWS,
    asymmetric_periodic_compute, pipelining_chunk_compute,
    symmetric_chunk_compute, symmetric_periodic_compute,
)

#: cell-axis tile: one lane row minimum, a few VPU rows maximum — the
#: state working set per tile stays well under VMEM either way
MAX_TILE = 8192
LANE = 128

#: the periodic observers hold PERIOD_WINDOW-row bands per tile (4 for
#: asymmetric, 8 for symmetric), so their cell tiles are capped lower than
#: the chunk kernels' to fit v5e's 16 MiB of scoped VMEM: asymmetric
#: needs 21.0 MiB at 8192, symmetric 21.3 MiB at 4096
ASYM_PERIODIC_MAX_TILE = 4096
SYM_PERIODIC_MAX_TILE = 2048


def tile_for(cells: int, max_tile: int = MAX_TILE) -> tuple:
    """(tile, padded cell count) for a cell axis of ``cells``."""
    pad = -(-max(cells, 1) // LANE) * LANE
    tile = min(max_tile, pad)
    pad = -(-pad // tile) * tile
    return tile, pad


def pad_cells(rows: jnp.ndarray, padded: int) -> jnp.ndarray:
    """Pad the cell axis to ``padded`` columns by replicating cell 0."""
    short = padded - rows.shape[1]
    if short <= 0:
        return rows
    return jnp.concatenate(
        [rows, jnp.broadcast_to(rows[:, :1], (rows.shape[0], short))],
        axis=1)


def _row_specs(tile: int, row_counts, n_scal: int):
    """BlockSpecs: one [rows, tile] block per stacked operand plus the
    broadcast [1, SCAL_COLS] scalar rows."""
    specs = [pl.BlockSpec((r, tile), lambda i: (0, i)) for r in row_counts]
    specs += [pl.BlockSpec((1, SCAL_COLS), lambda i: (0, 0))] * n_scal
    return specs


def _sym_kernel(params_ref, state_ref, hist_ref, scal_ref, out_ref, *,
                chunk: int):
    out_ref[...] = symmetric_chunk_compute(
        params_ref[...], state_ref[...], hist_ref[...], scal_ref[...],
        chunk=chunk)


def symmetric_chunk(params, state, hist, scal, *, chunk: int, tile: int,
                    interpret: bool = False):
    """One adaptive symmetric chunk over padded ``[SYM_ROWS, C]`` rows."""
    c = params.shape[1]
    return pl.pallas_call(
        functools.partial(_sym_kernel, chunk=chunk),
        grid=(c // tile,),
        in_specs=_row_specs(tile, (SYM_ROWS, SYM_ROWS, SYM_ROWS), 1),
        out_specs=pl.BlockSpec((SYM_ROWS, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((SYM_ROWS, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(params, state, hist, scal)


def _asym_kernel(params_ref, out_ref, *, n_accesses: int):
    out_ref[...] = asymmetric_periodic_compute(
        params_ref[...], n_accesses=n_accesses)


def asymmetric_periodic(params, *, n_accesses: int, tile: int,
                        interpret: bool = False):
    """Whole asymmetric grid in ONE launch: observe ~2 periods, detect
    the credit period, extrapolate the lane clocks to the horizon."""
    c = params.shape[1]
    return pl.pallas_call(
        functools.partial(_asym_kernel, n_accesses=n_accesses),
        grid=(c // tile,),
        in_specs=_row_specs(tile, (ASYM_ROWS,), 0),
        out_specs=pl.BlockSpec((ASYM_ROWS, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((ASYM_ROWS, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(params)


def _sym_periodic_kernel(params_ref, out_ref, *, n_flits: int):
    out_ref[...] = symmetric_periodic_compute(
        params_ref[...], n_flits=n_flits)


def symmetric_periodic(params, *, n_flits: int, tile: int,
                       interpret: bool = False):
    """Whole symmetric grid in ONE launch: observe the pool-state window,
    detect exact f32 state periods, extrapolate the warm-window delivery
    sum bitwise to the horizon."""
    c = params.shape[1]
    return pl.pallas_call(
        functools.partial(_sym_periodic_kernel, n_flits=n_flits),
        grid=(c // tile,),
        in_specs=_row_specs(tile, (SYM_ROWS,), 0),
        out_specs=pl.BlockSpec((SYM_PERIODIC_ROWS, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((SYM_PERIODIC_ROWS, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(params)


def _pipe_kernel(params_ref, state_ref, hist_ref, scal_ref, out_ref, *,
                 chunk: int):
    out_ref[...] = pipelining_chunk_compute(
        params_ref[...], state_ref[...], hist_ref[...], scal_ref[...],
        chunk=chunk)


def pipelining_chunk(params, state, hist, scal, *, chunk: int, tile: int,
                     interpret: bool = False):
    """One adaptive Fig-13 pipelining chunk over padded rows."""
    c = params.shape[1]
    return pl.pallas_call(
        functools.partial(_pipe_kernel, chunk=chunk),
        grid=(c // tile,),
        in_specs=_row_specs(tile, (PIPE_ROWS, PIPE_ROWS, ASYM_ROWS), 1),
        out_specs=pl.BlockSpec((PIPE_ROWS, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((PIPE_ROWS, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(params, state, hist, scal)
