"""Backend dispatch for the fused flit-simulator kernels.

Real Pallas lowering on TPU; ``interpret=True`` everywhere else (the
interpret path traces to ordinary XLA ops, so the CPU tier-1 suite runs
the exact kernel bodies with no TPU in sight).  ``interpret=None`` in the
launch helpers means "auto" — callers (the flitsim runners, the tests)
can still force either mode explicitly.

The ``*_launch`` functions are the jit targets the engine's shared
compile cache (:func:`repro.core.space.cached_program`) memoizes: each is
one device program per adaptive chunk (symmetric / pipelining) or per
whole run (asymmetric periodic), returning the packed state rows plus the
convergence/detection flags the host loop reads back (one scalar-sized
sync per launch).
"""
from __future__ import annotations

import jax

from repro.kernels.flit_sim import kernel as _k
from repro.kernels.flit_sim.ref import (  # noqa: F401  (re-exported)
    ASYM_ROWS, PERIOD_EPS, PERIOD_MAX, PERIOD_OBS, PIPE_MAX_K, PIPE_ROWS,
    SCAL_COLS, SYM_PERIOD_OBS, SYM_PERIODIC_ROWS, SYM_ROWS,
)

pad_cells = _k.pad_cells
tile_for = _k.tile_for
ASYM_PERIODIC_MAX_TILE = _k.ASYM_PERIODIC_MAX_TILE
SYM_PERIODIC_MAX_TILE = _k.SYM_PERIODIC_MAX_TILE


def default_interpret() -> bool:
    """Interpret (trace-to-XLA) everywhere but TPU."""
    return jax.default_backend() != "tpu"


def _resolve(interpret):
    return default_interpret() if interpret is None else bool(interpret)


def symmetric_chunk_launch(params, state, hist, scal, *, chunk: int,
                           tile: int, cells: int, interpret=None):
    """One symmetric chunk; returns (state_rows, conv flags [cells])."""
    out = _k.symmetric_chunk(params, state, hist, scal, chunk=chunk,
                             tile=tile, interpret=_resolve(interpret))
    return out, out[11, :cells] > 0.5


def asymmetric_periodic_launch(params, *, n_accesses: int, tile: int,
                               cells: int, interpret=None):
    """One-launch periodic run; returns (out_rows, detected [cells])."""
    out = _k.asymmetric_periodic(params, n_accesses=n_accesses, tile=tile,
                                 interpret=_resolve(interpret))
    return out, out[1, :cells] > 0.5


def symmetric_periodic_launch(params, *, n_flits: int, tile: int,
                              cells: int, interpret=None):
    """One-launch periodic run; returns (out_rows, detected [cells])."""
    out = _k.symmetric_periodic(params, n_flits=n_flits, tile=tile,
                                interpret=_resolve(interpret))
    return out, out[1, :cells] > 0.5


def pipelining_chunk_launch(params, state, hist, scal, *, chunk: int,
                            tile: int, cells: int, interpret=None):
    """One pipelining chunk; returns (state_rows, conv flags [cells])."""
    out = _k.pipelining_chunk(params, state, hist, scal, chunk=chunk,
                              tile=tile, interpret=_resolve(interpret))
    return out, out[11, :cells] > 0.5
