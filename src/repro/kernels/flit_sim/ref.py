"""Period-exact probe bodies of the adaptive flit simulators.

The adaptive runners in :mod:`repro.core.flitsim` compile these two
bodies as their probe programs (``_run_asymmetric_periodic`` and
``_run_symmetric_periodic``): one launch observes a short window of the
simulator, certifies each cell's period and extrapolates its report to
the full horizon; the cells it cannot certify are escalated by the
caller.

Both contracts work on ROW-STACKED f32 arrays ``[rows, cells]`` (cells
last, so the cell axis maps onto the TPU lanes).  The row layouts:

symmetric ``params`` [16, C] (pad rows zero)::

    0..10  SymmetricFlitParams fields in dataclass order
           (g_slots .. write_buffer_lines)
    11 x   12 y   13 backlog

asymmetric ``params`` [8, C]: AsymmetricLaneParams fields in dataclass
order (total_lanes .. access_bits) then 6 x, 7 y.

Both outputs are [8, C]: 0 rep, 1 detected, 2 period (pad rows zero).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.flitsim import (
    AsymmetricLaneParams, SymmetricFlitParams, _asymmetric_stepfn,
    _symmetric_stepfn,
)

#: rows per stacked operand (f32 sublane multiple)
SYM_ROWS = 16
ASYM_ROWS = 8

#: largest credit-cycle denominator the period detector resolves; the
#: observation run is ~2 such periods (warm prefix + one full window)
PERIOD_MAX = 64
PERIOD_WINDOW = PERIOD_MAX + 1
PERIOD_WARM = PERIOD_MAX - 1
#: sequential steps the periodic observer executes
PERIOD_OBS = PERIOD_WARM + PERIOD_WINDOW
#: credit-phase match tolerance — true-period matches differ only by f32
#: accumulation noise (~1e-5 over the window) while non-matches differ by
#: a multiple of 1/PERIOD_MAX >= 1.5e-2
PERIOD_EPS = 1e-4

#: symmetric periodic detector: same warm/window geometry as the
#: asymmetric one, but the match predicate is EXACT f32 equality of the
#: whole 7-component pool/credit core against the lagged observation row
#: (plus an integer-valued delivery window, so every f32 sum below is
#: exact) — a state match is a trajectory certificate, so extrapolation
#: is bit-identical to the fixed engine wherever the cell detects
SYM_PERIOD_OBS = PERIOD_WARM + PERIOD_WINDOW
#: output rows of the symmetric periodic contract (0 rep, 1 detected,
#: 2 period; pad rows zero)
SYM_PERIODIC_ROWS = 8
#: probe-attempt gate: saturated pools re-round the proportional
#: read/write split every step, so their state period always exceeds
#: PERIOD_MAX and the observation probe is guaranteed wasted work (an
#: extra compiled program + SYM_PERIOD_OBS cycles).  Grids whose max
#: backlog exceeds this skip straight to the chunked core.  Purely a
#: cost heuristic — detection itself stays an exact state match.
SYM_PERIODIC_MAX_BACKLOG = 4.0


# -- periodic observation helpers --------------------------------------------
# Written with what Mosaic lowers: one-hot masked window writes instead of
# dynamic_update_slice, and a min over a lag iota instead of reversed rows,
# cumsum or an argmax over the sublane axis.


def _observe(step, core, cells: int):
    """Run ``step`` PERIOD_WINDOW times from ``core``; returns the final
    core and one ``[PERIOD_WINDOW, cells]`` band per core component, row
    ``i`` holding that component after observed step ``i``."""
    W = PERIOD_WINDOW
    rows = jax.lax.broadcasted_iota(jnp.int32, (W, cells), 0)

    def obs(i, carry):
        core, bands = carry
        core = step(core)
        hit = rows == i
        return core, tuple(jnp.where(hit, v[None, :], b)
                           for v, b in zip(core, bands))

    bands0 = tuple(jnp.zeros((W, cells), jnp.float32) for _ in core)
    return jax.lax.fori_loop(0, W, obs, (core, bands0))


def _lagged(band):
    """The PERIOD_MAX rows before the last one; row ``j`` lags the last
    row by ``d = PERIOD_MAX - j`` steps (see :func:`_lag_rows`)."""
    return band[PERIOD_WINDOW - 1 - PERIOD_MAX:PERIOD_WINDOW - 1]


def _lag_rows(cells: int):
    """``[PERIOD_MAX, cells]`` f32 lag ``d`` of each :func:`_lagged` row."""
    return (PERIOD_MAX - jax.lax.broadcasted_iota(
        jnp.int32, (PERIOD_MAX, cells), 0)).astype(jnp.float32)


def _smallest_lag(ok):
    """(smallest lag ``d`` whose row of ``ok`` holds — 1 where none does —
    as int32, detected flag) per cell.  Lags are small exact f32 ints."""
    lag = jnp.min(jnp.where(ok, _lag_rows(ok.shape[1]), float(PERIOD_WINDOW)),
                  axis=0)
    detected = lag <= PERIOD_MAX
    return jnp.where(detected, lag, 1.0).astype(jnp.int32), detected


def asymmetric_periodic_compute(params, *, n_accesses: int):
    """One-launch period-exact asymmetric evaluation.

    Runs the PERIOD_OBS-step observation (warm prefix, then a
    PERIOD_WINDOW ring of per-step lane/credit boundaries), detects each
    cell's credit period from the credit phase, and extrapolates every
    lane's busy time exactly to the full horizon:

        T_lane(N) = T(n0) + m * [T(n0) - T(n0 - d)]
                  + [T(n0 - d + r) - T(n0 - d)]        N - n0 = m*d + r

    Exact because the per-period lane increments repeat exactly (the
    credit state is periodic with denominator q = (x+y)/gcd when the mix
    is rational; d == q whenever q <= PERIOD_MAX).  Undetected cells
    (q > PERIOD_MAX, or irrational mixes) are flagged for exact
    escalation by the caller.
    """
    W = PERIOD_WINDOW
    cells = params.shape[1]
    p = AsymmetricLaneParams(*[params[i] for i in range(6)])
    x, y = params[6], params[7]
    step = _asymmetric_stepfn(p, x, y)

    core = tuple(jnp.zeros((cells,), jnp.float32) for _ in range(4))
    core = jax.lax.fori_loop(0, PERIOD_WARM, lambda _, c: step(c), core)

    # observation window: 4 W-row bands (t_read / t_write / t_cmd /
    # credit boundaries after each observed step)
    _, (tr, tw, tc, cr) = _observe(step, core, cells)

    # smallest lag d with matching credit phase; the credit alone
    # determines all future increments, so a phase match is a period
    ok = jnp.abs(cr[W - 1][None, :] - _lagged(cr)) < PERIOD_EPS
    d, detected = _smallest_lag(ok)

    rem = n_accesses - PERIOD_OBS
    m = rem // d
    r = rem - m * d
    rows = jax.lax.broadcasted_iota(jnp.int32, (W, cells), 0)
    sel_a = (rows == (W - 1 - d)[None, :]).astype(jnp.float32)
    sel_b = (rows == (W - 1 - d + r)[None, :]).astype(jnp.float32)

    def lane(t):
        t_cur = t[W - 1]
        t_a = jnp.sum(t * sel_a, axis=0)              # T(n0 - d)
        t_b = jnp.sum(t * sel_b, axis=0)              # T(n0 - d + r)
        return t_cur + m.astype(jnp.float32) * (t_cur - t_a) + (t_b - t_a)

    T = jnp.maximum(jnp.maximum(lane(tr), lane(tw)), lane(tc))
    rep = 512.0 * n_accesses / (params[0] * jnp.maximum(T, 1e-9))
    rep = jnp.where(detected, rep, 0.0)
    pad = jnp.zeros_like(rep)
    return jnp.stack([rep, detected.astype(jnp.float32),
                      jnp.where(detected, d, 0).astype(jnp.float32)]
                     + [pad] * (ASYM_ROWS - 3))


def symmetric_periodic_compute(params, *, n_flits: int):
    """One-launch period-exact symmetric evaluation.

    Runs the SYM_PERIOD_OBS-cycle observation (warm prefix, then a
    PERIOD_WINDOW ring of per-cycle core states and data-slot
    deliveries), detects each cell's pool-state period by EXACT f32
    equality of the full 7-component core against the lagged rows, and
    extrapolates the warm-window delivery sum in closed form to the full
    horizon::

        S(W0..N) = g(N - n0) - g(W0 - n0)
        g(M)     = (M // d) * P + C[M mod d]          n0 = SYM_PERIOD_OBS

    where ``P`` is the delivery sum over the last detected period of the
    window and ``C`` its prefix sums.  A state match is a trajectory
    certificate (the step map is state-only), so every future delivery
    repeats bit-for-bit; requiring the window deliveries to be
    integer-valued makes all the f32 sums above exact, and the report
    reproduces the fixed engine's sequential accumulation BITWISE.
    Undetected cells (aperiodic in f32, period > PERIOD_MAX, fractional
    deliveries, or still transient) are flagged for exact escalation by
    the caller.  Callers must keep ``n_flits // 4 >= SYM_PERIOD_OBS`` so
    the warm window opens after the observation ends.
    """
    W = PERIOD_WINDOW
    cells = params.shape[1]
    p = SymmetricFlitParams(*[params[i] for i in range(11)])
    x, y, backlog = params[11], params[12], params[13]
    step = _symmetric_stepfn(p, x, y, backlog)

    core = tuple(jnp.zeros((cells,), jnp.float32) for _ in range(7))
    core = jax.lax.fori_loop(0, PERIOD_WARM,
                             lambda _, c: step(c)[0], core)

    # observation window: 8 W-row bands — the 7 core components after
    # each observed cycle plus that cycle's data-slot delivery
    def step_nd(c):
        c, nd = step(c[:7])
        return c + (nd,)

    _, bands = _observe(step_nd, core + (jnp.zeros_like(core[0]),), cells)
    dwin = bands[7]

    # smallest lag d whose full core matches EXACTLY; the core alone
    # determines the whole future trajectory, so an exact match repeats
    # the delivery window verbatim forever
    ok = None
    for band in bands[:7]:
        eq = band[W - 1][None, :] == _lagged(band)
        ok = eq if ok is None else ok & eq
    # integer-delivery gate: all f32 partial sums of an integer window
    # below 2^24 are exact, so the closed form equals the fixed engine's
    # sequential fold bit-for-bit.  Lag d passes when the last d
    # deliveries are integers, i.e. d is below the distance from the end
    # of the window to its last fractional delivery (W when none is).
    rows = jax.lax.broadcasted_iota(jnp.int32, (W, cells), 0)
    frac_at = jnp.min(jnp.where(jnp.floor(dwin) == dwin, float(W),
                                (W - rows).astype(jnp.float32)), axis=0)
    ok = ok & (_lag_rows(cells) < frac_at[None, :])
    d, detected = _smallest_lag(ok)

    in_period = rows >= (W - d)[None, :]              # last d deliveries
    psum = jnp.sum(jnp.where(in_period, dwin, 0.0), axis=0)

    def g(M):                                         # M static >= 0
        m = M // d
        r = M - m * d
        pref = in_period & (rows < (W - d + r)[None, :])
        return (m.astype(jnp.float32) * psum
                + jnp.sum(jnp.where(pref, dwin, 0.0), axis=0))

    W0 = n_flits // 4
    S = g(n_flits - SYM_PERIOD_OBS) - g(W0 - SYM_PERIOD_OBS)

    # same expression order as flitsim._symmetric_efficiency
    data_bits = S * 128.0
    cap_bits = 2.0 * jnp.float32(n_flits - W0) * p.flit_bits
    rep = jnp.where(detected, data_bits / cap_bits, 0.0)
    pad = jnp.zeros_like(rep)
    return jnp.stack([rep, detected.astype(jnp.float32),
                      jnp.where(detected, d, 0).astype(jnp.float32)]
                     + [pad] * (SYM_PERIODIC_ROWS - 3))
