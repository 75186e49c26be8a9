"""Pure-jnp reference for the fused flit-simulator chunk contracts.

The Pallas kernels in :mod:`repro.kernels.flit_sim.kernel` and this
oracle share the compute bodies below verbatim — the kernel adds only the
tiling / ref plumbing — so kernel-vs-ref agreement is by construction and
the tests pin it bit-for-bit in ``interpret=True`` mode.

Every contract works on ROW-STACKED f32 arrays ``[rows, cells]`` (cells
last so the vector axis maps onto TPU lanes).  The row layouts:

symmetric ``params`` [16, C] (pad rows zero)::

    0..10  SymmetricFlitParams fields in dataclass order
           (g_slots .. write_buffer_lines)
    11 x   12 y   13 backlog

symmetric ``state`` [16, C] — also the chunk output layout::

    0..6   core (rq, wq, wdata, rdata, resp, cr, cw)
    7 D    cumulative data slots        8 TD   time-weighted sum(t * d_t)
    9 t    cycles simulated             10 rep  last report
    11 conv  convergence flag (output only)

symmetric ``hist`` [16, C] — chunk-boundary history rows the host gathers
from its per-chunk list (one launch per chunk keeps no cross-chunk
history on the device)::

    0..4   pools (rq, wq, wdata, rdata, resp) at chunk max(k-3, 0)
    5 D_m  6 TD_m  7 D_mid  8 TD_mid   (zeros when m == k / mid == k:
           the kernel substitutes the freshly computed accumulators)
    9 D_K0 (zeros when k <= K0)

symmetric ``scal`` [1, 128] broadcast scalars::

    0 k  1 m  2 mid  3 K0  4 K  5 chunk  6 tol
    7 exit_ok (k >= min_k and k > drift span)   8 at_horizon (k == K)
    9 drift_tol (slots / chunk)

asymmetric ``params`` [8, C]: AsymmetricLaneParams fields in dataclass
order (total_lanes .. access_bits) then 6 x, 7 y.  Output [8, C]:
0 rep, 1 detected, 2 period.

symmetric periodic: input is the symmetric ``params`` [16, C] stack;
output [8, C]: 0 rep, 1 detected, 2 period (pad rows zero).

pipelining ``params`` [8, C]: 0 k_devices, 1 ucie_line_ui,
2 device_line_ui.  ``state`` [16, C]: 0..7 dev_ready (padded to 8
devices), 8 link_free, 9 idx, 10 rep; output adds 11 conv.  ``hist``
[8, C]: 0 T1 (link free time after chunk 1; zeros at k == 1).  ``scal``
[1, 128]: 0 k, 1 K, 2 chunk, 3 tol, 4 exit_ok, 5 at_horizon, 6 n_lines.
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
PIPE_ROWS = 16
#: broadcast-scalar operand shape (one full lane row)
SCAL_COLS = 128

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

#: device-ready table width shared with flitsim._PIPELINING_PAD_K
PIPE_MAX_K = 8

#: drift-guard pool-snapshot span (mirrors flitsim._DRIFT_SPAN)
DRIFT_SPAN = 3.0


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


def symmetric_chunk_compute(params, state, hist, scal, *, chunk: int):
    """Advance every cell ``chunk`` cycles and re-evaluate report + drift
    + convergence — the whole per-chunk body of the adaptive symmetric
    core, one launch worth of work.  All operands/results row-stacked."""
    p = SymmetricFlitParams(*[params[i] for i in range(11)])
    x, y, backlog = params[11], params[12], params[13]
    step = _symmetric_stepfn(p, x, y, backlog)
    core = tuple(state[i] for i in range(7))
    D, TD, t = state[7], state[8], state[9]
    rep_prev = state[10]

    def body(_, carry):
        core, D, TD, t = carry
        core, nd = step(core)
        t = t + 1.0
        return core, D + nd, TD + t * nd, t

    core, D, TD, t = jax.lax.fori_loop(
        0, chunk, body, (core, D, TD, t))

    kf, mf, midf = scal[0, 0], scal[0, 1], scal[0, 2]
    K0f, Kf, ch = scal[0, 3], scal[0, 4], scal[0, 5]
    tol, exit_ok = scal[0, 6], scal[0, 7]
    at_hor, drift_tol = scal[0, 8], scal[0, 9]

    # report: triangular trailing-window mean blended with the observed
    # warm prefix — float transcription of flitsim's report()/
    # _tri_window_mean (chunk indices are small ints, exact in f32)
    denom = 2.0 * params[8] / 128.0
    D_m = jnp.where(mf == kf, D, hist[5])
    TD_m = jnp.where(mf == kf, TD, hist[6])
    D_mid = jnp.where(midf == kf, D, hist[7])
    TD_mid = jnp.where(midf == kf, TD, hist[8])
    b_i, b_m, b_j = mf * ch, midf * ch, kf * ch
    c1, c2 = b_m - b_i, b_j - b_m
    w_sum = c1 * (c1 + 1.0) / 2.0 + c2 * (c2 - 1.0) / 2.0
    num = ((TD_mid - TD_m) - b_i * (D_mid - D_m)
           + b_j * (D - D_mid) - (TD - TD_mid))
    mu = num / (jnp.maximum(w_sum, 1.0) * denom)
    wA = jnp.maximum(kf - K0f, 1.0) * ch
    A = (D - hist[9]) / (wA * denom)
    rep = jnp.where(kf > K0f,
                    (A * (kf - K0f) + mu * (Kf - kf)) / (Kf - K0f), mu)

    pools = jnp.stack(core[:5])
    drift = jnp.max(jnp.abs(pools - hist[0:5]), axis=0) / DRIFT_SPAN
    delta = jnp.abs(rep - rep_prev) / jnp.maximum(jnp.abs(rep), 1e-9)
    conv = (((delta <= tol) & (drift < drift_tol) & (exit_ok > 0.0))
            | (at_hor > 0.0)).astype(jnp.float32)

    pad = jnp.zeros_like(D)
    return jnp.stack(list(core) + [D, TD, t, rep, conv]
                     + [pad] * (SYM_ROWS - 12))


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


def pipelining_chunk_compute(params, state, hist, scal, *, chunk: int):
    """Per-chunk body of the adaptive Fig-13 pipelining core, row-stacked.

    The per-cell device rotation (``dev = idx % k``; read/update row
    ``dev`` of the ready table) is expressed as a one-hot mask over the
    padded PIPE_MAX_K ready rows so the whole tile advances with dense
    vector ops — no per-cell dynamic indexing."""
    kdev, ucie, dev_ui = params[0], params[1], params[2]
    dev_ready = state[0:PIPE_MAX_K]
    link_free, idx = state[PIPE_MAX_K], state[PIPE_MAX_K + 1]
    rep_prev = state[PIPE_MAX_K + 2]
    rows = jax.lax.broadcasted_iota(
        jnp.int32, (PIPE_MAX_K, dev_ready.shape[1]), 0).astype(jnp.float32)

    def body(_, carry):
        dev_ready, link_free, idx = carry
        # idx and k are small exact f32 ints, so the float modulo is exact
        dev = idx - jnp.floor(idx / kdev) * kdev
        sel = rows == dev[None, :]
        ready = jnp.sum(jnp.where(sel, dev_ready, 0.0), axis=0)
        start = jnp.maximum(ready, link_free)
        dev_ready = jnp.where(sel, start + dev_ui, dev_ready)
        return dev_ready, start + ucie, idx + 1.0

    dev_ready, link_free, idx = jax.lax.fori_loop(
        0, chunk, body, (dev_ready, link_free, idx))

    kf, Kf, ch = scal[0, 0], scal[0, 1], scal[0, 2]
    tol, exit_ok, at_hor = scal[0, 3], scal[0, 4], scal[0, 5]
    n_lines = scal[0, 6]
    T1 = jnp.where(kf == 1.0, link_free, hist[0])
    ahat = (link_free - T1) / jnp.maximum((kf - 1.0) * ch, 1.0)
    rep = n_lines * ucie / jnp.maximum(
        link_free + ahat * (Kf - kf) * ch, 1e-9)
    delta = jnp.abs(rep - rep_prev) / jnp.maximum(jnp.abs(rep), 1e-9)
    conv = (((delta <= tol) & (exit_ok > 0.0))
            | (at_hor > 0.0)).astype(jnp.float32)

    pad = jnp.zeros_like(link_free)
    return jnp.stack(list(dev_ready) + [link_free, idx, rep, conv]
                     + [pad] * (PIPE_ROWS - PIPE_MAX_K - 4))


# -- jnp oracles (what the Pallas kernels are tested against) -----------------


def symmetric_chunk_ref(params, state, hist, scal, *, chunk: int):
    return symmetric_chunk_compute(params, state, hist, scal, chunk=chunk)


def asymmetric_periodic_ref(params, *, n_accesses: int):
    return asymmetric_periodic_compute(params, n_accesses=n_accesses)


def symmetric_periodic_ref(params, *, n_flits: int):
    return symmetric_periodic_compute(params, n_flits=n_flits)


def pipelining_chunk_ref(params, state, hist, scal, *, chunk: int):
    return pipelining_chunk_compute(params, state, hist, scal, chunk=chunk)
