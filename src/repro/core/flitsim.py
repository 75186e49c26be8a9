"""Flit-level discrete-event link simulator — batched, jit-cached sweep engine.

Validates the paper's closed-form bandwidth-efficiency expressions with a
cycle-level simulation of slot scheduling — the executable counterpart of
the Appendix (Fig 13) timing analysis.  Three simulator families:

  * symmetric   — slot/granule scheduler for approaches C/D/E (256 B flits
    per direction per step; greedy packing per the paper: "pack as many
    headers as possible into an H-slot and leave as many G-slots for data").
  * asymmetric  — lane-group/UI scheduler for approaches A/B.
  * pipelining  — Fig 13: k LPDDR6 devices time-multiplexed behind the
    logic die; utilization -> 100% at k=4.

The memory is modeled with zero processing latency: steady-state throughput
(what the closed forms predict) is latency-independent; queue feedback —
headers stealing data slots and vice versa — emerges naturally and is
exactly what the analytic max() terms capture.

Batched API
-----------
``SymmetricFlitParams`` and ``AsymmetricLaneParams`` are registered pytrees,
so parameter *stacks* (one leading axis per protocol, optionally folded with
a perturbation axis) flow straight through ``jax.vmap``.  One jitted
``lax.scan`` evaluates an entire ``[P protocols, B backlogs, M mixes]`` grid
in a single compiled program.  :func:`simulate_grid` is the engine entry
point the axes-first :class:`repro.core.space.DesignSpace` lowers onto;
the retired ``sweep`` front-end survives as the private ``_sweep_impl``
engine body:

    res = _sweep_impl()                         # 5 protocols x 5 mixes
    res = _sweep_impl(mixes=grid, backlogs=[16, 64, 128])
    res.efficiency                              # [P, B, M] (or [P, M])
    flitsim.sweep_perturbed([{}, {"credit_lines": 0.5}])   # sensitivity

``_sweep_pipelining_impl`` batches the Fig-13 model over device counts — and,
when ``ucie_line_ui`` / ``device_line_ui`` are sequences, over the full
``[k x ucie_line_ui x device_line_ui]`` joint grid (faster DRAM generations
behind the logic die).  Compiled executables are memoized in the SHARED
design-space cache (:mod:`repro.core.space`) keyed on (family, grid shape,
static lengths, :class:`repro.core.space.SimConfig`) — a second
identically-shaped sweep from ANY front-end (a ``DesignSpace``
evaluation, a scalar ``simulate_*`` call) reuses the warm executable with
zero retracing, and alternating sim configs never invalidates other
configs' entries.  ``compile_cache_stats()`` exposes this module's slice
of the shared counters; the scalar entry points ``simulate_symmetric`` /
``simulate_asymmetric`` / ``simulate_lpddr6_pipelining`` are thin wrappers
over a ``[1, 1, 1]`` grid, so they share the same cache and numerics
bit-for-bit with the batched grid.

Convergence-adaptive execution (``sim=ADAPTIVE_SIM``)
-----------------------------------------------------
Every front-end accepts a ``sim=`` :class:`repro.core.space.SimConfig`.
The default (:data:`FIXED_SIM`) runs the full fixed horizon — bit-identical
to the pre-config engine and to every pinned golden.  ``ADAPTIVE_SIM``
swaps the ``lax.scan`` cores for chunked ``lax.while_loop`` cores with
batched early exit:

* the loop advances the whole vmapped grid one chunk of C cycles at a
  time (inner ``lax.scan``, optionally unrolled), sampling cumulative and
  time-weighted delivery accumulators at chunk boundaries;
* each cell's *report* reconstructs the fixed engine's warm-window average
  ``[N/4, N]``: the observed ``[N/4, n]`` prefix is kept verbatim and the
  unobserved tail ``[n, N]`` is padded with a triangularly-weighted
  trailing-window steady estimate (triangular weighting suppresses the
  periodic-aliasing error of short windows to second order);
* a cell counts as converged when its report is stable to ``tol`` AND —
  for the symmetric family — its queue/credit pools are not drifting
  (slow write-buffer fill produces metastable plateaus that a pure
  output-stability test cannot distinguish from steady state);
* the loop exits when every cell converged, when the straggler count
  drops below the escalation budget (large grids only — the stragglers
  are then re-simulated EXACTLY at the full fixed horizon in a tiny
  padded flat-cell program), or at the horizon (where the report equals
  the fixed warm-window average by construction — exactly so when the
  chunk count is a multiple of 4, which the divisor selection prefers;
  horizons with no usable chunk divisor fall back to the fixed engine).

``last_run_info()`` exposes the cycles-to-convergence telemetry
(per-family cycles run, straggler counts, per-cell convergence histogram)
that ``bench_flitsim`` reports.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import space as space_mod
from repro.core.space import (
    ADAPTIVE_SIM, FIXED_SIM, PALLAS_SIM, CacheStats, SimConfig,
    cached_program,
)
from repro.core.protocols.chi_ucie import CHIOnUCIe
from repro.core.protocols.cxl_mem import CXLMemOnUCIe
from repro.core.protocols.cxl_mem_opt import CXLMemOptOnUCIe
from repro.core.protocols.hbm_ucie import HBMOnUCIe
from repro.core.protocols.lpddr6_ucie import LPDDR6OnUCIe


def _f32(v) -> jnp.ndarray:
    return jnp.asarray(v, dtype=jnp.float32)


def _check_mix(x: float, y: float) -> None:
    """Reject degenerate mixes loudly (the traced cores would emit NaN)."""
    if x < 0 or y < 0 or x + y <= 0:
        raise ValueError(f"invalid traffic mix x={x} y={y}: need x, y >= 0 "
                         "and x + y > 0")

def _register_params_pytree(cls):
    """Register a frozen params dataclass as a pytree (all fields leaves).

    Lets a *stack* of parameter sets (every field a ``[P]`` array) pass
    through ``jax.vmap`` / ``jax.jit`` like any other array pytree.
    """
    names = tuple(f.name for f in dataclasses.fields(cls))
    jax.tree_util.register_pytree_node(
        cls,
        lambda p: (tuple(getattr(p, n) for n in names), None),
        lambda _, children: cls(*children),
    )
    return cls


def apply_perturbation(obj, pert: Mapping[str, float]):
    """Multiplicatively scale the named fields of a frozen dataclass.

    The shared perturbation core behind every sensitivity axis: the flit
    simulators' ``protocol_param`` (scaling :class:`SymmetricFlitParams` /
    :class:`AsymmetricLaneParams` stacks) and the analytic catalog's
    ``catalog_param`` (scaling :class:`repro.core.ucie.UCIePhy` pJ/b and
    density fields).  Fields ``obj`` doesn't have are ignored — validate
    applicability upstream (:func:`check_perturbation` for flit params,
    ``UCIePhy.perturbed`` for catalog params).
    """
    fields = {f.name for f in dataclasses.fields(type(obj))}
    rep = {k: float(getattr(obj, k)) * float(s)
           for k, s in pert.items() if k in fields}
    return dataclasses.replace(obj, **rep) if rep else obj


class _Stackable:
    """Mixin: stack N parameter sets into one pytree of ``[N]`` f32 arrays."""

    @classmethod
    def stack(cls, params: Sequence["_Stackable"]):
        names = [f.name for f in dataclasses.fields(cls)]
        return cls(*[_f32([getattr(p, n) for p in params]) for n in names])

    def perturbed(self, pert: Mapping[str, float]) -> "_Stackable":
        """Scale the named fields multiplicatively (fields this family
        doesn't have are ignored — validated upstream)."""
        return apply_perturbation(self, pert)


@_register_params_pytree
@dataclasses.dataclass(frozen=True)
class SymmetricFlitParams(_Stackable):
    """Slot geometry for a symmetric flit protocol."""

    g_slots: Any                 # payload-capable slots per flit
    h_slots: Any                 # header-only slots per flit
    reqs_per_h: Any              # requests fitting the header slot
    resps_per_h: Any
    reqs_per_g: Any              # requests per payload slot (header overflow)
    resps_per_g: Any
    data_slots_per_line: Any     # slots per 64 B line
    slot_bits: Any               # payload slot size in bits
    flit_bits: Any = 2048        # 256 B
    #: in-flight read-return credit, in flits' worth of payload slots —
    #: the credit limit is ``credit_lines * g_slots`` slots (default 8
    #: flits, the pre-perturbation constant)
    credit_lines: Any = 8.0
    #: write-buffer depth on the memory side, in flits' worth of payload
    #: slots — the write-request gate is ``write_buffer_lines * g_slots``
    #: slots.  Defaults to ``credit_lines`` (the engine historically
    #: reused the read credit as the write-buffer bound; a distinct field
    #: makes the write path independently perturbable while preserving
    #: the default numerics bit-for-bit).
    write_buffer_lines: Any = None

    def __post_init__(self):
        if self.write_buffer_lines is None:
            object.__setattr__(self, "write_buffer_lines",
                               self.credit_lines)

    @classmethod
    def cxl_unopt(cls) -> "SymmetricFlitParams":
        # 1 H + 14 G usable; 16 B slots; 1 req / 2 resp per slot.
        return cls(g_slots=14, h_slots=1, reqs_per_h=1, resps_per_h=2,
                   reqs_per_g=1, resps_per_g=2, data_slots_per_line=4,
                   slot_bits=128)

    @classmethod
    def cxl_opt(cls) -> "SymmetricFlitParams":
        # 15 G + 1 HS (10 B, headers only); 1 req / 4 resp per slot.
        return cls(g_slots=15, h_slots=1, reqs_per_h=1, resps_per_h=4,
                   reqs_per_g=1, resps_per_g=4, data_slots_per_line=4,
                   slot_bits=128)

    @classmethod
    def chi(cls) -> "SymmetricFlitParams":
        # 12 granules of 20 B, no dedicated header slot; 16 B payload/granule.
        return cls(g_slots=12, h_slots=0, reqs_per_h=0, resps_per_h=0,
                   reqs_per_g=1, resps_per_g=2, data_slots_per_line=4,
                   slot_bits=160)   # granule is 20 B on the wire


@_register_params_pytree
@dataclasses.dataclass(frozen=True)
class AsymmetricLaneParams(_Stackable):
    """Lane-group geometry for the asymmetric mappings (A/B)."""

    total_lanes: Any
    read_lanes: Any
    write_lanes: Any
    cmd_lanes: Any
    cmd_bits_per_access: Any
    access_bits: Any = 576

    @classmethod
    def lpddr6(cls) -> "AsymmetricLaneParams":
        return cls(total_lanes=74, read_lanes=36, write_lanes=24,
                   cmd_lanes=10, cmd_bits_per_access=96)

    @classmethod
    def hbm(cls) -> "AsymmetricLaneParams":
        return cls(total_lanes=138, read_lanes=72, write_lanes=36,
                   cmd_lanes=24, cmd_bits_per_access=96)


#: every flit-simulator parameter field a perturbation may scale
PERTURBABLE_FIELDS: Tuple[str, ...] = tuple(sorted(
    {f.name for f in dataclasses.fields(SymmetricFlitParams)}
    | {f.name for f in dataclasses.fields(AsymmetricLaneParams)}))


def check_perturbation(pert: Mapping[str, float]) -> None:
    """Reject ``{field: scale}`` perturbations naming unknown flit-simulator
    parameter fields (catalog perturbations are validated by
    ``UCIePhy.perturbed`` against its own field set)."""
    unknown = sorted(k for k in pert if k not in PERTURBABLE_FIELDS)
    if unknown:
        raise ValueError(f"unknown perturbation fields {unknown}; choose "
                         f"from {PERTURBABLE_FIELDS}")


#: backwards-compatible alias (pre-shared-helper name)
_check_perturbation = check_perturbation


# -- simulator cores (traced params; static lengths only) ---------------------


def _symmetric_stepfn(p: SymmetricFlitParams, x, y, backlog):
    """Single-cycle kernel shared by the fixed and adaptive symmetric
    cores: ``step(core) -> (core', data_slots_delivered_this_cycle)``.

    ``core`` is the queue/credit state ``(rq, wq, wdata, rdata, resp, cr,
    cw)``; the data/warm accounting lives in the mode-specific wrappers so
    the fixed path stays bit-identical to the pre-config engine.
    """
    x, y, backlog = _f32(x), _f32(y), _f32(backlog)
    tot = x + y
    xr = x / tot
    yr = y / tot
    dpl = p.data_slots_per_line
    rdata_limit = p.credit_lines * p.g_slots  # in-flight read credit (slots)
    wbuf_limit = p.write_buffer_lines * p.g_slots  # write-buffer bound
    hdr_cap = p.reqs_per_h * p.h_slots + p.reqs_per_g * p.g_slots
    resp_cap = p.resps_per_h * p.h_slots + p.resps_per_g * p.g_slots
    reqs_per_g = jnp.maximum(_f32(p.reqs_per_g), 1e-9)
    resps_per_g = jnp.maximum(_f32(p.resps_per_g), 1e-9)

    def step(core):
        rq, wq, wdata, rdata, resp, cr, cw = core
        # -- generate traffic to hold the request backlog at `backlog` ------
        deficit = jnp.maximum(backlog - (rq + wq), 0.0)
        cr2 = cr + deficit * xr
        cw2 = cw + deficit * yr
        gen_r = jnp.floor(cr2)
        gen_w = jnp.floor(cw2)
        cr2, cw2 = cr2 - gen_r, cw2 - gen_w
        rq = rq + gen_r
        wq = wq + gen_w

        # -- SoC -> Mem flit: headers first (H then G), data fills the rest -
        # Both request kinds are credit-gated by their data path: reads by
        # the in-flight read-return credit, writes by the write buffer.
        credit_r = jnp.maximum(rdata_limit - rdata, 0.0) / dpl
        credit_w = jnp.maximum(wbuf_limit - wdata, 0.0) / dpl
        rq_elig = jnp.minimum(rq, credit_r)
        wq_elig = jnp.minimum(wq, credit_w)
        sent_req = jnp.minimum(rq_elig + wq_elig, hdr_cap)
        tot_q = jnp.maximum(rq_elig + wq_elig, 1e-9)
        sent_r = sent_req * rq_elig / tot_q
        sent_w = sent_req * wq_elig / tot_q
        g_hdr = (jnp.maximum(sent_req - p.reqs_per_h * p.h_slots, 0.0)
                 / reqs_per_g)
        d_s2m = jnp.minimum(wdata, p.g_slots - g_hdr)
        rq, wq = rq - sent_r, wq - sent_w
        wdata = wdata + sent_w * dpl - d_s2m   # data follows its request
        # a sent read instantly enqueues 4 data slots + 1 response (M2S);
        # a sent write enqueues 1 completion response
        rdata = rdata + sent_r * dpl
        resp = resp + sent_r + sent_w

        # -- Mem -> SoC flit: responses first, read data fills the rest -----
        sent_resp = jnp.minimum(resp, resp_cap)
        g_resp = (jnp.maximum(sent_resp - p.resps_per_h * p.h_slots, 0.0)
                  / resps_per_g)
        d_m2s = jnp.minimum(rdata, p.g_slots - g_resp)
        resp = resp - sent_resp
        rdata = rdata - d_m2s

        return (rq, wq, wdata, rdata, resp, cr2, cw2), d_s2m + d_m2s

    return step


def _symmetric_core_init():
    return tuple(jnp.zeros((), jnp.float32) for _ in range(7))


def _symmetric_efficiency(p: SymmetricFlitParams, x, y, backlog,
                          n_flits: int):
    """Saturation data efficiency of a symmetric full-duplex link.

    Data bits delivered (both directions, 512 b per line) over raw link
    capacity — directly comparable to the analytic ``bw_eff``.  Headers
    have priority; data fills the remaining G-slots.  Read requests are
    gated by credit-based flow control on the read-data return path (as
    CXL's credit mechanism does); writes by the memory-side write buffer.

    Fixed-horizon core: runs exactly ``n_flits`` cycles and averages over
    the warm window (the last three quarters) — the reference numerics
    every golden is pinned against.
    """
    kernel = _symmetric_stepfn(p, x, y, backlog)

    def step(carry, _):
        core, data_slots, warm_slots, warm = carry
        core, new_data = kernel(core)
        # warm-up: skip the first quarter of the run when accumulating
        warm = warm + 1
        is_warm = (warm > n_flits // 4).astype(jnp.float32)
        data_slots = data_slots + new_data * is_warm
        warm_slots = warm_slots + is_warm
        return (core, data_slots, warm_slots, warm), None

    init = (_symmetric_core_init(), jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    (_, data_slots, warm_slots, _), _ = jax.lax.scan(
        step, init, None, length=n_flits)
    # data bits delivered over both-direction capacity during warm window
    data_bits = data_slots * 128.0           # 16 B of payload per data slot
    cap_bits = 2.0 * warm_slots * _f32(p.flit_bits)
    return data_bits / cap_bits


def _asymmetric_stepfn(p: AsymmetricLaneParams, x, y):
    """Single-access kernel shared by the fixed and adaptive asymmetric
    cores: ``step(core) -> core'`` over ``(t_read, t_write, t_cmd,
    credit)``."""
    x, y = _f32(x), _f32(y)
    xr = x / (x + y)
    r_ui = _f32(p.access_bits) / p.read_lanes
    w_ui = _f32(p.access_bits) / p.write_lanes
    c_ui = _f32(p.cmd_bits_per_access) / p.cmd_lanes

    def step(core):
        t_read, t_write, t_cmd, credit = core
        credit = credit + xr
        is_read = credit >= 1.0
        credit = jnp.where(is_read, credit - 1.0, credit)
        t_read = t_read + jnp.where(is_read, r_ui, 0.0)
        t_write = t_write + jnp.where(is_read, 0.0, w_ui)
        t_cmd = t_cmd + c_ui
        return (t_read, t_write, t_cmd, credit)

    return step


def _asymmetric_efficiency(p: AsymmetricLaneParams, x, y, n_accesses: int):
    """Lane-occupancy simulation: issue n accesses in x:y ratio, measure
    512*n/(total_lanes*T) — comparable to eq (3).  Fixed-horizon core."""
    kernel = _asymmetric_stepfn(p, x, y)

    def step(carry, _):
        return kernel(carry), None

    init = (jnp.zeros((), jnp.float32),) * 4
    (t_r, t_w, t_c, _), _ = jax.lax.scan(step, init, None, length=n_accesses)
    t_total = jnp.maximum(jnp.maximum(t_r, t_w), t_c)
    return 512.0 * n_accesses / (p.total_lanes * t_total)


def _pipelining_utilization(k, ucie_line_ui, device_line_ui,
                            max_k: int, n_lines: int):
    """Appendix Fig 13: k x12 LPDDR6 devices time-multiplexed behind the
    logic die.  The UCIe link moves a 64 B line in ``ucie_line_ui`` UI; each
    device sources a line every ``device_line_ui`` UI.  Returns link data
    utilization — 1.0 at k = 4.

    Commands are pipelined (ACT/RD interleaved at 8-bit granularity, Fig 13)
    so the command bus never limits: we model device ready-times only.
    The device ready-time table is padded to ``max_k`` so one executable
    serves every batched ``k`` (entries past k are never addressed).
    """
    k = jnp.asarray(k, jnp.int32)
    ucie_line_ui = _f32(ucie_line_ui)
    device_line_ui = _f32(device_line_ui)
    kernel = _pipelining_stepfn(k, ucie_line_ui, device_line_ui)

    def step(carry, _):
        return kernel(carry), None

    init = _pipelining_core_init(max_k)
    (_, last_finish, _), _ = jax.lax.scan(step, init, None, length=n_lines)
    return n_lines * ucie_line_ui / last_finish


def _pipelining_stepfn(k, ucie_line_ui, device_line_ui):
    """Single-line kernel shared by the fixed and adaptive pipelining
    cores: ``step(core) -> core'`` over ``(dev_ready, last_finish, idx)``."""
    def step(core):
        dev_ready, link_free, idx = core
        dev = idx % k
        start = jnp.maximum(dev_ready[dev], link_free)
        finish = start + ucie_line_ui
        dev_ready = dev_ready.at[dev].set(start + device_line_ui)
        return (dev_ready, finish, idx + 1)

    return step


def _pipelining_core_init(max_k: int):
    return (jnp.zeros((max_k,), jnp.float32), jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.int32))


# -- batched grid programs ----------------------------------------------------


def _symmetric_grid(pstack, x, y, backlogs, *, n_flits: int):
    """[P params] x [B backlogs] x [M mixes] -> efficiency [P, B, M]."""
    point = lambda p, b, xx, yy: _symmetric_efficiency(p, xx, yy, b, n_flits)
    over_m = jax.vmap(point, in_axes=(None, None, 0, 0))
    over_bm = jax.vmap(over_m, in_axes=(None, 0, None, None))
    over_pbm = jax.vmap(over_bm, in_axes=(0, None, None, None))
    return over_pbm(pstack, backlogs, x, y)


def _asymmetric_grid(pstack, x, y, *, n_accesses: int):
    """[P params] x [M mixes] -> efficiency [P, M] (backlog-independent)."""
    point = lambda p, xx, yy: _asymmetric_efficiency(p, xx, yy, n_accesses)
    over_m = jax.vmap(point, in_axes=(None, 0, 0))
    return jax.vmap(over_m, in_axes=(0, None, None))(pstack, x, y)


def _pipelining_grid(ks, ucie_line_uis, device_line_uis, *, max_k: int,
                     n_lines: int):
    """[K device-counts] x [U link-UIs] x [D device-UIs] -> utilization
    [K, U, D] — the joint faster-DRAM-generations sweep."""
    point = lambda k, u, d: _pipelining_utilization(k, u, d, max_k, n_lines)
    over_d = jax.vmap(point, in_axes=(None, None, 0))
    over_ud = jax.vmap(over_d, in_axes=(None, 0, None))
    over_kud = jax.vmap(over_ud, in_axes=(0, None, None))
    return over_kud(ks, ucie_line_uis, device_line_uis)


# -- trace-scan cores (the DesignSpace ``trace`` axis) ------------------------
#
# A trace is a sequence of (read_fraction, backlog) phases; the trace-scan
# cores run the phases BACK TO BACK through the shared single-cycle step
# kernels, carrying the queue/credit state across every phase boundary —
# a write-buffer filled by a prefill burst drains INTO the next decode
# phase instead of being reset, so backlog transients are simulated, not
# assumed away.  Every phase runs the same static ``cycles`` count (one
# executable per (grid shape, phase count, cycles)); phase DURATIONS are
# aggregation weights applied host-side by the design space.
#
# Accounting resets per phase; phase 0 keeps the fixed engine's quarter
# warm-up (so a SINGLE-phase trace is bit-identical to the fixed static
# cell) and later phases count every cycle — their "warm-up" is the real
# carried transient.


def _symmetric_trace_point(p, xs, ys, bls, *, n_phases: int, cycles: int):
    """Per-phase efficiency ``[N]`` of one symmetric cell over a phase
    sequence ``xs / ys / bls`` ``[N]``, queue/credit state carried."""

    def phase(core, inp):
        x, yv, b, thresh = inp
        kernel = _symmetric_stepfn(p, x, yv, b)

        def step(carry, _):
            c, data_slots, warm_slots, warm = carry
            c, new_data = kernel(c)
            warm = warm + 1
            is_warm = (warm > thresh).astype(jnp.float32)
            data_slots = data_slots + new_data * is_warm
            warm_slots = warm_slots + is_warm
            return (c, data_slots, warm_slots, warm), None

        init = (core, jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
        (core, data_slots, warm_slots, _), _ = jax.lax.scan(
            step, init, None, length=cycles)
        data_bits = data_slots * 128.0
        cap_bits = 2.0 * warm_slots * _f32(p.flit_bits)
        return core, data_bits / cap_bits

    threshs = jnp.concatenate([
        jnp.full((1,), cycles // 4, jnp.int32),
        jnp.zeros((n_phases - 1,), jnp.int32)])
    _, effs = jax.lax.scan(phase, _symmetric_core_init(),
                           (xs, ys, bls, threshs))
    return effs


def _symmetric_trace_grid(pstack, xs, ys, bls, *, n_phases: int,
                          cycles: int):
    """[P params] x [T traces] -> per-phase efficiency [P, T, N]."""
    point = lambda p, xr, yr, br: _symmetric_trace_point(
        p, xr, yr, br, n_phases=n_phases, cycles=cycles)
    over_t = jax.vmap(point, in_axes=(None, 0, 0, 0))
    return jax.vmap(over_t, in_axes=(0, None, None, None))(pstack, xs, ys,
                                                           bls)


def _asymmetric_trace_point(p, xs, ys, *, n_phases: int, cycles: int):
    """Per-phase efficiency ``[N]`` of one asymmetric cell: lane clocks
    and the read/write credit accumulator carry across phases; each
    phase's efficiency comes from its lane-time DELTA."""

    def phase(carry, inp):
        core, t_prev = carry
        x, yv = inp
        kernel = _asymmetric_stepfn(p, x, yv)

        def step(c, _):
            return kernel(c), None

        core, _ = jax.lax.scan(step, core, None, length=cycles)
        t_r, t_w, t_c, _ = core
        t_total = jnp.maximum(jnp.maximum(t_r, t_w), t_c)
        eff = 512.0 * cycles / (p.total_lanes * (t_total - t_prev))
        return (core, t_total), eff

    init = ((jnp.zeros((), jnp.float32),) * 4, jnp.zeros((), jnp.float32))
    _, effs = jax.lax.scan(phase, init, (xs, ys))
    return effs


def _asymmetric_trace_grid(pstack, xs, ys, *, n_phases: int, cycles: int):
    """[P params] x [T traces] -> per-phase efficiency [P, T, N]."""
    point = lambda p, xr, yr: _asymmetric_trace_point(
        p, xr, yr, n_phases=n_phases, cycles=cycles)
    over_t = jax.vmap(point, in_axes=(None, 0, 0))
    return jax.vmap(over_t, in_axes=(0, None, None))(pstack, xs, ys)


# -- convergence-adaptive chunked cores (SimConfig mode="adaptive") -----------
#
# Each adaptive core is a ``lax.while_loop`` over chunks of C cycles (inner
# ``lax.scan`` with ``unroll=``) carrying per-cell running estimates; the
# WHOLE vmapped grid exits as soon as the slowest cell converges (or the
# straggler set shrinks below the escalation budget / the horizon is hit).
#
# The per-cell estimate is NOT the raw steady-state mean: it reconstructs
# the fixed engine's warm-window average ``[N/4, N]`` so the adaptive value
# tracks the fixed-mode value, transients included:
#
#   report = ( observed [N/4, n] prefix * its width
#            + mu_hat * (N - n) ) / (N - N/4)
#
# where ``mu_hat`` is a TRIANGULARLY-weighted trailing-window mean (the
# triangular window suppresses the O(T/w) periodic-aliasing error of a
# rectangular window to O((T/w)^2)), formed from cumulative data and
# time-weighted-data accumulators sampled at chunk boundaries.  A cell is
# converged when its report is stable to ``tol`` AND its queue/credit
# pools are not drifting (the drift guard catches slow write-buffer-fill
# metastability a short stability test cannot see).  On grids of >=
# _ESCALATION_MIN_CELLS cells the loop may exit with up to cells //
# _ESCALATION_BUDGET_DIV unconverged stragglers, which are re-simulated
# EXACTLY (full fixed horizon, bit-identical numerics) in a tiny padded
# flat-cell program — so a handful of slow cells cannot hold the whole
# grid at the full horizon.

#: chunks between pool snapshots for the drift guard
_DRIFT_SPAN = 3
#: max pool movement per chunk (slots) still considered "steady" — steady
#: boundary aliasing measures <= ~1.4 slots/chunk; slow-fill transients
#: measure 8-34 slots/chunk
_DRIFT_TOL_SLOTS = 2.0
#: never exit before this many chunks (two comparable reports + warm-up)
_MIN_EXIT_CHUNKS = 4
#: straggler escalation only pays off on grids at least this large (on
#: small grids the fixed per-cycle dispatch cost of a second full-horizon
#: pass outweighs the saved chunks)
_ESCALATION_MIN_CELLS = 256
#: max stragglers the early exit may leave behind: cells // this
_ESCALATION_BUDGET_DIV = 8


def _divisor_chunk(horizon: int, chunk: int) -> int:
    """Effective chunk: near ``horizon / 16`` (so per-chunk estimate
    overhead stays amortized for long horizons), at least the configured
    ``chunk``, at most ``horizon / 8`` (so short horizons still get >= 8
    convergence checks), snapped down to an exact divisor of ``horizon``
    so the chunked loop lands on the fixed horizon precisely.

    Divisors making the chunk count a multiple of 4 are preferred — then
    the reconstructed warm window starts exactly at ``horizon // 4`` and
    the at-horizon report equals the fixed warm-window average.  Returns
    a value < 8 when ``horizon`` has no usable divisor (e.g. a prime);
    the runners fall back to the fixed engine in that case rather than
    degrade to per-cycle chunking."""
    horizon = int(horizon)
    cap = min(max(int(chunk), horizon // 16), max(horizon // 8, 1))
    best = 1
    for c in range(cap, 7, -1):
        if horizon % c:
            continue
        if (horizon // c) % 4 == 0:
            return c
        best = max(best, c)
    return best


def _tri_window_mean(Dh, TDh, k, m, chunk: float, denom_per_cycle):
    """Triangular-weighted mean of the per-cycle delivery over the chunk
    window ``(m, k]`` (apex at the midpoint), from cumulative ``D`` and
    time-weighted ``TD = sum(t * d_t)`` boundary histories."""
    mid = (m + k + 1) // 2
    idx = lambda H, i: jax.lax.dynamic_index_in_dim(H, i, axis=0,
                                                    keepdims=False)
    D_m, D_mid, D_k = idx(Dh, m), idx(Dh, mid), idx(Dh, k)
    TD_m, TD_mid, TD_k = idx(TDh, m), idx(TDh, mid), idx(TDh, k)
    b_i = m.astype(jnp.float32) * chunk
    b_m = mid.astype(jnp.float32) * chunk
    b_j = k.astype(jnp.float32) * chunk
    c1 = b_m - b_i
    c2 = b_j - b_m
    w_sum = c1 * (c1 + 1.0) / 2.0 + c2 * (c2 - 1.0) / 2.0
    num = ((TD_mid - TD_m) - b_i * (D_mid - D_m)
           + b_j * (D_k - D_mid) - (TD_k - TD_mid))
    return num / (jnp.maximum(w_sum, 1.0) * denom_per_cycle)


def _symmetric_grid_adaptive(pstack, x, y, backlogs, *, n_flits: int,
                             chunk: int, unroll: int, tol: float,
                             budget: int):
    """Chunked early-exit symmetric core over the ``[P, B, M]`` grid.

    Returns ``(report, converged, chunks_run, conv_at_chunk)`` where
    ``report`` reconstructs the fixed warm-window average (see the module
    section comment), ``converged`` marks cells whose report is trusted,
    and ``conv_at_chunk`` is each cell's first stable chunk (-1 = never).
    """
    P = pstack.g_slots.shape[0]
    B = backlogs.shape[0]
    M = x.shape[0]
    K = n_flits // chunk
    K0 = max(K // 4, 1)           # fixed warm window starts at chunk K0
    min_k = max(_MIN_EXIT_CHUNKS, K0 + 1)
    ch = jnp.float32(chunk)
    fb = pstack.flit_bits[:, None, None]
    denom = 2.0 * fb / 128.0      # capacity bits per cycle / bits per slot

    def cell_chunk(p, b, xx, yy, core, D, TD, t):
        kernel = _symmetric_stepfn(p, xx, yy, b)

        def step(c, _):
            core, D, TD, t = c
            core, nd = kernel(core)
            t = t + 1.0
            D = D + nd
            TD = TD + t * nd
            return (core, D, TD, t), None

        (core, D, TD, t), _ = jax.lax.scan(
            step, (core, D, TD, t), None, length=chunk, unroll=unroll)
        return core, D, TD, t

    over_m = jax.vmap(cell_chunk, in_axes=(None, None, 0, 0, 0, 0, 0, 0))
    over_bm = jax.vmap(over_m, in_axes=(None, 0, None, None, 0, 0, 0, 0))
    over_pbm = jax.vmap(over_bm, in_axes=(0, None, None, None, 0, 0, 0, 0))

    def report(k, Dh, TDh):
        m = jnp.maximum(k - 4, (k + 1) // 2)
        mu = _tri_window_mean(Dh, TDh, k, m, ch, denom)
        D_K0 = Dh[K0]
        D_k = jax.lax.dynamic_index_in_dim(Dh, k, axis=0, keepdims=False)
        wA = jnp.maximum((k - K0).astype(jnp.float32), 1.0) * ch
        A = (D_k - D_K0) / (wA * denom)
        kf = (k - K0).astype(jnp.float32)
        return jnp.where(k > K0,
                         (A * kf + mu * (K - k).astype(jnp.float32))
                         / float(K - K0), mu)

    zeros = lambda: jnp.zeros((P, B, M), jnp.float32)

    def body(state):
        (k, core, D, TD, t, Dh, TDh, Ph, rep, conv, conv_at, unconv) = state
        core, D, TD, t = over_pbm(pstack, backlogs, x, y, core, D, TD, t)
        k = k + 1
        Dh = Dh.at[k].set(D)
        TDh = TDh.at[k].set(TD)
        pools = jnp.stack(core[:5])          # rq, wq, wdata, rdata, resp
        Ph = Ph.at[k].set(pools)
        new_rep = report(k, Dh, TDh)
        prev_pools = jax.lax.dynamic_index_in_dim(
            Ph, jnp.maximum(k - _DRIFT_SPAN, 0), axis=0, keepdims=False)
        drift = jnp.max(jnp.abs(pools - prev_pools), axis=0) / _DRIFT_SPAN
        delta = jnp.abs(new_rep - rep) / jnp.maximum(jnp.abs(new_rep),
                                                     1e-9)
        conv = ((delta <= tol) & (drift < _DRIFT_TOL_SLOTS)
                & (k >= min_k) & (k > _DRIFT_SPAN)) | (k >= K)
        conv_at = jnp.where((conv_at < 0) & conv, k, conv_at)
        unconv = jnp.sum(jnp.where(conv, 0, 1))
        return (k, core, D, TD, t, Dh, TDh, Ph, new_rep, conv, conv_at,
                unconv)

    def cond(state):
        k, unconv = state[0], state[-1]
        return (k < K) & (unconv > budget)

    init = (jnp.zeros((), jnp.int32),
            tuple(zeros() for _ in range(7)),
            zeros(), zeros(), zeros(),
            jnp.zeros((K + 1, P, B, M), jnp.float32),
            jnp.zeros((K + 1, P, B, M), jnp.float32),
            jnp.zeros((K + 1, 5, P, B, M), jnp.float32),
            zeros(), jnp.zeros((P, B, M), bool),
            -jnp.ones((P, B, M), jnp.int32),
            jnp.asarray(P * B * M + budget + 1, jnp.int32))
    (k, _, _, _, _, _, _, _, rep, conv, conv_at, _) = jax.lax.while_loop(
        cond, body, init)
    return rep, conv, k, conv_at


def _symmetric_cells_grid(pcells, xs, ys, bs, *, n_flits: int):
    """Flat per-cell fixed-horizon program for straggler escalation: each
    cell carries its own (param row, mix, backlog) — numerics identical to
    the fixed grid core."""
    point = lambda p, xx, yy, b: _symmetric_efficiency(p, xx, yy, b,
                                                       n_flits)
    return jax.vmap(point)(pcells, xs, ys, bs)


def _asymmetric_grid_adaptive(pstack, x, y, *, n_accesses: int, chunk: int,
                              unroll: int, tol: float, budget: int):
    """Chunked early-exit asymmetric core over the ``[P, M]`` grid.

    The busiest-lane time grows linearly in steady state, so the report
    extrapolates the fixed-horizon value ``512 N / (lanes * T(N))`` from
    the observed ``T(n)`` plus the trailing slope — killing the ``C/n``
    tail a plain cumulative estimate would carry.
    """
    P = pstack.total_lanes.shape[0]
    M = x.shape[0]
    K = n_accesses // chunk
    min_k = _MIN_EXIT_CHUNKS
    ch = jnp.float32(chunk)
    lanes = pstack.total_lanes[:, None]

    def cell_chunk(p, xx, yy, core):
        kernel = _asymmetric_stepfn(p, xx, yy)

        def step(c, _):
            return kernel(c), None

        core, _ = jax.lax.scan(step, core, None, length=chunk,
                               unroll=unroll)
        return core

    over_m = jax.vmap(cell_chunk, in_axes=(None, 0, 0, 0))
    over_pm = jax.vmap(over_m, in_axes=(0, None, None, 0))

    def report(k, Th):
        T_k = jax.lax.dynamic_index_in_dim(Th, k, axis=0, keepdims=False)
        ahat = (T_k - Th[1]) / jnp.maximum(
            (k - 1).astype(jnp.float32) * ch, 1.0)
        tail = (K - k).astype(jnp.float32) * ch
        return 512.0 * n_accesses / (lanes * jnp.maximum(
            T_k + ahat * tail, 1e-9))

    def body(state):
        k, core, Th, rep, conv, conv_at, unconv = state
        core = over_pm(pstack, x, y, core)
        k = k + 1
        T = jnp.maximum(jnp.maximum(core[0], core[1]), core[2])
        Th = Th.at[k].set(T)
        new_rep = report(k, Th)
        delta = jnp.abs(new_rep - rep) / jnp.maximum(jnp.abs(new_rep),
                                                     1e-9)
        conv = ((delta <= tol) & (k >= min_k)) | (k >= K)
        conv_at = jnp.where((conv_at < 0) & conv, k, conv_at)
        unconv = jnp.sum(jnp.where(conv, 0, 1))
        return (k, core, Th, new_rep, conv, conv_at, unconv)

    def cond(state):
        k, unconv = state[0], state[-1]
        return (k < K) & (unconv > budget)

    zeros = lambda: jnp.zeros((P, M), jnp.float32)
    init = (jnp.zeros((), jnp.int32),
            tuple(zeros() for _ in range(4)),
            jnp.zeros((K + 1, P, M), jnp.float32),
            zeros(), jnp.zeros((P, M), bool),
            -jnp.ones((P, M), jnp.int32),
            jnp.asarray(P * M + budget + 1, jnp.int32))
    (k, _, _, rep, conv, conv_at, _) = jax.lax.while_loop(cond, body, init)
    return rep, conv, k, conv_at


def _asymmetric_cells_grid(pcells, xs, ys, *, n_accesses: int):
    """Flat per-cell fixed-horizon asymmetric program (escalation)."""
    point = lambda p, xx, yy: _asymmetric_efficiency(p, xx, yy, n_accesses)
    return jax.vmap(point)(pcells, xs, ys)


def _pipelining_grid_adaptive(ks, ucie_line_uis, device_line_uis, *,
                              max_k: int, n_lines: int, chunk: int,
                              unroll: int, tol: float):
    """Chunked early-exit Fig-13 pipelining core over ``[K, U, D]``.

    Same linear-growth extrapolation as the asymmetric core (the link
    free-time grows exactly linearly once the k-device rotation fills).
    """
    Kk = ks.shape[0]
    U = ucie_line_uis.shape[0]
    Dn = device_line_uis.shape[0]
    K = n_lines // chunk
    min_k = min(_MIN_EXIT_CHUNKS, K)
    ch = jnp.float32(chunk)
    ucie = ucie_line_uis[None, :, None]

    def cell_chunk(k_dev, u, d, core):
        kernel = _pipelining_stepfn(k_dev, u, d)

        def step(c, _):
            return kernel(c), None

        core, _ = jax.lax.scan(step, core, None, length=chunk,
                               unroll=unroll)
        return core

    over_d = jax.vmap(cell_chunk, in_axes=(None, None, 0, 0))
    over_ud = jax.vmap(over_d, in_axes=(None, 0, None, 0))
    over_kud = jax.vmap(over_ud, in_axes=(0, None, None, 0))

    def report(k, Th):
        T_k = jax.lax.dynamic_index_in_dim(Th, k, axis=0, keepdims=False)
        ahat = (T_k - Th[1]) / jnp.maximum(
            (k - 1).astype(jnp.float32) * ch, 1.0)
        tail = (K - k).astype(jnp.float32) * ch
        return n_lines * ucie / jnp.maximum(T_k + ahat * tail, 1e-9)

    def body(state):
        k, core, Th, rep, conv, conv_at, unconv = state
        core = over_kud(ks, ucie_line_uis, device_line_uis, core)
        k = k + 1
        Th = Th.at[k].set(core[1])
        new_rep = report(k, Th)
        delta = jnp.abs(new_rep - rep) / jnp.maximum(jnp.abs(new_rep),
                                                     1e-9)
        conv = ((delta <= tol) & (k >= min_k)) | (k >= K)
        conv_at = jnp.where((conv_at < 0) & conv, k, conv_at)
        unconv = jnp.sum(jnp.where(conv, 0, 1))
        return (k, core, Th, new_rep, conv, conv_at, unconv)

    def cond(state):
        k, unconv = state[0], state[-1]
        return (k < K) & (unconv > 0)

    init = (jnp.zeros((), jnp.int32),
            (jnp.zeros((Kk, U, Dn, max_k), jnp.float32),
             jnp.zeros((Kk, U, Dn), jnp.float32),
             jnp.zeros((Kk, U, Dn), jnp.int32)),
            jnp.zeros((K + 1, Kk, U, Dn), jnp.float32),
            jnp.zeros((Kk, U, Dn), jnp.float32),
            jnp.zeros((Kk, U, Dn), bool),
            -jnp.ones((Kk, U, Dn), jnp.int32),
            jnp.asarray(Kk * U * Dn + 1, jnp.int32))
    (k, _, _, rep, conv, conv_at, _) = jax.lax.while_loop(cond, body, init)
    return rep, conv, k, conv_at


# -- shared compile cache (repro.core.space) ---------------------------------


def compile_cache_stats() -> CacheStats:
    """This module's slice of the SHARED design-space compile cache
    (families ``flitsim.*``): hits / misses, one miss == one compile."""
    return space_mod.cache_stats(space_mod.FLITSIM_FAMILIES)


def clear_compile_cache() -> None:
    """Drop this module's cached executables and reset its counters."""
    space_mod.clear_cache(space_mod.FLITSIM_FAMILIES)


#: telemetry from the most recent ADAPTIVE run per engine family —
#: cycles executed, horizon, straggler count, and the cycles-to-convergence
#: histogram the benchmarks report (see :func:`last_run_info`)
_LAST_RUN_INFO: Dict[str, Dict[str, Any]] = {}


def last_run_info() -> Dict[str, Dict[str, Any]]:
    """Per-family telemetry of the most recent adaptive run: ``cycles_run``
    (main-loop chunks executed), ``sequential_depth`` (the run's true
    sequential depth — the horizon whenever a straggler-escalation pass
    ran), ``horizon`` / ``chunk`` / ``stragglers`` / ``cells``, plus a
    ``converged_cycles`` histogram ({cycles: cell count}; stragglers and
    horizon-exits count under ``"horizon"``).

    Engine telemetry (PR 6): ``engine`` (``"xla"`` / ``"pallas"``),
    ``launches`` (device programs the runner dispatched — the pallas host
    loop issues one per chunk plus one per escalation pass; the XLA
    ``while_loop`` cores are a single launch), ``elapsed_s`` (runner wall
    time, device work blocked to completion), and
    ``cycles_per_sec_per_cell`` (executed main-loop cycles per second per
    grid cell — the throughput number the BENCH million-cell row reports).
    The asymmetric periodic detector additionally reports a ``periods``
    histogram ({detected credit period: cell count}).

    Streaming dispatch telemetry (PR 10): ``stream.*`` families carry a
    ``stream`` record — ``dispatches``, ``prefetch`` (bounded in-flight
    depth), ``pad_cells`` (replicated tail cells across all dispatches)
    and ``overlap_frac`` (fraction of host marshalling wall time spent
    while at least one dispatch was in flight on the device).

    Fixed-mode runs do not update it.  The raw arrays are kept lazily on
    device so the hot path pays no host sync; this accessor materializes
    them ONCE per recorded run (the materialized view is memoized, so
    polling telemetry from a dispatch loop never re-syncs)."""
    out: Dict[str, Dict[str, Any]] = {}
    for fam, info in _LAST_RUN_INFO.items():
        cached = info.get("_materialized")
        if cached is not None:
            out[fam] = cached
            continue
        d = {k: v for k, v in info.items() if not k.startswith("_")}
        if d.get("mode") in ("trace", "stream"):
            # trace-scan runs (``family.trace`` keys) and streaming
            # dispatch runs report their counters directly; no
            # convergence histogram
            info["_materialized"] = d
            out[fam] = d
            continue
        chunk = d["chunk"]
        conv_at = np.asarray(info["_conv_at"]).reshape(-1)
        d["cycles_run"] = int(np.asarray(info["_k_exit"])) * chunk
        # the straggler escalation pass runs the FULL horizon, so the
        # run's true sequential depth is the horizon whenever any cell
        # was escalated — cycles_run alone would overstate the depth cut
        d["sequential_depth"] = (d["horizon"] if d["stragglers"]
                                 else d["cycles_run"])
        d["cells"] = int(conv_at.size)
        vals, counts = np.unique(conv_at, return_counts=True)
        d["converged_cycles"] = {
            ("horizon" if v < 0 else str(int(v) * chunk)): int(c)
            for v, c in zip(vals, counts)}
        if d.get("elapsed_s"):
            d["cycles_per_sec_per_cell"] = d["cycles_run"] / d["elapsed_s"]
        if info.get("_periods") is not None:
            p = np.asarray(info["_periods"]).reshape(-1)
            pv, pc = np.unique(p[p > 0], return_counts=True)
            d["periods"] = {int(v): int(c) for v, c in zip(pv, pc)}
        info["_materialized"] = d
        out[fam] = d
    return out


def _record_adaptive(family: str, horizon: int, chunk: int, k_exit,
                     conv_at, stragglers: int, *, engine: str = "xla",
                     launches: int = 1, elapsed_s: Optional[float] = None,
                     periods=None) -> None:
    _LAST_RUN_INFO[family] = {
        "mode": "adaptive", "horizon": int(horizon), "chunk": int(chunk),
        "stragglers": int(stragglers), "engine": engine,
        "launches": int(launches), "elapsed_s": elapsed_s,
        "_k_exit": k_exit, "_conv_at": conv_at, "_periods": periods,
    }


def _record_stream(family: str, *, dispatches: int, prefetch: int,
                   pad_cells: int, overlap_frac: float, cells: int,
                   elapsed_s: Optional[float] = None,
                   marshal_s: Optional[float] = None) -> None:
    """Telemetry for a streaming dispatch run (``stream.*`` families):
    dispatch count, bounded in-flight depth, replicated pad-cell total,
    and the marshal-vs-device overlap fraction (how much of the host's
    index-marshalling wall time ran while a previous chunk was still in
    flight — the async win over the strictly sequential loop).
    ``marshal_s`` is the total host marshalling wall time, so
    ``marshal_s / elapsed_s`` bounds the async win available."""
    _LAST_RUN_INFO[family] = {
        "mode": "stream", "dispatches": int(dispatches),
        "prefetch": int(prefetch), "pad_cells": int(pad_cells),
        "overlap_frac": float(overlap_frac), "cells": int(cells),
        "elapsed_s": elapsed_s, "marshal_s": marshal_s,
    }


def _record_trace(family: str, phases: int, cycles: int,
                  cells: int) -> None:
    """Telemetry for a trace-scan run, keyed ``family + ".trace"`` so it
    never clobbers the same family's adaptive record: per-phase cycle
    count, total cycles, grid cells simulated, and the state-carry depth
    (cycles whose initial state came from a PREVIOUS phase)."""
    _LAST_RUN_INFO[family + ".trace"] = {
        "mode": "trace", "phases": int(phases),
        "cycles_per_phase": int(cycles),
        "cycles_run": int(phases) * int(cycles),
        "trace_cells": int(cells),
        "state_carry_depth": (int(phases) - 1) * int(cycles),
        "engine": "xla",
    }


def _escalate_stragglers(family: str, cells_grid_fn, horizon: int, rep,
                         conv_np: np.ndarray, args_builder):
    """Re-simulate unconverged straggler cells EXACTLY at the full fixed
    horizon in a padded flat-cell program, scattering the exact values
    back over the adaptive reports.  ``args_builder(idx)`` maps the padded
    ``[S, ndim]`` straggler indices to the flat-cell program's arguments.
    """
    idx = _pad_pow2(np.argwhere(~conv_np))
    args = args_builder(idx)
    cells_fn = cached_program(family, ("cells", idx.shape[0], horizon),
                              cells_grid_fn, args)
    exact = np.asarray(cells_fn(*args))
    rep_np = np.asarray(rep).copy()
    rep_np[~conv_np] = exact[:int((~conv_np).sum())]
    return jnp.asarray(rep_np)


def _escalation_budget(cells: int, chunk: int, horizon: int) -> int:
    """Max stragglers the early exit may strand: roughly the break-even
    point where re-simulating S cells at the full horizon costs what one
    more full-grid chunk would (S * horizon ~= cells * chunk), floored by
    the cells // _ESCALATION_BUDGET_DIV policy cap."""
    if cells < _ESCALATION_MIN_CELLS:
        return 0
    return min(cells // _ESCALATION_BUDGET_DIV,
               max((cells * chunk) // horizon, 1))


def _gather_cells(pstack, rows) -> Any:
    """Per-cell parameter pytree: row ``rows[i]`` of every stacked leaf."""
    return jax.tree_util.tree_map(
        lambda leaf: jnp.asarray(np.asarray(leaf)[rows]), pstack)


def _pad_pow2(idx: np.ndarray) -> np.ndarray:
    """Pad straggler indices to the next power-of-two bucket (repeating
    the first row) so escalation compiles once per bucket size."""
    bucket = 1 << max(idx.shape[0] - 1, 0).bit_length()
    return np.concatenate([idx, np.repeat(idx[:1], bucket - idx.shape[0],
                                          axis=0)])


# -- fused-kernel engine (SimConfig engine="pallas") + periodic detector ------
#
# The row-stacked operand layouts and the per-chunk compute contracts live
# in repro.kernels.flit_sim (ref.py documents them; kernel.py is the
# Pallas transcription sharing the same compute bodies).  The kernels
# package imports this module for the step functions, so everything below
# imports it lazily.
#
# The asymmetric family additionally gets a PERIOD-EXACT detector (both
# engines): the credit accumulator advances by the rational read fraction
# x/(x+y) each access, so the credit state — which alone determines every
# future lane increment — is exactly periodic with denominator
# q = (x+y)/gcd(x,y).  The runner observes ~2 maximal periods
# (ref.PERIOD_OBS sequential steps), detects each cell's period from the
# credit phase, extrapolates the per-lane busy times exactly to the full
# horizon, and escalates the (rare) undetected cells through the usual
# exact full-horizon path — closing the asymmetric warm window at ~128
# steps instead of the chunked core's ~1280/4096.
#
# The symmetric family mirrors it (PR 10) with a stricter certificate:
# the pool/credit core's proportional-split division breaks bitwise
# orbits at saturated backlogs, so the detector requires an EXACT f32
# match of the full 7-component core against the lagged observation row
# plus an integer-valued delivery window.  Where that holds (low-backlog
# and degenerate-mix cells lock into period <= PERIOD_MAX orbits) the
# warm-window delivery sum extrapolates in closed form BIT-IDENTICALLY
# to the fixed engine; saturated grids are mostly undetected and fall
# through to the chunked adaptive core unchanged.


def _sym_param_rows(pstack, x, y, backlogs):
    """Row-stack a symmetric grid into the kernels' [SYM_ROWS, P*B*M]
    layout (cell order matches ``rep.reshape(P, B, M)``)."""
    from repro.kernels.flit_sim import ref as fs_ref
    P, B, M = pstack.g_slots.shape[0], backlogs.shape[0], x.shape[0]
    rows = [jnp.repeat(_f32(getattr(pstack, f.name)), B * M)
            for f in dataclasses.fields(SymmetricFlitParams)]
    rows.append(jnp.tile(_f32(x), P * B))
    rows.append(jnp.tile(_f32(y), P * B))
    rows.append(jnp.tile(jnp.repeat(_f32(backlogs), M), P))
    pad = jnp.zeros_like(rows[0])
    return jnp.stack(rows + [pad] * (fs_ref.SYM_ROWS - len(rows)))


def _asym_param_rows(pstack, x, y):
    """Row-stack an asymmetric grid into [ASYM_ROWS, P*M]."""
    from repro.kernels.flit_sim import ref as fs_ref
    P, M = pstack.total_lanes.shape[0], x.shape[0]
    rows = [jnp.repeat(_f32(getattr(pstack, f.name)), M)
            for f in dataclasses.fields(AsymmetricLaneParams)]
    rows.append(jnp.tile(_f32(x), P))
    rows.append(jnp.tile(_f32(y), P))
    pad = jnp.zeros_like(rows[0])
    return jnp.stack(rows + [pad] * (fs_ref.ASYM_ROWS - len(rows)))


def _pipe_param_rows(ks, ucie_line_uis, device_line_uis):
    """Row-stack a pipelining grid into [ASYM_ROWS, K*U*D]."""
    from repro.kernels.flit_sim import ref as fs_ref
    Kk, U, Dn = (ks.shape[0], ucie_line_uis.shape[0],
                 device_line_uis.shape[0])
    rows = [jnp.repeat(_f32(ks), U * Dn),
            jnp.tile(jnp.repeat(_f32(ucie_line_uis), Dn), Kk),
            jnp.tile(_f32(device_line_uis), Kk * U)]
    pad = jnp.zeros_like(rows[0])
    return jnp.stack(rows + [pad] * (fs_ref.ASYM_ROWS - len(rows)))


def _scal_row(values) -> jnp.ndarray:
    """Broadcast-scalar [1, SCAL_COLS] operand from leading values."""
    from repro.kernels.flit_sim import ref as fs_ref
    row = np.zeros((1, fs_ref.SCAL_COLS), np.float32)
    row[0, :len(values)] = values
    return jnp.asarray(row)


def _run_asymmetric_periodic(pstack, x, y, horizon: int, sim: SimConfig):
    """Period-exact asymmetric run (one launch + exact escalation of
    undetected cells).  Returns the report grid, or ``None`` when the
    grid is mostly aperiodic and the chunked core is the better tool."""
    from repro.kernels.flit_sim import ops as fs_ops
    from repro.kernels.flit_sim import ref as fs_ref
    P, M = pstack.total_lanes.shape[0], x.shape[0]
    cells = P * M
    t0 = time.perf_counter()
    # the row-stacking runs INSIDE the cached program: the whole periodic
    # run is one dispatch from the host's point of view
    if sim.engine == "pallas":
        tile, cpad = fs_ops.tile_for(cells, fs_ops.ASYM_PERIODIC_MAX_TILE)

        def build(ps, xs, ys):
            rows = fs_ops.pad_cells(_asym_param_rows(ps, xs, ys), cpad)
            return fs_ops.asymmetric_periodic_launch(
                rows, n_accesses=horizon, tile=tile, cells=cells)[0]
    else:
        def build(ps, xs, ys):
            return fs_ref.asymmetric_periodic_compute(
                _asym_param_rows(ps, xs, ys), n_accesses=horizon)
    fn = cached_program("flitsim.asymmetric",
                        (P, M, horizon, "periodic") + sim.key(),
                        build, (pstack, x, y))
    out = fn(pstack, x, y)
    det_np = np.asarray(out[1, :cells]) > 0.5
    undet = int((~det_np).sum())
    if undet > max(cells // 4, 8):
        return None
    rep = out[0, :cells].reshape(P, M)
    launches = 1
    if undet:
        conv_np = det_np.reshape(P, M)
        rep = _escalate_stragglers(
            "flitsim.asymmetric",
            functools.partial(_asymmetric_cells_grid, n_accesses=horizon),
            horizon, rep, conv_np,
            lambda idx: (_gather_cells(pstack, idx[:, 0]),
                         jnp.asarray(np.asarray(x)[idx[:, 1]]),
                         jnp.asarray(np.asarray(y)[idx[:, 1]])))
        launches += 1
    jax.block_until_ready(rep)
    conv_at = np.where(det_np, 1, -1).astype(np.int32).reshape(P, M)
    _record_adaptive("flitsim.asymmetric", horizon, fs_ref.PERIOD_OBS, 1,
                     conv_at, undet, engine=sim.engine, launches=launches,
                     elapsed_s=time.perf_counter() - t0,
                     periods=out[2, :cells])
    return rep


def _run_symmetric_periodic(pstack, x, y, backlogs, horizon: int,
                            sim: SimConfig):
    """Period-exact symmetric run (one launch + exact escalation of
    undetected cells).  Detection is an EXACT f32 match of the full
    7-component pool/credit core against a lagged observation row — a
    trajectory certificate, so detected cells reproduce the fixed
    engine's report bit-for-bit.  Returns the report grid, or ``None``
    when the grid is mostly aperiodic (saturated backlogs) and the
    chunked core is the better tool."""
    from repro.kernels.flit_sim import ops as fs_ops
    from repro.kernels.flit_sim import ref as fs_ref
    P, B, M = pstack.g_slots.shape[0], backlogs.shape[0], x.shape[0]
    cells = P * B * M
    t0 = time.perf_counter()
    # the row-stacking runs INSIDE the cached program: the whole periodic
    # run is one dispatch from the host's point of view
    if sim.engine == "pallas":
        tile, cpad = fs_ops.tile_for(cells, fs_ops.SYM_PERIODIC_MAX_TILE)

        def build(ps, xs, ys, bs):
            rows = fs_ops.pad_cells(_sym_param_rows(ps, xs, ys, bs), cpad)
            return fs_ops.symmetric_periodic_launch(
                rows, n_flits=horizon, tile=tile, cells=cells)[0]
    else:
        def build(ps, xs, ys, bs):
            return fs_ref.symmetric_periodic_compute(
                _sym_param_rows(ps, xs, ys, bs), n_flits=horizon)
    fn = cached_program("flitsim.symmetric",
                        (P, B, M, horizon, "periodic") + sim.key(),
                        build, (pstack, x, y, backlogs))
    out = fn(pstack, x, y, backlogs)
    det_np = np.asarray(out[1, :cells]) > 0.5
    undet = int((~det_np).sum())
    if undet > max(cells // 4, 8):
        return None
    rep = out[0, :cells].reshape(P, B, M)
    launches = 1
    if undet:
        conv_np = det_np.reshape(P, B, M)
        rep = _escalate_stragglers(
            "flitsim.symmetric",
            functools.partial(_symmetric_cells_grid, n_flits=horizon),
            horizon, rep, conv_np,
            lambda idx: (_gather_cells(pstack, idx[:, 0]),
                         jnp.asarray(np.asarray(x)[idx[:, 2]]),
                         jnp.asarray(np.asarray(y)[idx[:, 2]]),
                         jnp.asarray(np.asarray(backlogs)[idx[:, 1]])))
        launches += 1
    jax.block_until_ready(rep)
    conv_at = np.where(det_np, 1, -1).astype(np.int32).reshape(P, B, M)
    _record_adaptive("flitsim.symmetric", horizon, fs_ref.SYM_PERIOD_OBS,
                     1, conv_at, undet, engine=sim.engine,
                     launches=launches,
                     elapsed_s=time.perf_counter() - t0,
                     periods=out[2, :cells])
    return rep


def _run_symmetric_pallas(pstack, x, y, backlogs, horizon: int,
                          chunk: int, sim: SimConfig):
    """Host-driven adaptive symmetric loop on the fused chunk kernel: one
    launch per chunk; report / drift / convergence evaluated in-kernel;
    the host reads back one flag row per chunk to steer the early exit.
    Chunk-boundary histories stay as a host-side list of device rows (the
    kernel receives exactly the rows the report formula needs)."""
    from repro.kernels.flit_sim import ops as fs_ops
    P, B, M = pstack.g_slots.shape[0], backlogs.shape[0], x.shape[0]
    cells = P * B * M
    K = horizon // chunk
    K0 = max(K // 4, 1)
    min_k = max(_MIN_EXIT_CHUNKS, K0 + 1)
    budget = _escalation_budget(cells, chunk, horizon)
    t0 = time.perf_counter()
    tile, cpad = fs_ops.tile_for(cells)
    params = fs_ops.pad_cells(_sym_param_rows(pstack, x, y, backlogs),
                              cpad)
    state = jnp.zeros((fs_ops.SYM_ROWS, cpad), jnp.float32)
    zrow = jnp.zeros((1, cpad), jnp.float32)
    z5 = jnp.zeros((5, cpad), jnp.float32)
    z6 = jnp.zeros((6, cpad), jnp.float32)
    Dh, TDh, Ph = [zrow], [zrow], [z5]

    def hist_for(k: int):
        m = max(k - 4, (k + 1) // 2)
        mid = (m + k + 1) // 2
        return m, mid, jnp.concatenate([
            Ph[max(k - _DRIFT_SPAN, 0)],
            Dh[m] if m < k else zrow, TDh[m] if m < k else zrow,
            Dh[mid] if mid < k else zrow, TDh[mid] if mid < k else zrow,
            Dh[K0] if k > K0 else zrow, z6])

    def scal_for(k: int, m: int, mid: int):
        return _scal_row([k, m, mid, K0, K, chunk, sim.tol,
                          1.0 if (k >= min_k and k > _DRIFT_SPAN) else 0.0,
                          1.0 if k >= K else 0.0, _DRIFT_TOL_SLOTS])

    m1, mid1, hist1 = hist_for(1)
    launch = cached_program(
        "flitsim.symmetric",
        (P, B, M, horizon, "pallas-chunk") + sim.key(),
        functools.partial(fs_ops.symmetric_chunk_launch, chunk=chunk,
                          tile=tile, cells=cells),
        (params, state, hist1, scal_for(1, m1, mid1)))
    conv_at = np.full(cells, -1, np.int32)
    conv_np = np.zeros(cells, bool)
    k = 0
    while k < K:
        k += 1
        m, mid, hist = hist_for(k)
        state, conv = launch(params, state, hist, scal_for(k, m, mid))
        Dh.append(state[7:8])
        TDh.append(state[8:9])
        Ph.append(state[0:5])
        conv_np = np.asarray(conv)
        conv_at[(conv_at < 0) & conv_np] = k
        if int((~conv_np).sum()) <= budget:
            break
    rep = state[10, :cells].reshape(P, B, M)
    stragglers = int((~conv_np).sum()) if budget > 0 else 0
    launches = k
    if stragglers:
        rep = _escalate_stragglers(
            "flitsim.symmetric",
            functools.partial(_symmetric_cells_grid, n_flits=horizon),
            horizon, rep, conv_np.reshape(P, B, M),
            lambda idx: (_gather_cells(pstack, idx[:, 0]),
                         jnp.asarray(np.asarray(x)[idx[:, 2]]),
                         jnp.asarray(np.asarray(y)[idx[:, 2]]),
                         jnp.asarray(np.asarray(backlogs)[idx[:, 1]])))
        launches += 1
    jax.block_until_ready(rep)
    _record_adaptive("flitsim.symmetric", horizon, chunk, k,
                     conv_at.reshape(P, B, M), stragglers,
                     engine="pallas", launches=launches,
                     elapsed_s=time.perf_counter() - t0)
    return rep


def _run_pipelining_pallas(ks, ucie_line_uis, device_line_uis,
                           horizon: int, chunk: int, sim: SimConfig):
    """Host-driven adaptive pipelining loop on the fused chunk kernel
    (same shape as the symmetric loop; no drift guard / escalation —
    the rotation report converges monotonically)."""
    from repro.kernels.flit_sim import ops as fs_ops
    Kk, U, Dn = (ks.shape[0], ucie_line_uis.shape[0],
                 device_line_uis.shape[0])
    cells = Kk * U * Dn
    K = horizon // chunk
    min_k = min(_MIN_EXIT_CHUNKS, K)
    t0 = time.perf_counter()
    tile, cpad = fs_ops.tile_for(cells)
    params = fs_ops.pad_cells(
        _pipe_param_rows(ks, ucie_line_uis, device_line_uis), cpad)
    state = jnp.zeros((fs_ops.PIPE_ROWS, cpad), jnp.float32)
    hist = jnp.zeros((fs_ops.ASYM_ROWS, cpad), jnp.float32)

    def scal_for(k: int):
        return _scal_row([k, K, chunk, sim.tol,
                          1.0 if k >= min_k else 0.0,
                          1.0 if k >= K else 0.0, horizon])

    launch = cached_program(
        "flitsim.pipelining",
        (Kk, U, Dn, horizon, "pallas-chunk") + sim.key(),
        functools.partial(fs_ops.pipelining_chunk_launch, chunk=chunk,
                          tile=tile, cells=cells),
        (params, state, hist, scal_for(1)))
    conv_at = np.full(cells, -1, np.int32)
    k = 0
    while k < K:
        k += 1
        state, conv = launch(params, state, hist, scal_for(k))
        if k == 1:      # T1 anchor for the linear-growth extrapolation
            hist = jnp.concatenate(
                [state[8:9], jnp.zeros((7, cpad), jnp.float32)])
        conv_np = np.asarray(conv)
        conv_at[(conv_at < 0) & conv_np] = k
        if int((~conv_np).sum()) == 0:
            break
    rep = state[10, :cells].reshape(Kk, U, Dn)
    jax.block_until_ready(rep)
    _record_adaptive("flitsim.pipelining", horizon, chunk, k,
                     conv_at.reshape(Kk, U, Dn), 0, engine="pallas",
                     launches=k, elapsed_s=time.perf_counter() - t0)
    return rep


def _run_symmetric(pstack, x, y, backlogs, n_flits: int,
                   sim: Optional[SimConfig] = None):
    sim = sim if sim is not None else FIXED_SIM
    P, B, M = pstack.g_slots.shape[0], backlogs.shape[0], x.shape[0]
    if sim.mode == "fixed":
        fn = cached_program(
            "flitsim.symmetric", (P, B, M, n_flits) + sim.key(),
            functools.partial(_symmetric_grid, n_flits=n_flits),
            (pstack, x, y, backlogs))
        return fn(pstack, x, y, backlogs)
    horizon = sim.horizon(n_flits)
    chunk = _divisor_chunk(horizon, sim.chunk)
    if chunk < 8:               # divisor-poor horizon: adaptive degrades
        return _run_symmetric(pstack, x, y, backlogs, horizon,
                              sim=FIXED_SIM)
    from repro.kernels.flit_sim.ref import (
        SYM_PERIOD_OBS, SYM_PERIODIC_MAX_BACKLOG,
    )
    if (horizon // 4 >= SYM_PERIOD_OBS
            and float(np.max(np.asarray(backlogs)))
            <= SYM_PERIODIC_MAX_BACKLOG):
        # period-exact cut (both engines): observe the pool-state window
        # before the warm window opens and extrapolate bitwise; falls
        # through to the chunked core on mostly aperiodic grids (None).
        # Saturated grids skip the probe outright (see the
        # SYM_PERIODIC_MAX_BACKLOG note in kernels/flit_sim/ref.py)
        rep = _run_symmetric_periodic(pstack, x, y, backlogs, horizon,
                                      sim)
        if rep is not None:
            return rep
    if sim.engine == "pallas":
        return _run_symmetric_pallas(pstack, x, y, backlogs, horizon,
                                     chunk, sim)
    t0 = time.perf_counter()
    budget = _escalation_budget(P * B * M, chunk, horizon)
    fn = cached_program(
        "flitsim.symmetric", (P, B, M, horizon) + sim.key(),
        functools.partial(_symmetric_grid_adaptive, n_flits=horizon,
                          chunk=chunk, unroll=int(sim.unroll),
                          tol=float(sim.tol), budget=budget),
        (pstack, x, y, backlogs))
    rep, conv, k_exit, conv_at = fn(pstack, x, y, backlogs)
    stragglers = 0
    if budget > 0:                      # budget 0 can only exit converged
        conv_np = np.asarray(conv)
        stragglers = int((~conv_np).sum())
        if stragglers:
            rep = _escalate_stragglers(
                "flitsim.symmetric",
                functools.partial(_symmetric_cells_grid, n_flits=horizon),
                horizon, rep, conv_np,
                lambda idx: (_gather_cells(pstack, idx[:, 0]),
                             jnp.asarray(np.asarray(x)[idx[:, 2]]),
                             jnp.asarray(np.asarray(y)[idx[:, 2]]),
                             jnp.asarray(np.asarray(backlogs)[idx[:, 1]])))
    jax.block_until_ready(rep)
    _record_adaptive("flitsim.symmetric", horizon, chunk, k_exit, conv_at,
                     stragglers, engine="xla",
                     launches=1 + (1 if stragglers else 0),
                     elapsed_s=time.perf_counter() - t0)
    return rep


def _run_asymmetric(pstack, x, y, n_accesses: int,
                    sim: Optional[SimConfig] = None):
    sim = sim if sim is not None else FIXED_SIM
    P, M = pstack.total_lanes.shape[0], x.shape[0]
    if sim.mode == "fixed":
        fn = cached_program(
            "flitsim.asymmetric", (P, M, n_accesses) + sim.key(),
            functools.partial(_asymmetric_grid, n_accesses=n_accesses),
            (pstack, x, y))
        return fn(pstack, x, y)
    horizon = sim.horizon(n_accesses)
    chunk = _divisor_chunk(horizon, sim.chunk)
    if chunk < 8:
        return _run_asymmetric(pstack, x, y, horizon, sim=FIXED_SIM)
    from repro.kernels.flit_sim.ref import PERIOD_OBS
    if horizon >= PERIOD_OBS:
        # period-exact cut (both engines): observe ~2 credit periods and
        # extrapolate; falls through to the chunked core on mostly
        # aperiodic grids (None)
        rep = _run_asymmetric_periodic(pstack, x, y, horizon, sim)
        if rep is not None:
            return rep
    t0 = time.perf_counter()
    budget = _escalation_budget(P * M, chunk, horizon)
    fn = cached_program(
        "flitsim.asymmetric", (P, M, horizon) + sim.key(),
        functools.partial(_asymmetric_grid_adaptive, n_accesses=horizon,
                          chunk=chunk, unroll=int(sim.unroll),
                          tol=float(sim.tol), budget=budget),
        (pstack, x, y))
    rep, conv, k_exit, conv_at = fn(pstack, x, y)
    stragglers = 0
    if budget > 0:
        conv_np = np.asarray(conv)
        stragglers = int((~conv_np).sum())
        if stragglers:
            rep = _escalate_stragglers(
                "flitsim.asymmetric",
                functools.partial(_asymmetric_cells_grid,
                                  n_accesses=horizon),
                horizon, rep, conv_np,
                lambda idx: (_gather_cells(pstack, idx[:, 0]),
                             jnp.asarray(np.asarray(x)[idx[:, 1]]),
                             jnp.asarray(np.asarray(y)[idx[:, 1]])))
    jax.block_until_ready(rep)
    _record_adaptive("flitsim.asymmetric", horizon, chunk, k_exit, conv_at,
                     stragglers, engine="xla",
                     launches=1 + (1 if stragglers else 0),
                     elapsed_s=time.perf_counter() - t0)
    return rep


def _run_pipelining(ks, ucie_line_uis, device_line_uis, max_k: int,
                    n_lines: int, sim: Optional[SimConfig] = None):
    sim = sim if sim is not None else FIXED_SIM
    shape = (ks.shape[0], ucie_line_uis.shape[0], device_line_uis.shape[0])
    if sim.mode == "fixed":
        fn = cached_program(
            "flitsim.pipelining", shape + (max_k, n_lines) + sim.key(),
            functools.partial(_pipelining_grid, max_k=max_k,
                              n_lines=n_lines),
            (ks, ucie_line_uis, device_line_uis))
        return fn(ks, ucie_line_uis, device_line_uis)
    horizon = sim.horizon(n_lines)
    chunk = _divisor_chunk(horizon, sim.chunk)
    if chunk < 8:
        return _run_pipelining(ks, ucie_line_uis, device_line_uis, max_k,
                               horizon, sim=FIXED_SIM)
    if sim.engine == "pallas":
        from repro.kernels.flit_sim.ref import PIPE_MAX_K
        if max_k <= PIPE_MAX_K:     # kernel holds PIPE_MAX_K device rows
            return _run_pipelining_pallas(ks, ucie_line_uis,
                                          device_line_uis, horizon, chunk,
                                          sim)
    t0 = time.perf_counter()
    fn = cached_program(
        "flitsim.pipelining", shape + (max_k, horizon) + sim.key(),
        functools.partial(_pipelining_grid_adaptive, max_k=max_k,
                          n_lines=horizon, chunk=chunk,
                          unroll=int(sim.unroll), tol=float(sim.tol)),
        (ks, ucie_line_uis, device_line_uis))
    rep, conv, k_exit, conv_at = fn(ks, ucie_line_uis, device_line_uis)
    jax.block_until_ready(rep)
    _record_adaptive("flitsim.pipelining", horizon, chunk, k_exit, conv_at,
                     0,                 # exits only converged / at horizon
                     engine="xla", launches=1,
                     elapsed_s=time.perf_counter() - t0)
    return rep


def _run_symmetric_trace(pstack, xs, ys, bls, cycles: int,
                         sim: SimConfig):
    """Trace-scan runner: ``xs/ys/bls`` are ``[T, N]`` phase grids;
    returns per-phase efficiency ``[P, T, N]``.  Shapes (not phase data)
    key the cache, so alternating same-shaped traces stays warm."""
    P = pstack.flit_bits.shape[0]
    T, N = xs.shape
    fn = cached_program(
        "flitsim.symmetric", ("trace", P, T, N, cycles) + sim.key(),
        functools.partial(_symmetric_trace_grid, n_phases=N,
                          cycles=cycles),
        (pstack, xs, ys, bls))
    rep = fn(pstack, xs, ys, bls)
    _record_trace("flitsim.symmetric", N, cycles, P * T)
    return rep


def _run_asymmetric_trace(pstack, xs, ys, cycles: int, sim: SimConfig):
    """Trace-scan runner for the asymmetric family: ``[P, T, N]``."""
    P = pstack.total_lanes.shape[0]
    T, N = xs.shape
    fn = cached_program(
        "flitsim.asymmetric", ("trace", P, T, N, cycles) + sim.key(),
        functools.partial(_asymmetric_trace_grid, n_phases=N,
                          cycles=cycles),
        (pstack, xs, ys))
    rep = fn(pstack, xs, ys)
    _record_trace("flitsim.asymmetric", N, cycles, P * T)
    return rep


# -- engine entry point (what DesignSpace lowers onto) ------------------------


def simulate_grid(protocols: Sequence[str], x, y, backlogs, *,
                  perturbations: Optional[Sequence[Mapping[str, float]]]
                  = None,
                  n_flits: int = 2048,
                  n_accesses: int = 4096,
                  sim: Optional[SimConfig] = None) -> jnp.ndarray:
    """Evaluate the full ``[Q perturbations, P protocols, B backlogs,
    M mixes]`` grid, one compiled call per simulator family.

    ``x`` / ``y`` are flat ``[M]`` mix arrays; ``backlogs`` is ``[B]``
    (symmetric family only — asymmetric rows broadcast across it).
    ``perturbations`` are multiplicative ``{field: scale}`` overrides
    folded into the parameter stacks (the protocol axis becomes ``Q*P``
    rows of one pytree), so sensitivity sweeps ride the exact same
    executables as the baseline.  Returns efficiency ``[Q, P, B, M]``.

    ``sim`` selects the execution config: :data:`FIXED_SIM` (default,
    bit-identical full-horizon scan) or :data:`ADAPTIVE_SIM` (chunked
    early-exit cores, <= tol-scale deviation; see
    :func:`last_run_info` for the cycles-to-convergence telemetry).
    """
    keys = tuple(protocols)
    unknown = sorted(k for k in keys
                     if k not in SYMMETRIC_PARAMS
                     and k not in ASYMMETRIC_PARAMS)
    if unknown:
        raise ValueError(f"unknown protocol keys {unknown}; "
                         f"choose from {sorted(SIMULATORS)}")
    perts = [dict(p) for p in (perturbations or [{}])]
    active_fields: set = set()
    if any(k in SYMMETRIC_PARAMS for k in keys):
        active_fields |= {f.name
                          for f in dataclasses.fields(SymmetricFlitParams)}
    if any(k in ASYMMETRIC_PARAMS for k in keys):
        active_fields |= {f.name
                          for f in dataclasses.fields(AsymmetricLaneParams)}
    for p in perts:
        _check_perturbation(p)
        # a perturbation that touches NO field of the selected families
        # would silently produce a baseline row labeled as perturbed
        if p and not set(p) & active_fields:
            raise ValueError(
                f"perturbation {p} applies to no parameter of the selected "
                f"protocols {keys}; applicable fields: "
                f"{sorted(active_fields)}")
    x = _f32(np.asarray(x).reshape(-1))
    y = _f32(np.asarray(y).reshape(-1))
    b = _f32(np.asarray(backlogs).reshape(-1))
    n_q, n_b, n_m = len(perts), b.shape[0], x.shape[0]

    per_key: Dict[str, jnp.ndarray] = {}            # key -> [Q, B, M]
    sym_keys = [k for k in keys if k in SYMMETRIC_PARAMS]
    if sym_keys:
        pstack = SymmetricFlitParams.stack(
            [SYMMETRIC_PARAMS[k].perturbed(p) for p in perts
             for k in sym_keys])
        grid = _run_symmetric(pstack, x, y, b, int(n_flits), sim=sim)
        grid = grid.reshape((n_q, len(sym_keys), n_b, n_m))
        for i, k in enumerate(sym_keys):
            per_key[k] = grid[:, i]
    asym_keys = [k for k in keys if k in ASYMMETRIC_PARAMS]
    if asym_keys:
        pstack = AsymmetricLaneParams.stack(
            [ASYMMETRIC_PARAMS[k].perturbed(p) for p in perts
             for k in asym_keys])
        grid = _run_asymmetric(pstack, x, y, int(n_accesses), sim=sim)
        grid = grid.reshape((n_q, len(asym_keys), n_m))
        for i, k in enumerate(asym_keys):
            per_key[k] = jnp.broadcast_to(grid[:, i, None, :],
                                          (n_q, n_b, n_m))
    return jnp.stack([per_key[k] for k in keys], axis=1)   # [Q, P, B, M]


def simulate_trace_grid(protocols: Sequence[str], xs, ys, backlogs, *,
                        perturbations: Optional[
                            Sequence[Mapping[str, float]]] = None,
                        n_flits: int = 2048, n_accesses: int = 4096,
                        sim: Optional[SimConfig] = None) -> jnp.ndarray:
    """Evaluate ``T`` traffic traces of ``N`` phases each through the
    trace-scan cores: per-PHASE efficiency ``[Q, P, T, N]``.

    ``xs`` / ``ys`` / ``backlogs`` are ``[T, N]`` phase grids (read /
    write mix percentages and queue backlog per phase).  Queue and credit
    state carries across phase boundaries inside each (protocol, trace)
    cell, so phase ``n``'s efficiency includes the transient inherited
    from phase ``n-1``; a single-phase trace is bit-identical to the
    fixed static cell at the same (mix, backlog).  Asymmetric protocols
    ignore the backlog grid, exactly as in :func:`simulate_grid`.

    Every phase runs ``sim.trace_cycles`` cycles (default: the family's
    static horizon — ``n_flits`` symmetric, ``n_accesses`` asymmetric).
    Phase DURATIONS are not consumed here: the design space applies them
    as aggregation weights over the returned per-phase grid.
    """
    sim = sim if sim is not None else FIXED_SIM
    keys = tuple(protocols)
    unknown = sorted(k for k in keys
                     if k not in SYMMETRIC_PARAMS
                     and k not in ASYMMETRIC_PARAMS)
    if unknown:
        raise ValueError(f"unknown protocol keys {unknown}; "
                         f"choose from {sorted(SIMULATORS)}")
    perts = [dict(p) for p in (perturbations or [{}])]
    active_fields: set = set()
    if any(k in SYMMETRIC_PARAMS for k in keys):
        active_fields |= {f.name
                          for f in dataclasses.fields(SymmetricFlitParams)}
    if any(k in ASYMMETRIC_PARAMS for k in keys):
        active_fields |= {f.name
                          for f in dataclasses.fields(AsymmetricLaneParams)}
    for p in perts:
        _check_perturbation(p)
        if p and not set(p) & active_fields:
            raise ValueError(
                f"perturbation {p} applies to no parameter of the selected "
                f"protocols {keys}; applicable fields: "
                f"{sorted(active_fields)}")
    xs = _f32(np.asarray(xs))
    ys = _f32(np.asarray(ys))
    bls = _f32(np.asarray(backlogs))
    if xs.ndim != 2 or xs.shape != ys.shape or xs.shape != bls.shape:
        raise ValueError(
            f"trace phase grids must share one [T, N] shape; got "
            f"xs {xs.shape}, ys {ys.shape}, backlogs {bls.shape}")
    n_q, (n_t, n_p) = len(perts), xs.shape

    per_key: Dict[str, jnp.ndarray] = {}            # key -> [Q, T, N]
    sym_keys = [k for k in keys if k in SYMMETRIC_PARAMS]
    if sym_keys:
        cycles = int(sim.trace_cycles or n_flits)
        pstack = SymmetricFlitParams.stack(
            [SYMMETRIC_PARAMS[k].perturbed(p) for p in perts
             for k in sym_keys])
        grid = _run_symmetric_trace(pstack, xs, ys, bls, cycles, sim)
        grid = grid.reshape((n_q, len(sym_keys), n_t, n_p))
        for i, k in enumerate(sym_keys):
            per_key[k] = grid[:, i]
    asym_keys = [k for k in keys if k in ASYMMETRIC_PARAMS]
    if asym_keys:
        cycles = int(sim.trace_cycles or n_accesses)
        pstack = AsymmetricLaneParams.stack(
            [ASYMMETRIC_PARAMS[k].perturbed(p) for p in perts
             for k in asym_keys])
        grid = _run_asymmetric_trace(pstack, xs, ys, cycles, sim)
        grid = grid.reshape((n_q, len(asym_keys), n_t, n_p))
        for i, k in enumerate(asym_keys):
            per_key[k] = grid[:, i]
    return jnp.stack([per_key[k] for k in keys], axis=1)   # [Q, P, T, N]


# -- scalar entry points (thin wrappers over a [1, 1, 1] grid) ----------------


def simulate_symmetric(params: SymmetricFlitParams, x: float, y: float,
                       n_flits: int = 2048,
                       backlog: float = 64) -> float:
    """Single-point symmetric simulation; shares the sweep compile cache."""
    _check_mix(x, y)
    pstack = SymmetricFlitParams.stack([params])
    eff = _run_symmetric(pstack, _f32([x]), _f32([y]), _f32([backlog]),
                         int(n_flits))
    return float(eff[0, 0, 0])


def simulate_asymmetric(params: AsymmetricLaneParams, x: float, y: float,
                        n_accesses: int = 4096) -> float:
    """Single-point asymmetric simulation; shares the sweep compile cache."""
    _check_mix(x, y)
    pstack = AsymmetricLaneParams.stack([params])
    eff = _run_asymmetric(pstack, _f32([x]), _f32([y]), int(n_accesses))
    return float(eff[0, 0])


_PIPELINING_PAD_K = 8     # pad the ready-table so all k <= 8 share one exe


def simulate_lpddr6_pipelining(num_devices: int, n_lines: int = 512,
                               ucie_line_ui: float = 16,
                               device_line_ui: float = 64) -> float:
    """Single-k Fig-13 pipelining simulation; shares the sweep cache."""
    max_k = max(int(num_devices), _PIPELINING_PAD_K)
    u = _run_pipelining(jnp.asarray([num_devices], jnp.int32),
                        _f32([ucie_line_ui]), _f32([device_line_ui]),
                        max_k, int(n_lines))
    return float(u[0, 0, 0])


# -- sweep API ---------------------------------------------------------------


#: The five canonical read:write mixes every validation sweep covers.
CANONICAL_MIXES: Tuple[Tuple[float, float], ...] = (
    (1.0, 0.0), (2.0, 1.0), (1.0, 1.0), (1.0, 2.0), (0.0, 1.0))

SYMMETRIC_PARAMS: Dict[str, SymmetricFlitParams] = {
    "cxl_unopt": SymmetricFlitParams.cxl_unopt(),
    "cxl_opt": SymmetricFlitParams.cxl_opt(),
    "chi": SymmetricFlitParams.chi(),
}

ASYMMETRIC_PARAMS: Dict[str, AsymmetricLaneParams] = {
    "lpddr6_asym": AsymmetricLaneParams.lpddr6(),
    "hbm_asym": AsymmetricLaneParams.hbm(),
}


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Output of :func:`sweep`.

    ``efficiency`` is ``[P, M]`` when a single backlog was requested and
    ``[P, B, M]`` for a backlog grid; axes follow ``protocols`` /
    ``backlogs`` / ``mixes`` order.
    """

    protocols: Tuple[str, ...]
    mixes: Tuple[Tuple[float, float], ...]
    backlogs: Optional[Tuple[float, ...]]
    efficiency: jnp.ndarray

    def for_protocol(self, key: str) -> jnp.ndarray:
        return self.efficiency[self.protocols.index(key)]


def _normalize_mixes(mixes) -> Tuple[Tuple[float, float], ...]:
    if mixes is None:
        return CANONICAL_MIXES
    out = []
    for m in mixes:
        if hasattr(m, "x") and hasattr(m, "y"):     # TrafficMix
            x, y = float(m.x), float(m.y)
        else:
            x, y = m
            x, y = float(x), float(y)
        _check_mix(x, y)
        out.append((x, y))
    return tuple(out)


def _sweep_impl(protocols: Optional[Sequence[str]] = None,
                mixes=None,
                backlogs: Union[None, float, Sequence[float]] = None,
                *, n_flits: int = 2048, n_accesses: int = 4096,
                sim: Optional[SimConfig] = None) -> SweepResult:
    """Engine body of the retired ``sweep`` front-end — internal
    callers (``backlog_knees``) use this directly, warning-free."""
    keys = tuple(protocols) if protocols is not None else tuple(SIMULATORS)
    if not keys:
        raise ValueError("sweep() needs at least one protocol key")
    mix_tuples = _normalize_mixes(mixes)
    if not mix_tuples:
        raise ValueError("sweep() needs at least one traffic mix")
    squeeze_b = backlogs is None or np.ndim(backlogs) == 0
    if backlogs is None:
        backlog_vals: Tuple[float, ...] = (64.0,)
    else:
        backlog_vals = tuple(
            float(b) for b in np.atleast_1d(np.asarray(backlogs)))

    x = _f32([m[0] for m in mix_tuples])
    y = _f32([m[1] for m in mix_tuples])
    eff = simulate_grid(keys, x, y, backlog_vals, n_flits=n_flits,
                        n_accesses=n_accesses, sim=sim)[0]  # [P, B, M]
    if squeeze_b:
        return SweepResult(protocols=keys, mixes=mix_tuples, backlogs=None,
                           efficiency=eff[:, 0, :])
    return SweepResult(protocols=keys, mixes=mix_tuples,
                       backlogs=backlog_vals, efficiency=eff)


def sweep_perturbed(perturbations: Sequence[Mapping[str, float]],
                    protocols: Optional[Sequence[str]] = None,
                    mixes=None,
                    backlogs: Union[None, float, Sequence[float]] = None,
                    *, n_flits: int = 2048, n_accesses: int = 4096,
                    sim: Optional[SimConfig] = None):
    """Protocol-parameter sensitivity sweep: multiplicative ``{field:
    scale}`` perturbations (slot counts, credit limits, lane splits) over
    the existing pytree param stacks.

    Front-end over the axes-first API: returns a
    :class:`repro.core.space.SpaceResult` whose ``sim_efficiency`` array
    carries a ``protocol_param`` axis — include ``{}`` as the first
    perturbation to get the baseline row for free.
    """
    from repro.core.space import DesignSpace, axis
    keys = tuple(protocols) if protocols is not None else tuple(SIMULATORS)
    axes = [axis("protocol_param", list(perturbations)),
            axis("protocol", keys),
            axis("mix", _normalize_mixes(mixes))]
    if backlogs is not None and np.ndim(backlogs) > 0:
        axes.append(axis("backlog", list(np.atleast_1d(backlogs))))
        default_backlog = 64.0
    else:
        default_backlog = 64.0 if backlogs is None else float(backlogs)
    return DesignSpace(axes, default_backlog=default_backlog,
                       n_flits=n_flits, n_accesses=n_accesses,
                       sim=sim).evaluate(metrics=("sim_efficiency",))


#: Default queue-depth axis for knee extraction — doubling steps wide
#: enough to bracket every simulated protocol's saturation cliff.
KNEE_BACKLOGS: Tuple[float, ...] = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0,
                                    128.0)


def backlog_knees(mixes=None,
                  backlogs: Sequence[float] = KNEE_BACKLOGS,
                  knee_frac: float = 0.95,
                  n_flits: int = 2048,
                  per_mix: bool = False,
                  sim: Optional[SimConfig] = None) -> Dict[str, Any]:
    """Efficiency-cliff knee per simulated protocol: the smallest request
    backlog at which simulated data efficiency reaches ``knee_frac`` of
    that protocol's best efficiency over the backlog axis.

    By default the knee is maximized over ``mixes`` (conservative: a
    protocol must hit its knee on every mix) and the result is a scalar
    per protocol.  With ``per_mix=True`` the per-mix knees are returned as
    a ``[M]`` array per protocol — this is what lets the bridge follow
    each workload's own HLO-derived mix along the configs axis instead of
    the canonical-mix envelope.

    One :func:`sweep` call over the ``[P, B, M]`` grid — repeated calls
    with the same grid shape reuse the warm executable.  Asymmetric
    protocols are backlog-independent, so their knee is the smallest
    backlog probed.  The result feeds ``SelectionConstraints.
    max_backlog_knee``: a queue-depth budget the selector enforces.
    """
    res = _sweep_impl(mixes=mixes, backlogs=backlogs, n_flits=n_flits,
                      sim=sim)
    eff = np.asarray(res.efficiency)                    # [P, B, M]
    b = np.asarray(res.backlogs, dtype=np.float64)
    knees: Dict[str, Any] = {}
    for i, key in enumerate(res.protocols):
        e = eff[i]                                      # [B, M]
        ok = e >= knee_frac * e.max(axis=0, keepdims=True)
        first = np.argmax(ok, axis=0)                   # per-mix knee index
        knees[key] = b[first] if per_mix else float(b[first].max())
    return knees


def _sweep_pipelining_impl(ks: Sequence[int], n_lines: int = 512,
                           ucie_line_ui: Union[float, Sequence[float]] = 16,
                           device_line_ui: Union[float, Sequence[float]] = 64,
                           sim: Optional[SimConfig] = None) -> jnp.ndarray:
    """Engine body of the retired ``sweep_pipelining`` front-end
    — the ``k`` / ``ucie_line_ui`` / ``device_line_ui`` axes lower here."""
    ks = tuple(int(k) for k in ks)
    squeeze = (np.ndim(ucie_line_ui) == 0 and np.ndim(device_line_ui) == 0)
    us = _f32(np.atleast_1d(np.asarray(ucie_line_ui, dtype=np.float64)))
    ds = _f32(np.atleast_1d(np.asarray(device_line_ui, dtype=np.float64)))
    max_k = max(max(ks), _PIPELINING_PAD_K)
    util = _run_pipelining(jnp.asarray(ks, jnp.int32), us, ds,
                           max_k, int(n_lines), sim=sim)
    return util[:, 0, 0] if squeeze else util


# -- convenience: analytic counterparts for the property tests ---------------

ANALYTIC = {
    "cxl_unopt": CXLMemOnUCIe(),
    "cxl_opt": CXLMemOptOnUCIe(),
    "chi": CHIOnUCIe(),
    "lpddr6_asym": LPDDR6OnUCIe(),
    "hbm_asym": HBMOnUCIe(),
}

SIMULATORS = {
    "cxl_unopt": lambda x, y: simulate_symmetric(SymmetricFlitParams.cxl_unopt(), x, y),
    "cxl_opt": lambda x, y: simulate_symmetric(SymmetricFlitParams.cxl_opt(), x, y),
    "chi": lambda x, y: simulate_symmetric(SymmetricFlitParams.chi(), x, y),
    "lpddr6_asym": lambda x, y: simulate_asymmetric(AsymmetricLaneParams.lpddr6(), x, y),
    "hbm_asym": lambda x, y: simulate_asymmetric(AsymmetricLaneParams.hbm(), x, y),
}
