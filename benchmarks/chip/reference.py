"""Plain reference of the flit-level link simulators, written from the
paper's slot and lane-group model (arXiv:2510.06513, Appendix) and kept
apart from the program: it imports nothing of ``repro``.

Every cell runs the full fixed horizon, one ``lax.fori_loop`` over cycles
on a flat vector of cells, in the precision it is given (``float32`` as
the configurations state; ``bfloat16`` for the control).

* symmetric (approaches C/D/E): each cycle tops the request backlog up,
  sends headers first (the H slot, then G slots), fills the remaining
  G slots with write data SoC->Mem and with read data Mem->SoC, reads
  gated by the read-return credit and writes by the write buffer; the
  efficiency is the data delivered over both directions' capacity in
  the warm window (the last three quarters of the horizon);
* asymmetric (approaches A/B): accesses are issued in the x:y ratio by a
  fractional read credit; each lane group accumulates its unit
  intervals; the efficiency is ``512 n / (lanes * busiest group time)``.
"""
from __future__ import annotations

import functools
from typing import Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: slot geometry of the symmetric flit protocols: g_slots, h_slots,
#: reqs_per_h, resps_per_h, reqs_per_g, resps_per_g, data_slots_per_line,
#: flit_bits, credit_lines, write_buffer_lines
SYMMETRIC = {
    # CXL.mem on UCIe, 256 B flit: 1 H + 14 G slots of 16 B
    "cxl_unopt": (14, 1, 1, 2, 1, 2, 4, 2048, 8, 8),
    # latency-optimized CXL.mem: 15 G + 1 header-only HS slot
    "cxl_opt": (15, 1, 1, 4, 1, 4, 4, 2048, 8, 8),
    # CHI on UCIe: 12 granules of 20 B, no dedicated header slot
    "chi": (12, 0, 0, 0, 1, 2, 4, 2048, 8, 8),
}
SYM_FIELDS = ("g_slots", "h_slots", "reqs_per_h", "resps_per_h",
              "reqs_per_g", "resps_per_g", "data_slots_per_line",
              "flit_bits", "credit_lines", "write_buffer_lines")

#: lane groups of the asymmetric mappings: total, read, write, command
#: lanes, command bits per access, bits per access (64 B + ECC/meta)
ASYMMETRIC = {
    "lpddr6_asym": (74, 36, 24, 10, 96, 576),
    "hbm_asym": (138, 72, 36, 24, 96, 576),
}
ASYM_FIELDS = ("total_lanes", "read_lanes", "write_lanes", "cmd_lanes",
               "cmd_bits_per_access", "access_bits")


def sym_params(protocols: Sequence[str],
               perturbations: Sequence[Mapping[str, float]]) -> np.ndarray:
    """``[len(perturbations) * len(protocols), 10]`` float64 rows,
    perturbation-major; a perturbation scales the named fields."""
    return _params(SYMMETRIC, SYM_FIELDS, protocols, perturbations)


def asym_params(protocols: Sequence[str],
                perturbations: Sequence[Mapping[str, float]]) -> np.ndarray:
    return _params(ASYMMETRIC, ASYM_FIELDS, protocols, perturbations)


def _params(table, fields, protocols, perturbations) -> np.ndarray:
    rows = []
    for pert in perturbations:
        for key in protocols:
            row = dict(zip(fields, (float(v) for v in table[key])))
            for name, scale in pert.items():
                if name in row:
                    row[name] *= float(scale)
            rows.append([row[f] for f in fields])
    return np.asarray(rows, np.float64)


@functools.partial(jax.jit, static_argnames=("n_flits", "dtype"))
def symmetric_efficiency(p, x, y, backlog, *, n_flits: int, dtype=jnp.float32):
    """Warm-window data efficiency of each cell; ``p`` is ``[C, 10]``
    (:data:`SYM_FIELDS`), ``x``/``y``/``backlog`` are ``[C]``."""
    f = lambda a: jnp.asarray(a, dtype)
    (g, h, rph, sph, rpg, spg, dpl, fbits, cl, wbl) = (
        f(p[:, i]) for i in range(10))
    x, y, backlog = f(x), f(y), f(backlog)
    xr, yr = x / (x + y), y / (x + y)
    rdata_limit = cl * g
    wbuf_limit = wbl * g
    hdr_cap = rph * h + rpg * g
    resp_cap = sph * h + spg * g
    rpg_safe = jnp.maximum(rpg, f(1e-9))
    spg_safe = jnp.maximum(spg, f(1e-9))
    zero = jnp.zeros_like(x)
    warm_from = n_flits // 4

    def cycle(t, s):
        rq, wq, wdata, rdata, resp, cr, cw, delivered = s
        deficit = jnp.maximum(backlog - (rq + wq), f(0))
        cr = cr + deficit * xr
        cw = cw + deficit * yr
        new_r, new_w = jnp.floor(cr), jnp.floor(cw)
        cr, cw = cr - new_r, cw - new_w
        rq, wq = rq + new_r, wq + new_w
        # SoC -> Mem: requests eligible under their data-path credit
        rq_ok = jnp.minimum(rq, jnp.maximum(rdata_limit - rdata, f(0)) / dpl)
        wq_ok = jnp.minimum(wq, jnp.maximum(wbuf_limit - wdata, f(0)) / dpl)
        sent = jnp.minimum(rq_ok + wq_ok, hdr_cap)
        share = jnp.maximum(rq_ok + wq_ok, f(1e-9))
        sent_r = sent * rq_ok / share
        sent_w = sent * wq_ok / share
        g_hdr = jnp.maximum(sent - rph * h, f(0)) / rpg_safe
        up = jnp.minimum(wdata, g - g_hdr)
        rq, wq = rq - sent_r, wq - sent_w
        wdata = wdata + sent_w * dpl - up
        rdata = rdata + sent_r * dpl
        resp = resp + sent_r + sent_w
        # Mem -> SoC: responses first, read data in the rest
        sent_resp = jnp.minimum(resp, resp_cap)
        g_resp = jnp.maximum(sent_resp - sph * h, f(0)) / spg_safe
        down = jnp.minimum(rdata, g - g_resp)
        resp = resp - sent_resp
        rdata = rdata - down
        delivered = jnp.where(t >= warm_from, delivered + (up + down),
                              delivered)
        return rq, wq, wdata, rdata, resp, cr, cw, delivered

    s = jax.lax.fori_loop(0, n_flits, cycle, (zero,) * 8)
    warm = f(n_flits - warm_from)
    return (s[7] * f(128)) / (f(2) * warm * fbits)


@functools.partial(jax.jit, static_argnames=("n_accesses", "dtype"))
def asymmetric_efficiency(p, x, y, *, n_accesses: int, dtype=jnp.float32):
    """Lane-occupancy efficiency of each cell; ``p`` is ``[C, 6]``."""
    f = lambda a: jnp.asarray(a, dtype)
    total, rl, wl, cl, cbits, abits = (f(p[:, i]) for i in range(6))
    x, y = f(x), f(y)
    xr = x / (x + y)
    r_ui, w_ui, c_ui = abits / rl, abits / wl, cbits / cl
    zero = jnp.zeros_like(x)

    def access(_, s):
        t_r, t_w, t_c, credit = s
        credit = credit + xr
        read = credit >= f(1)
        credit = jnp.where(read, credit - f(1), credit)
        t_r = t_r + jnp.where(read, r_ui, f(0))
        t_w = t_w + jnp.where(read, f(0), w_ui)
        return t_r, t_w, t_c + c_ui, credit

    t_r, t_w, t_c, _ = jax.lax.fori_loop(0, n_accesses, access, (zero,) * 4)
    busiest = jnp.maximum(jnp.maximum(t_r, t_w), t_c)
    return f(512 * n_accesses) / (total * busiest)


def _blocks(n: int, block: int):
    for lo in range(0, n, block):
        yield lo, min(lo + block, n)


def run_blocked(fn, arrays: Sequence[np.ndarray], block: int,
                **static) -> np.ndarray:
    """``fn`` over the leading axis in fixed-size blocks (the last one
    padded by repetition, so one program serves every block); float32
    results on the host."""
    n = arrays[0].shape[0]
    out = np.empty(n, np.float32)
    block = min(block, n)
    for lo, hi in _blocks(n, block):
        part = [a[lo:hi] for a in arrays]
        if hi - lo < block:
            part = [np.concatenate([a, np.repeat(a[-1:], block - (hi - lo),
                                                 axis=0)]) for a in part]
        vals = fn(*part, **static)
        out[lo:hi] = np.asarray(vals, np.float32)[:hi - lo]
    return out


def grid_efficiency(sym_protocols, asym_protocols, mixes, backlogs,
                    perturbations, *, n_flits: int, n_accesses: int,
                    dtype=jnp.float32, block: int = 1 << 18
                    ) -> Dict[str, np.ndarray]:
    """Per-protocol efficiency over ``[perturbation, backlog, mix]``.

    The symmetric cells are all simulated; an asymmetric cell depends
    on its perturbation and mix only, so it is simulated once and
    broadcast over backlogs."""
    mixes = np.asarray(mixes, np.float64)
    backlogs = np.asarray(backlogs, np.float64)
    nq, nb, nm = len(perturbations), len(backlogs), len(mixes)
    out: Dict[str, np.ndarray] = {}
    if sym_protocols:
        p = sym_params(sym_protocols, perturbations)          # [Q*P, 10]
        npr = len(sym_protocols)
        rows = np.repeat(np.arange(nq * npr), nb * nm)
        b_i = np.tile(np.repeat(np.arange(nb), nm), nq * npr)
        m_i = np.tile(np.arange(nm), nq * npr * nb)
        eff = run_blocked(
            functools.partial(symmetric_efficiency, n_flits=n_flits,
                              dtype=dtype),
            [p[rows], mixes[m_i, 0], mixes[m_i, 1], backlogs[b_i]], block)
        eff = eff.reshape(nq, npr, nb, nm)
        for i, k in enumerate(sym_protocols):
            out[k] = eff[:, i]
    if asym_protocols:
        p = asym_params(asym_protocols, perturbations)
        npr = len(asym_protocols)
        rows = np.repeat(np.arange(nq * npr), nm)
        m_i = np.tile(np.arange(nm), nq * npr)
        eff = run_blocked(
            functools.partial(asymmetric_efficiency, n_accesses=n_accesses,
                              dtype=dtype),
            [p[rows], mixes[m_i, 0], mixes[m_i, 1]], block)
        eff = eff.reshape(nq, npr, 1, nm)
        for i, k in enumerate(asym_protocols):
            out[k] = np.broadcast_to(eff[:, i], (nq, nb, nm))
    return out
