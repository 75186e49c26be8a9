"""Per-kernel micro-benchmarks (CPU: interpret-mode correctness cost is
not meaningful wall-clock; the jnp oracle timing is reported, with the
kernel's analytic HBM traffic as `derived` — the quantity the roofline
uses for kernel substitution)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import time_us


def run(rows: list):
    # flash attention oracle at serving-ish shape
    from repro.kernels.flash_attention.ref import attention_ref
    b, k, g, s, hd = 1, 8, 4, 1024, 128
    q = jax.random.normal(jax.random.PRNGKey(0), (b, k, g, s, hd),
                          jnp.bfloat16)
    kk = jax.random.normal(jax.random.PRNGKey(1), (b, k, s, hd),
                           jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, k, s, hd), jnp.bfloat16)
    fn = jax.jit(lambda a, b_, c: attention_ref(a, b_, c))
    us = time_us(fn, q, kk, v, iters=3)
    kernel_bytes = (q.size + kk.size + v.size) * 2 + q.size * 2
    xla_bytes = kernel_bytes + b * k * g * s * s * 6   # materialized scores
    rows.append(("kernels/flash_attention", us,
                 f"hbm_bytes_kernel={kernel_bytes:.3g};"
                 f"hbm_bytes_xla~{xla_bytes:.3g};"
                 f"saving=x{xla_bytes / kernel_bytes:.1f}"))

    from repro.kernels.ssd_scan.ref import ssd_ref
    bsz, s2, h, p, n = 2, 512, 8, 64, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (bsz, s2, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (bsz, s2, h)))
    bm = jax.random.normal(jax.random.PRNGKey(2), (bsz, s2, n)) * 0.3
    cm = jax.random.normal(jax.random.PRNGKey(3), (bsz, s2, n)) * 0.3
    alog = jnp.zeros((h,))
    fn2 = jax.jit(lambda *a: ssd_ref(*a)[0])
    us2 = time_us(fn2, x, dt, bm, cm, alog, iters=3)
    rows.append(("kernels/ssd_scan", us2,
                 f"state_bytes={bsz*h*p*n*4};seq={s2}"))

    from repro.models.rglru import lru_scan
    la = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(0),
                                            (2, 1024, 256)))
    bb = jax.random.normal(jax.random.PRNGKey(1), (2, 1024, 256))
    fn3 = jax.jit(lru_scan)
    us3 = time_us(fn3, la, bb, iters=3)
    rows.append(("kernels/rglru_scan", us3, "assoc_scan_oracle"))

    from repro.kernels.flit_pack.ref import pack_flits_ref, flits_needed
    n_lines = 15 * 64
    f = flits_needed(n_lines)
    lines = jax.random.randint(jax.random.PRNGKey(0), (n_lines, 64), 0, 256)
    hdrs = jnp.zeros((f, 10), jnp.int32)
    meta = jnp.zeros((f, 4), jnp.int32)
    fn4 = jax.jit(pack_flits_ref)
    us4 = time_us(fn4, lines, hdrs, meta, iters=5)
    gbs = n_lines * 64 / (us4 * 1e-6) / 1e9
    rows.append(("kernels/flit_pack", us4,
                 f"lines={n_lines};flits={f};cpu_pack_rate={gbs:.2f}GB/s"))
