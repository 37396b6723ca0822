"""Kernel micro-benchmarks: wall time of the XLA reference path on CPU (the
Pallas kernels themselves are TPU-targeted; interpret mode is not a timing
proxy) plus the oracle-vs-kernel agreement as the derived column."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(f, *args, iters=3):
    f(*args)                              # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def all_rows(fast: bool = False):
    rng = np.random.default_rng(0)
    arr = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    rows = []

    B, H, Hkv, T, hd = 1, 4, 2, 256, 64
    q, k, v = arr(B, H, T, hd), arr(B, Hkv, T, hd), arr(B, Hkv, T, hd)
    ref_attn = jax.jit(lambda q, k, v: ref.attention(q, k, v))
    us = _time(ref_attn, q, k, v)
    out = ops.flash_attention(q, k, v, block_q=128, block_k=128,
                              interpret=True)
    err = float(jnp.max(jnp.abs(out - ref.attention(q, k, v))))
    rows.append(("kernel_flash_attention_ref_xla", us, round(err, 6)))

    S = 512
    q1, k1, v1 = arr(B, H, hd), arr(B, Hkv, S, hd), arr(B, Hkv, S, hd)
    lengths = jnp.full((B,), S, jnp.int32)
    ref_dec = jax.jit(ref.decode_attention)
    us = _time(ref_dec, q1, k1, v1, lengths)
    out = ops.decode_attention(q1, k1, v1, lengths, block_k=128,
                               interpret=True)
    err = float(jnp.max(jnp.abs(out - ref.decode_attention(q1, k1, v1,
                                                           lengths))))
    rows.append(("kernel_decode_attention_ref_xla", us, round(err, 6)))

    E, C, D, F = 4, 128, 256, 128
    x, w = arr(E, C, D), arr(E, D, F)
    ref_gmm = jax.jit(ref.moe_gmm)
    us = _time(ref_gmm, x, w)
    out = ops.moe_gmm(x, w, block_c=64, block_f=64, block_d=64,
                      interpret=True)
    err = float(jnp.max(jnp.abs(out - ref.moe_gmm(x, w))))
    rows.append(("kernel_moe_gmm_ref_xla", us, round(err, 5)))

    if not fast:
        B2, H2, T2, M = 1, 2, 128, 32
        r = arr(B2, H2, T2, M); k2 = arr(B2, H2, T2, M); v2 = arr(B2, H2, T2, M)
        logw = -0.105 * jax.nn.sigmoid(arr(B2, H2, T2, M))
        u = arr(H2, M) * 0.1
        ref_rwkv = jax.jit(ref.rwkv_scan)
        us = _time(ref_rwkv, r, k2, v2, logw, u)
        o, _ = ops.rwkv_scan(r, k2, v2, logw, u, chunk=32, interpret=True)
        oe, _ = ref.rwkv_scan(r, k2, v2, logw, u)
        rows.append(("kernel_rwkv_scan_ref_xla", us,
                     round(float(jnp.max(jnp.abs(o - oe))), 6)))

        a = jax.nn.sigmoid(arr(2, 256, 128))
        b = arr(2, 256, 128)
        ref_lru = jax.jit(ref.rglru_scan)
        us = _time(ref_lru, a, b)
        h = ops.rglru_scan(a, b, chunk=64, block_d=64, interpret=True)
        rows.append(("kernel_rglru_scan_ref_xla", us,
                     round(float(jnp.max(jnp.abs(h - ref.rglru_scan(a, b)))),
                           6)))
    return rows
