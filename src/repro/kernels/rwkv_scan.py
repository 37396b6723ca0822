"""Chunked WKV6 recurrence (RWKV6 time-mix core), Pallas TPU.

Same matmul-dense chunk math as ``models.rwkv._wkv_chunk`` (exponents
relative to the chunk start, all bounded), with the cross-chunk state S
(M x M, f32) living in VMEM scratch across the sequential chunk axis.

Grid (B*H, T/C); per-program VMEM:
  4*C*M (r,k,v,logw) + C*M (o) + M*M f32 (S) + C*C f32 (scores)
C=128, M=64: ~0.3 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, sout_ref, s_ref, *,
            chunk: int):
    c = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)            # (C, M)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)          # logw <= 0
    u = u_ref[0].astype(jnp.float32)            # (1, M)

    hi = jax.lax.Precision.HIGHEST
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # the TPU lowering has no cumsum: an inclusive prefix sum over time is
    # a matmul with the lower-triangular ones matrix
    cs = jnp.dot((si <= ti).astype(jnp.float32), lw, precision=hi)  # logA_t
    q_in = r * jnp.exp(cs - lw)                 # r * A_{t-1}   (<= |r|)
    k_in = k * jnp.exp(-cs)                     # bounded by exp(C*decay_max)
    scores = jax.lax.dot_general(q_in, k_in, (((1,), (1,)), ((), ())))
    scores = jnp.where(si < ti, scores, 0.0)    # strict lower triangle
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # bonus (s == t)
    o = scores @ v + diag * v
    o = o + q_in @ s_ref[...]                   # cross-chunk history

    a_tail = jnp.exp(cs[-1:, :] - cs)           # prod_{s>t} w_s
    # whole-chunk decay of S's row i, broadcast along its columns:
    # [i, j] = sum_t logw[t, i]
    decay = jax.lax.dot_general(lw, jnp.ones_like(lw),
                                (((0,), (0,)), ((), ())), precision=hi)
    s_ref[...] = (jnp.exp(decay) * s_ref[...]
                  + jax.lax.dot_general(k * a_tail, v,
                                        (((0,), (0,)), ((), ()))))
    o_ref[0] = o.astype(o_ref.dtype)

    @pl.when(c == nc - 1)
    def _finish():
        sout_ref[0] = s_ref[...].astype(sout_ref.dtype)


def rwkv_scan(r, k, v, logw, u, *, chunk: int = 128,
              interpret: bool = False):
    """r,k,v,logw: (B, H, T, M); u: (H, M) -> (o (B,H,T,M), S (B,H,M,M))."""
    B, H, T, M = r.shape
    chunk = min(chunk, T)
    BH = B * H
    shp = (BH, T, M)
    rf, kf, vf, lwf = (a.reshape(shp) for a in (r, k, v, logw))
    # (BH, 1, M): a (1, 1, M) block spans the array's last two dims, as the
    # TPU lowering requires of a block that is not (8, 128)-aligned
    uf = jnp.broadcast_to(u[None], (B, H, M)).reshape(BH, 1, M)
    grid = (BH, T // chunk)

    o, s = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, M), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, M), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, M), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, M), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, M), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, M), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, M, M), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, M), jnp.float32),
            jax.ShapeDtypeStruct((BH, M, M), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((M, M), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, lwf, uf)
    return o.reshape(B, H, T, M), s.reshape(B, H, M, M)
