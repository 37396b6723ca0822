"""Decode attention (flash-decode), Pallas TPU: one new token per sequence
attends to a long KV cache.  Memory-bound — the kernel's job is to stream
the cache through VMEM exactly once at full HBM bandwidth.

Grid (B, Hkv, S/block_k), KV innermost/sequential; all G=H/Hkv query heads
of a kv group ride in one program so the cache block is read once per
group, not once per head.

  vmem = G*hd (q) + 2*block_k*hd (k,v) + G*(hd+2) f32 accumulators
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_k: int):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]
    k_start = j * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, bk)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kj < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q, k, v, lengths, *, block_k: int = 512,
                     interpret: bool = False):
    """q: (B, H, hd); k, v: (B, Hkv, S, hd); lengths: (B,) int32
    -> (B, H, hd).  Positions >= lengths[b] are masked."""
    B, H, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = H // Hkv
    block_k = min(block_k, S)
    scale = hd ** -0.5
    qg = q.reshape(B, Hkv, G, hd)
    grid = (B, Hkv, S // block_k)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # lengths prefetch
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(B, H, hd)
