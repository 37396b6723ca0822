"""Causal GQA flash attention (forward), Pallas TPU.

Dataflow: grid (B*H, Tq/block_q, S/block_k); the KV dimension is the
innermost (sequential) grid axis, so the per-program VMEM working set is
one q block + one kv block + f32 accumulators:

  vmem = block_q*hd (q) + 2*block_k*hd (k,v) + block_q*(hd+2) f32 (acc,m,l)

With block_q = block_k = 512, hd = 128: ~0.8 MB — comfortably in the 16 MB
VMEM budget; block sizes are multiples of the MXU tile (128).  Fully-masked
KV blocks (block start beyond the causal frontier) are skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_q: int, block_k: int, causal: bool):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * block_q
    k_start = j * block_k
    run = jnp.logical_or(not causal, k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if causal:
            qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kj = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kj <= qi, s, NEG_INF)
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
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False):
    """q: (B, H, T, hd); k, v: (B, Hkv, S, hd) -> (B, H, T, hd)."""
    B, H, T, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = H // Hkv
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    scale = hd ** -0.5

    qf = q.reshape(B * H, T, hd)
    kf = k.reshape(B * Hkv, S, hd)
    vf = v.reshape(B * Hkv, S, hd)
    grid = (B * H, T // block_q, S // block_k)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // G, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, T, hd)
