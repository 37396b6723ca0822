"""RG-LRU diagonal linear recurrence h_t = a_t*h_{t-1} + b_t, Pallas TPU.

Grid (B, D/bd, T/C): channel blocks are parallel programs, the time axis is
sequential with the carry h (1, bd) in VMEM scratch.  Inside a chunk the
recurrence runs as a fori_loop over rows — elementwise VPU work streaming
(C, bd) tiles once from HBM (this layer is bandwidth-bound by design).

  vmem = 2*C*bd (a, b) + C*bd (h out) + bd f32 (carry)
C=256, bd=512: ~1.6 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h_ref, carry_ref, *, chunk: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    def step(i, carry):                         # carry: (1, bd)
        row = pl.ds(i, 1)                       # row i of the (C, bd) tile
        a = a_ref[0, row, :].astype(jnp.float32)
        b = b_ref[0, row, :].astype(jnp.float32)
        h = a * carry + b
        h_ref[0, row, :] = h.astype(h_ref.dtype)
        return h

    carry_ref[...] = jax.lax.fori_loop(0, chunk, step, carry_ref[...])


def rglru_scan(a, b, *, chunk: int = 256, block_d: int = 512,
               interpret: bool = False):
    """a, b: (B, T, D) -> h: (B, T, D) with h_t = a_t h_{t-1} + b_t."""
    B, T, D = a.shape
    chunk = min(chunk, T)
    bd = min(block_d, D)
    grid = (B, D // bd, T // chunk)

    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, chunk, bd), lambda bi, di, ti: (bi, ti, di)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bd), lambda bi, di, ti: (bi, ti, di)),
        out_shape=jax.ShapeDtypeStruct((B, T, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
