"""Per-expert grouped matmul (MoE expert compute), Pallas TPU.

Operates on the capacity-buffer layout the router produces:
x (E, C, D) @ w (E, D, F) -> y (E, C, F).  Grid (E, C/bc, F/bf, D/bd) with
the contraction axis innermost; f32 accumulation in VMEM scratch.

  vmem = bc*bd (x) + bd*bf (w) + bc*bf f32 (acc)

bc=bf=256, bd=512: ~0.9 MB.  All tile dims are 128-multiples (MXU-aligned).
This is the hot 65% of MoE train-step FLOPs (see EXPERIMENTS §Roofline).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, y_ref, acc_ref):
    d = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(d == nd - 1)
    def _finish():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)


def moe_gmm(x, w, *, block_c: int = 256, block_f: int = 256,
            block_d: int = 512, interpret: bool = False):
    """x: (E, C, D); w: (E, D, F) -> (E, C, F)."""
    E, C, D = x.shape
    F = w.shape[2]
    bc, bf, bd = min(block_c, C), min(block_f, F), min(block_d, D)
    grid = (E, C // bc, F // bf, D // bd)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, d: (e, i, d)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, d: (e, d, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, d: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
