"""Jit'd public wrappers for every kernel.

The Pallas kernels compile natively for the TPU.  ``interpret=True``
executes the same blocked dataflow in Python on any backend; only a caller
that asks for it gets it (the per-kernel tests sweep shapes/dtypes against
the ``ref`` oracles that way).  Without it, a non-TPU backend raises.
"""

from __future__ import annotations

import functools

import jax

from .decode_attention import decode_attention as _decode
from .flash_attention import flash_attention as _flash
from .moe_gmm import moe_gmm as _gmm
from .rglru_scan import rglru_scan as _rglru
from .rwkv_scan import rwkv_scan as _rwkv


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False):
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, lengths, block_k: int = 512,
                     interpret: bool = False):
    return _decode(q, k, v, lengths, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_f", "block_d",
                                    "interpret"))
def moe_gmm(x, w, block_c: int = 256, block_f: int = 256, block_d: int = 512,
            interpret: bool = False):
    return _gmm(x, w, block_c=block_c, block_f=block_f, block_d=block_d,
                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_scan(r, k, v, logw, u, chunk: int = 128, interpret: bool = False):
    return _rwkv(r, k, v, logw, u, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def rglru_scan(a, b, chunk: int = 256, block_d: int = 512,
               interpret: bool = False):
    return _rglru(a, b, chunk=chunk, block_d=block_d, interpret=interpret)
