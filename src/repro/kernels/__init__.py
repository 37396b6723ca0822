"""Pallas TPU kernels for the compute hot spots.

Each kernel has: ``<name>.py`` (pl.pallas_call + explicit BlockSpec VMEM
tiling), a jit'd wrapper in ``ops.py``, and a pure-jnp oracle in ``ref.py``.
Off the TPU a wrapper runs only when its caller passes ``interpret=True``
(correctness only); the blocked dataflow is identical to what the MXU
executes.

Kernels:
  flash_attention  — causal GQA attention, online softmax over KV blocks
  decode_attention — one-token query vs a long KV cache (serve hot loop)
  moe_gmm          — per-expert grouped matmul over capacity buffers
  rwkv_scan        — chunked WKV6 recurrence (data-dependent decay)
  rglru_scan       — RG-LRU diagonal linear recurrence
"""

from . import ops, ref

__all__ = ["ops", "ref"]
