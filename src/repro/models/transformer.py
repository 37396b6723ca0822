"""Decoder assembly for every family: scan-over-layers (one compiled
layer body, whatever the depth), per-layer remat (each layer recomputed,
or its projection outputs kept: ``SAVE_PROJECTIONS``, ``saved_bytes``),
KV / ring / recurrent-state caches.

Layer recipes
  dense/vlm/audio : x += attn(norm(x));  x += mlp(norm(x))
  moe             : x += attn(norm(x));  x += moe(norm(x)) [+ dense residual]
  rwkv            : x += time_mix(norm(x));  x += channel_mix(norm(x))
  rglru           : blocks of `attn_every` layers — (attn_every-1) recurrent
                    + 1 local-attention — scanned; remainder unrolled.

Caches
  attention (global) : k/v (B, S, Hkv, hd) + scalar length
  attention (window) : ring buffer (B, W, ...) + slot positions
  rwkv               : S (B, H, M, M) + token-shift states
  rglru              : h (B, dl) + conv state
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from .config import ModelConfig
from . import layers as L
from . import moe as MOE
from . import rwkv as RWKV
from . import rglru as RGLRU
from repro.dist.sharding import activation_spec, shard_act


# ---------------------------------------------------------------------------
#  parameter init
# ---------------------------------------------------------------------------
def _layer_params(cfg: ModelConfig, key, i: int, dtype):
    p = {"norm1": jnp.zeros((cfg.d_model,), dtype),
         "norm2": jnp.zeros((cfg.d_model,), dtype)}
    k1, k2 = jax.random.split(key)
    if cfg.family == "rwkv":
        p.update(RWKV.rwkv_params(cfg, k1, dtype))
        return p
    if cfg._is_attn_layer(i):
        p["attn"] = L.attn_params(cfg, k1, dtype)
    else:
        p["rec"] = RGLRU.rglru_params(cfg, k1, dtype)
    if cfg.n_experts:
        p["moe"] = MOE.moe_params(cfg, k2, dtype)
        if cfg.dense_residual:
            p["mlp"] = L.mlp_params(cfg, jax.random.fold_in(k2, 1), dtype)
    else:
        p["mlp"] = L.mlp_params(cfg, k2, dtype)
    return p


def _layer_plan(cfg: ModelConfig):
    """(n_scanned, tail_indices): homogeneous stacks scan everything; hybrids
    scan whole blocks and unroll the remainder."""
    if cfg.attn_every:
        n_blocks = cfg.n_layers // cfg.attn_every
        n_scanned = n_blocks * cfg.attn_every
        return n_scanned, list(range(n_scanned, cfg.n_layers))
    return cfg.n_layers, []


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    ke, kh, kl = jax.random.split(key, 3)
    params: dict[str, Any] = {
        "embed": jax.random.normal(ke, (cfg.vocab, cfg.d_model), dtype) * 0.02,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            kh, (cfg.d_model, cfg.vocab), dtype) * cfg.d_model ** -0.5

    n_scanned, tail = _layer_plan(cfg)
    if cfg.scan_layers and n_scanned > 0:
        period = cfg.attn_every or 1
        n_steps = n_scanned // period

        def one_step(k):
            ks = jax.random.split(k, period)
            if period == 1:
                return _layer_params(cfg, ks[0], 0, dtype)
            return [_layer_params(cfg, ks[j], j, dtype) for j in range(period)]

        keys = jax.random.split(jax.random.fold_in(kl, 0), n_steps)
        params["layers"] = jax.vmap(one_step)(keys)       # leaves: (n_steps, ...)
    else:
        params["layers"] = [
            _layer_params(cfg, jax.random.fold_in(kl, i), i, dtype)
            for i in range(n_scanned)]
    params["tail"] = [
        _layer_params(cfg, jax.random.fold_in(kl, 1000 + i), i, dtype)
        for i in tail]
    return params


# ---------------------------------------------------------------------------
#  caches
# ---------------------------------------------------------------------------
def _attn_cache(cfg: ModelConfig, B: int, max_len: int):
    S = min(max_len, cfg.window) if cfg.window else max_len
    S = -(-S // cfg.attn_chunk) * cfg.attn_chunk
    hk = (B, S, cfg.n_kv_heads, cfg.hd)
    c = {"k": jnp.zeros(hk, jnp.dtype(cfg.dtype)),
         "v": jnp.zeros(hk, jnp.dtype(cfg.dtype))}
    if cfg.window:
        # unfilled ring slots must fail the window mask: far-past sentinel
        c["slot_pos"] = jnp.full((S,), -(1 << 30), jnp.int32)
    return c


def _layer_cache(cfg: ModelConfig, i: int, B: int, max_len: int):
    if cfg.family == "rwkv":
        M = cfg.rwkv_head_dim
        H = cfg.d_model // M
        return {"S": jnp.zeros((B, H, M, M), jnp.float32),
                "last": jnp.zeros((B, cfg.d_model), jnp.dtype(cfg.dtype)),
                "last_c": jnp.zeros((B, cfg.d_model), jnp.dtype(cfg.dtype))}
    if cfg._is_attn_layer(i):
        return _attn_cache(cfg, B, max_len)
    return {"h": jnp.zeros((B, cfg.lru_d), jnp.float32),
            "conv": jnp.zeros((B, RGLRU.CONV_W - 1, cfg.lru_d),
                              jnp.dtype(cfg.dtype))}


def init_cache(cfg: ModelConfig, batch: int, max_len: int | None = None):
    max_len = max_len or cfg.max_target_len
    n_scanned, tail = _layer_plan(cfg)
    period = cfg.attn_every or 1
    n_steps = n_scanned // period

    def one_step(_):
        if period == 1:
            return _layer_cache(cfg, 0, batch, max_len)
        return [_layer_cache(cfg, j, batch, max_len) for j in range(period)]

    if not cfg.scan_layers:
        return {
            "layers": [_layer_cache(cfg, i % period, batch, max_len)
                       for i in range(n_scanned)],
            "tail": [_layer_cache(cfg, i, batch, max_len) for i in tail],
            "length": jnp.zeros((), jnp.int32),
        }

    cache = {
        "layers": jax.vmap(one_step)(jnp.arange(n_steps)),
        "tail": [_layer_cache(cfg, i, batch, max_len) for i in tail],
        "length": jnp.zeros((), jnp.int32),
    }
    return cache


# ---------------------------------------------------------------------------
#  blocks
# ---------------------------------------------------------------------------
def _attn_with_ring(cfg, p, x, positions, cache, length):
    """Windowed ring-buffer attention for decode (cache is (B,W,...))."""
    q = jnp.einsum("btd,dnh->btnh", x, p["wq"])
    k = jnp.einsum("btd,dnh->btnh", x, p["wk"])
    v = jnp.einsum("btd,dnh->btnh", x, p["wv"])
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    W = cache["k"].shape[1]
    slot = length % W
    kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    sp = jax.lax.dynamic_update_slice(cache["slot_pos"], positions, (slot,))
    out = L.attention(q, kc, vc, positions, sp, window=cfg.window,
                      chunk=cfg.attn_chunk)
    y = jnp.einsum("btnh,nhd->btd", out, p["wo"])
    return y, {"k": kc, "v": vc, "slot_pos": sp}


def _block(cfg: ModelConfig, p, x, positions, cache, length, layer_idx,
           mesh=None):
    """One layer.  cache=None during training."""
    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if cfg.family == "rwkv":
        y, st = RWKV.time_mix(cfg, p, h, cache)
        x = x + y
        h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        y2, st2 = RWKV.channel_mix(cfg, p, h2, cache)
        new_cache = {**st, **st2} if cache is not None else None
        return x + y2, new_cache, 0.0

    if "attn" in p:
        with jax.named_scope("attention"):
            if cache is not None and cfg.window:
                y, new_c = _attn_with_ring(cfg, p["attn"], h, positions,
                                           cache, length)
            else:
                c = None if cache is None else {**cache, "length": length}
                y, new_c = L.attn_block(cfg, p["attn"], h, positions,
                                        cache=c, window=cfg.window)
                if new_c is not None:
                    new_c = {"k": new_c["k"], "v": new_c["v"]}
    else:
        y, new_c = RGLRU.rglru_block(cfg, p["rec"], h,
                                     cache if cache is not None else None)
    x = x + y
    x = shard_act(x, mesh)

    h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
    aux = 0.0
    with jax.named_scope("mlp"):
        if cfg.n_experts:
            if mesh is not None:
                y2, aux = MOE.moe_shardmap(cfg, mesh, p["moe"], h2)
            else:
                y2, aux = MOE.moe_block(cfg, p["moe"], h2)
            if cfg.dense_residual:
                y2 = y2 + L.mlp_block(cfg, p["mlp"], h2)
        else:
            y2 = L.mlp_block(cfg, p["mlp"], h2)
    x = x + y2
    x = shard_act(x, mesh)
    return x, (new_c if cache is not None else None), aux


# ---------------------------------------------------------------------------
#  remat policy
# ---------------------------------------------------------------------------
SAVE_PROJECTIONS = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
RECOMPUTE = jax.checkpoint_policies.nothing_saveable


def saved_bytes(cfg: ModelConfig, x_shape, mesh=None) -> int:
    """The bytes a chip keeps for the backward when the layer scan over a
    residual stream of ``x_shape`` (B, T, ...) runs under
    ``SAVE_PROJECTIONS``, or 0 where that policy is not offered: remat
    off, or a block this count does not describe (recurrent, expert).

    Saving keeps the projection outputs of every layer that the backward
    reads (q, k, v, o, gate, up: the dots with no batch dims; the down
    projection's output no backward rule needs), so the backward scan no
    longer runs each layer's forward again; the attention scores, which
    have batch dims, are still recomputed.  They are counted as the
    layers × the (B, T, width) of each in the residual stream's layout
    (``activation_spec``: batch over the data axes, sequence over
    ``model``), which is how the compiled 2x2 program keeps them.  Whether
    they fit a chip is the train step's to decide
    (``train_step.compile_fitting``)."""
    n_layers = _layer_plan(cfg)[0]
    if not cfg.remat or not n_layers or cfg.family == "rwkv" \
            or cfg.attn_every or cfg.n_experts:
        return 0
    B, T = x_shape[:2]
    widths = (cfg.n_heads * cfg.hd, cfg.n_kv_heads * cfg.hd,
              cfg.n_kv_heads * cfg.hd, cfg.d_model, cfg.d_ff, cfg.d_ff)
    per_layer = 0
    for w in widths:
        shape = (B, T, w)
        if mesh is not None:
            shape = NamedSharding(mesh, activation_spec(mesh, shape)
                                  ).shard_shape(shape)
        per_layer += math.prod(shape)
    return n_layers * per_layer * jnp.dtype(cfg.dtype).itemsize


# ---------------------------------------------------------------------------
#  forward / decode
# ---------------------------------------------------------------------------
def forward(cfg: ModelConfig, params, batch, mesh=None):
    """Training/prefill forward.  batch: tokens (B,T) [+ prefix_embeds
    (B,P,D) for VLM/audio stubs].  Returns (logits, aux_loss)."""
    x, aux_total = _forward_body(cfg, params, batch, mesh=mesh)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,dv->btv", x, head)
    return logits, aux_total


def forward_hidden(cfg: ModelConfig, params, batch, mesh=None,
                   remat_policy=RECOMPUTE):
    """Forward up to the final norm (no logits) — used by the chunked-CE
    loss so the (B,T,V) f32 logits never materialize.  ``remat_policy``
    is the layer scan's checkpoint policy under ``cfg.remat``."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return _forward_body(cfg, params, batch, mesh=mesh,
                         remat_policy=remat_policy), head


def _forward_body(cfg: ModelConfig, params, batch, mesh=None,
                  remat_policy=RECOMPUTE):
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.prefix_len and "prefix_embeds" in batch:
        x = jnp.concatenate(
            [batch["prefix_embeds"].astype(x.dtype), x], axis=1)
    T = x.shape[1]
    positions = jnp.arange(T, dtype=jnp.int32)
    x = shard_act(x, mesh)
    period = cfg.attn_every or 1
    aux_total = 0.0

    def block_fn(x, p_step):
        aux = 0.0
        if period == 1:
            x, _, aux = _block(cfg, p_step, x, positions, None, None, 0,
                               mesh=mesh)
        else:
            for j in range(period):
                x, _, a = _block(cfg, p_step[j], x, positions, None, None, j,
                                 mesh=mesh)
                aux = aux + a
        return x, aux

    if cfg.remat:
        block_fn = jax.checkpoint(block_fn, policy=remat_policy)
    if cfg.scan_layers:
        x, auxs = jax.lax.scan(lambda c, p: block_fn(c, p), x,
                               params["layers"])
        aux_total = aux_total + jnp.sum(auxs)
    else:
        # unrolled: layers is a flat per-layer list (heterogeneous for
        # hybrids), not period-grouped — apply _block directly
        def one(x, p_layer):
            x, _, aux = _block(cfg, p_layer, x, positions, None, None, 0,
                               mesh=mesh)
            return x, aux
        if cfg.remat:
            one = jax.checkpoint(one, policy=remat_policy)
        for p_layer in params["layers"]:
            x, aux = one(x, p_layer)
            aux_total = aux_total + aux
    for i, p_layer in enumerate(params["tail"]):
        x, _, aux = _block(cfg, p_layer, x, positions, None, None, i,
                           mesh=mesh)
        aux_total = aux_total + aux
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total


def decode_step(cfg: ModelConfig, params, cache, tokens, mesh=None):
    """One decode step.  tokens: (B,1).  Returns (logits (B,1,V), cache)."""
    length = cache["length"]
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.full((tokens.shape[1],), length, jnp.int32) \
        + jnp.arange(tokens.shape[1], dtype=jnp.int32)

    period = cfg.attn_every or 1

    def scan_step(x, pc):
        p_step, c_step = pc
        new_cs = []
        if period == 1:
            x, nc, _ = _block(cfg, p_step, x, positions, c_step, length, 0,
                              mesh=mesh)
            return x, nc
        for j in range(period):
            x, nc, _ = _block(cfg, p_step[j], x, positions, c_step[j],
                              length, j, mesh=mesh)
            new_cs.append(nc)
        return x, new_cs

    if cfg.scan_layers:
        x, new_layer_cache = jax.lax.scan(
            scan_step, x, (params["layers"], cache["layers"]))
    else:
        new_layer_cache = []
        for i, (p_layer, c_layer) in enumerate(
                zip(params["layers"], cache["layers"])):
            x, nc, _ = _block(cfg, p_layer, x, positions, c_layer, length,
                              i, mesh=mesh)
            new_layer_cache.append(nc)

    new_tail = []
    for p_layer, c_layer in zip(params["tail"], cache["tail"]):
        x, nc, _ = _block(cfg, p_layer, x, positions, c_layer, length, 0,
                          mesh=mesh)
        new_tail.append(nc)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,dv->btv", x, head)
    new_cache = {"layers": new_layer_cache, "tail": new_tail,
                 "length": length + tokens.shape[1]}
    return logits, new_cache
