"""Sharded-sequence decode attention (§Perf optimization).

Baseline decode lets XLA's partitioner handle attention over the
sequence-sharded KV cache; it gives up and all-gathers the cache
(~GB/token of ICI traffic).  This shard_map keeps every cache shard
local: each model rank computes a *partial* online-softmax over its
S/16 slice and the ranks combine (pmax + two psums of (B,H)-sized
stats) — bytes on the wire drop from the cache size to ~B*H*hd.

The cache append also stays local: exactly one rank owns the slot at
`length`; everyone else's update is masked out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from . import layers as L
from .config import ModelConfig


def attn_decode_sharded(cfg: ModelConfig, mesh, p, x, positions, cache,
                        length):
    """x: (B,1,D); cache k/v: (B,S,Hkv,hd) sharded (dp, model, -, -).
    Returns (y (B,1,D), new {k,v})."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_model = mesh.shape["model"]
    B, _, D = x.shape
    S = cache["k"].shape[1]
    if S % n_model or B % max(1, _size(mesh, dp)):
        # fall back to the XLA path when the cache/batch don't divide
        c = {**cache, "length": length}
        y, nc = L.attn_block(cfg, p, x, positions, cache=c,
                             window=cfg.window)
        return y, {"k": nc["k"], "v": nc["v"]}
    S_loc = S // n_model
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // Hkv

    q = jnp.einsum("btd,dnh->btnh", x, p["wq"])
    k = jnp.einsum("btd,dnh->btnh", x, p["wk"])
    v = jnp.einsum("btd,dnh->btnh", x, p["wv"])
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)

    def body(q, k_new, v_new, kc, vc, length):
        b = q.shape[0]                    # local batch (B / dp)
        r = jax.lax.axis_index("model")
        base = r * S_loc
        idx = jnp.clip(length - base, 0, S_loc - 1)
        in_range = jnp.logical_and(length >= base, length < base + S_loc)
        # masked write touching only the slot (no full-cache copy): ranks
        # that don't own the slot re-write the existing value
        k_old = jax.lax.dynamic_slice(kc, (0, idx, 0, 0), k_new.shape)
        v_old = jax.lax.dynamic_slice(vc, (0, idx, 0, 0), v_new.shape)
        kc2 = jax.lax.dynamic_update_slice(
            kc, jnp.where(in_range, k_new, k_old), (0, idx, 0, 0))
        vc2 = jax.lax.dynamic_update_slice(
            vc, jnp.where(in_range, v_new, v_old), (0, idx, 0, 0))

        qg = q.reshape(b, 1, Hkv, G, hd).astype(jnp.float32) * hd ** -0.5
        s = jnp.einsum("bokgh,bskh->bkgs", qg,
                       kc2.astype(jnp.float32))           # (b,Hkv,G,S_loc)
        k_pos = base + jnp.arange(S_loc)
        s = jnp.where((k_pos <= length)[None, None, None, :], s, -1e30)
        m_loc = s.max(axis=-1)
        pexp = jnp.exp(s - m_loc[..., None])
        l_loc = pexp.sum(axis=-1)
        acc_loc = jnp.einsum("bkgs,bskh->bkgh", pexp,
                             vc2.astype(jnp.float32))
        m = jax.lax.pmax(m_loc, "model")
        corr = jnp.exp(m_loc - m)
        l = jax.lax.psum(l_loc * corr, "model")
        acc = jax.lax.psum(acc_loc * corr[..., None], "model")
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(b, 1, H, hd)
        return out.astype(q.dtype), kc2, vc2

    rep4 = P(dp, None, None, None)
    shard4 = P(dp, "model", None, None)
    out, kc, vc = shard_map(
        body, mesh=mesh,
        in_specs=(rep4, rep4, rep4, shard4, shard4, P()),
        out_specs=(rep4, shard4, shard4),
        check_vma=False,
    )(q, k, v, cache["k"], cache["v"], length)
    y = jnp.einsum("btnh,nhd->btd", out, p["wo"])
    return y, {"k": kc, "v": vc}


def _size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
