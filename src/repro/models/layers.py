"""Shared pure-JAX layers: RMSNorm, RoPE, GQA attention (chunked flash-style
for long sequences), gated MLP.

Sharding notes (the ``dist.sharding`` rules assume these layouts):
  * attention projections:  wq (D, H, hd)   wk/wv (D, Hkv, hd)   wo (H, hd, D)
  * MLP:                    w_gate/w_up (D, F)   w_down (F, D)
  * activations between blocks carry P(data, model, None) — batch sharded
    over `data`, sequence over `model` (Megatron-style sequence parallelism);
    XLA inserts the all-gather / reduce-scatter pairs at the block boundary.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .config import ModelConfig


def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope(x, positions, theta: float = 1e6):
    """x: (..., T, n, hd); positions: (..., T)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (jnp.log(theta) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs     # (..., T, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _mask_bias(q_pos, k_pos, window: int):
    """Causal (+ optional sliding-window) additive bias."""
    causal = k_pos[None, :] <= q_pos[:, None]
    if window:
        causal &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(causal, 0.0, -1e30).astype(jnp.float32)


def attention(q, k, v, q_pos, k_pos, *, window: int = 0, chunk: int = 1024):
    """GQA attention.  q: (B,T,H,hd)  k,v: (B,S,Hkv,hd).

    Short sequences use one einsum; long sequences use an online-softmax scan
    over KV chunks (flash-style) so the score matrix never materializes.
    """
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = hd ** -0.5
    qg = q.reshape(B, T, Hkv, G, hd) * scale

    if S <= max(2 * chunk, 2048):
        scores = jnp.einsum("btkgh,bskh->bktgs", qg, k).astype(jnp.float32)
        bias = _mask_bias(q_pos, k_pos, window)                  # (T, S)
        scores = scores + bias[None, None, :, None, :]
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bktgs,bskh->btkgh", probs, v)
        return out.reshape(B, T, H, hd)

    # flash-style: scan over KV chunks with running (max, sum, acc)
    if S % chunk:                         # pad to a chunk multiple (masked)
        pad = chunk - S % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.concatenate(
            [k_pos, jnp.full((pad,), 1 << 30, k_pos.dtype)])   # future: masked
        S += pad
    n_chunks = S // chunk
    k_c = k.reshape(B, n_chunks, chunk, Hkv, hd)
    v_c = v.reshape(B, n_chunks, chunk, Hkv, hd)
    kpos_c = k_pos.reshape(n_chunks, chunk)

    def step(carry, xs):
        m, l, acc = carry
        kc, vc, kp = xs
        s = jnp.einsum("btkgh,bskh->bktgs", qg, kc).astype(jnp.float32)
        s = s + _mask_bias(q_pos, kp, window)[None, None, :, None, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bktgs,bskh->bktgh", p.astype(vc.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, T, G), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, T, G), jnp.float32)
    a0 = jnp.zeros((B, Hkv, T, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (k_c.swapaxes(0, 1), v_c.swapaxes(0, 1), kpos_c))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.swapaxes(1, 2).reshape(B, T, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
#  Attention block (projections + rope + qk-norm + cache handling)
# ---------------------------------------------------------------------------
def attn_params(cfg: ModelConfig, key, dtype):
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (d, H, hd), dtype) * s),
        "wk": (jax.random.normal(k2, (d, Hkv, hd), dtype) * s),
        "wv": (jax.random.normal(k3, (d, Hkv, hd), dtype) * s),
        "wo": (jax.random.normal(k4, (H, hd, d), dtype) * (H * hd) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def attn_block(cfg: ModelConfig, p, x, positions, *, cache=None,
               window: int = 0):
    """x: (B,T,D); positions: (T,) int32, shared across the batch.
    cache: dict(k/v: (B,S,Hkv,hd), length) for decode."""
    q = jnp.einsum("btd,dnh->btnh", x, p["wq"])
    k = jnp.einsum("btd,dnh->btnh", x, p["wk"])
    v = jnp.einsum("btd,dnh->btnh", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = attention(q, k, v, positions, positions, window=window,
                        chunk=cfg.attn_chunk)
    else:
        # decode: append the new token's k/v at `length`, attend to the cache
        length = cache["length"]
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, length, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, length, 0, 0))
        cache = {"k": kc, "v": vc, "length": length + q.shape[1]}
        k_pos = jnp.arange(kc.shape[1])
        # entries beyond `length` are masked by the causal bias (q_pos=length)
        out = attention(q, kc, vc, positions, k_pos, window=window,
                        chunk=cfg.attn_chunk)
    y = jnp.einsum("btnh,nhd->btd", out, p["wo"])
    return y, cache


# ---------------------------------------------------------------------------
#  Gated MLP
# ---------------------------------------------------------------------------
def mlp_params(cfg: ModelConfig, key, dtype, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": jax.random.normal(k1, (d, f), dtype) * d ** -0.5,
        "w_up": jax.random.normal(k2, (d, f), dtype) * d ** -0.5,
        "w_down": jax.random.normal(k3, (f, d), dtype) * f ** -0.5,
    }


def mlp_block(cfg: ModelConfig, p, x):
    g = jnp.einsum("btd,df->btf", x, p["w_gate"])
    u = jnp.einsum("btd,df->btf", x, p["w_up"])
    if cfg.act == "geglu":
        h = jax.nn.gelu(g) * u
    else:
        h = jax.nn.silu(g) * u
    return jnp.einsum("btf,fd->btd", h, p["w_down"])
