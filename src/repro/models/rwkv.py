"""RWKV6 "Finch" (arXiv:2404.05892): attention-free time-mix with
data-dependent decay, plus squared-ReLU channel-mix.

The WKV6 recurrence per head (state S: hd×hd):

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)

is evaluated in *chunks* (matmul-dense, MXU-friendly — the same dataflow the
Pallas kernel uses): within a chunk all pairwise decay products are expressed
relative to the chunk start so every exponent is ≤ 0 (no overflow).  The
data-dependent decay is LoRA-produced as in Finch; its per-step magnitude is
bounded (|log w| ≤ 0.105) so that cross-chunk ratios stay in f32 range — a
kernel-stability re-parameterization, noted in DESIGN.md.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import rms_norm

CHUNK = 64
LORA_R = 32
DECAY_SCALE = 0.105


def rwkv_params(cfg: ModelConfig, key, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    M = cfg.rwkv_head_dim
    H = d // M
    ks = jax.random.split(key, 12)
    s = d ** -0.5
    return {
        # time-mix
        "mu": jax.random.normal(ks[0], (5, d), dtype) * 0.02,   # r,k,v,w,g shifts
        "wr": jax.random.normal(ks[1], (d, d), dtype) * s,
        "wk": jax.random.normal(ks[2], (d, d), dtype) * s,
        "wv": jax.random.normal(ks[3], (d, d), dtype) * s,
        "wg": jax.random.normal(ks[4], (d, d), dtype) * s,
        "wo": jax.random.normal(ks[5], (d, d), dtype) * s,
        "w0": jax.random.normal(ks[6], (d,), jnp.float32) * 0.5,
        "w_lora_a": jax.random.normal(ks[7], (d, LORA_R), dtype) * s,
        "w_lora_b": jax.random.normal(ks[8], (LORA_R, d), dtype) * LORA_R ** -0.5,
        "u": jax.random.normal(ks[9], (H, M), jnp.float32) * 0.1,
        "ln_x": jnp.zeros((d,), dtype),
        # channel-mix
        "mu_c": jax.random.normal(ks[10], (2, d), dtype) * 0.02,
        "ck": jax.random.normal(ks[11], (d, ff), dtype) * s,
        "cv": jax.random.normal(jax.random.fold_in(key, 99), (ff, d), dtype)
              * ff ** -0.5,
        "cr": jax.random.normal(jax.random.fold_in(key, 98), (d, d), dtype) * s,
    }


def _token_shift(x, last):
    """shift(x)_t = x_{t-1}; position 0 takes `last` (B, D) from the cache."""
    prev = jnp.concatenate([last[:, None, :], x[:, :-1, :]], axis=1)
    return prev


def _wkv_chunk(r, k, v, logw, u, S0):
    """One chunk of the WKV6 recurrence, matmul form.

    r,k,v: (B,H,C,M)  logw: (B,H,C,M) (≤0)  u: (H,M)  S0: (B,H,M,M)
    Returns (o: (B,H,C,M), S_next).
    """
    cs = jnp.cumsum(logw, axis=2)                       # logA_t, inclusive
    A = jnp.exp(cs)                                     # ≤ 1
    A_prev = jnp.exp(cs - logw)                         # logA_{t-1}
    A_tail = jnp.exp(cs[:, :, -1:, :] - cs)             # Π_{s>t} w_s ≤ 1

    q_in = r * A_prev                                   # decay from chunk start
    k_in = k * jnp.exp(-cs + cs[:, :, :1, :] - logw[:, :, :1, :])
    # k_in decays *backwards*: exponent = -(logA_s - logA_0) ≥ 0 but bounded
    # by C*DECAY_SCALE ≈ 6.7 → e^6.7 ≈ 800, f32-safe.

    C = r.shape[2]
    scores = jnp.einsum("bhtm,bhsm->bhts", q_in, k_in)
    mask = jnp.tril(jnp.ones((C, C), bool), k=-1)
    scores = jnp.where(mask[None, None], scores, 0.0)
    diag = jnp.einsum("bhtm,hm,bhtm->bht", r, u, k)     # bonus term (s == t)
    o = jnp.einsum("bhts,bhsm->bhtm", scores, v) + diag[..., None] * v
    o = o + jnp.einsum("bhtm,bhmn->bhtn", q_in, S0)     # cross-chunk history

    k_tail = k * A_tail
    S_next = jnp.exp(cs[:, :, -1, :])[..., None] * S0 \
        + jnp.einsum("bhtm,bhtn->bhmn", k_tail, v)
    return o, S_next


def time_mix(cfg: ModelConfig, p, x, state):
    """x: (B,T,D). state: {"S": (B,H,M,M), "last": (B,D)} or None (training
    uses zeros).  Returns (out, new_state)."""
    B, T, D = x.shape
    M = cfg.rwkv_head_dim
    H = D // M
    if state is None:
        S = jnp.zeros((B, H, M, M), jnp.float32)
        last = jnp.zeros((B, D), x.dtype)
    else:
        S, last = state["S"], state["last"]

    prev = _token_shift(x, last)
    mix = x[None] + p["mu"][:, None, None, :] * (prev - x)[None]  # (5,B,T,D)
    xr, xk, xv, xw, xg = mix
    r = (xr @ p["wr"]).reshape(B, T, H, M).transpose(0, 2, 1, 3)
    k = (xk @ p["wk"]).reshape(B, T, H, M).transpose(0, 2, 1, 3)
    v = (xv @ p["wv"]).reshape(B, T, H, M).transpose(0, 2, 1, 3)
    g = jax.nn.silu(xg @ p["wg"])
    # Finch data-dependent decay, bounded for chunked stability
    lora = jnp.tanh(xw @ p["w_lora_a"]) @ p["w_lora_b"]
    logw = -DECAY_SCALE * jax.nn.sigmoid(
        p["w0"][None, None, :] + lora.astype(jnp.float32))       # (B,T,D) ≤ 0
    logw = logw.reshape(B, T, H, M).transpose(0, 2, 1, 3)

    if T == 1:                          # decode fast path: plain recurrence
        r1 = r[:, :, 0].astype(jnp.float32)
        k1 = k[:, :, 0].astype(jnp.float32)
        v1 = v[:, :, 0].astype(jnp.float32)
        w1 = jnp.exp(logw[:, :, 0])
        kv = jnp.einsum("bhm,bhn->bhmn", k1, v1)
        o = jnp.einsum("bhm,bhmn->bhn", r1, S + p["u"][None, :, :, None] * kv)
        S = w1[..., None] * S + kv
        o = o.reshape(B, 1, D).astype(x.dtype)
        o = rms_norm(o, p["ln_x"], cfg.norm_eps) * g
        return o @ p["wo"], {"S": S, "last": x[:, -1, :]}

    Tpad = -(-T // CHUNK) * CHUNK
    if Tpad != T:
        pad = [(0, 0), (0, 0), (0, Tpad - T), (0, 0)]
        r, k, v = (jnp.pad(a, pad) for a in (r, k, v))
        logw = jnp.pad(logw, pad)
    nc = Tpad // CHUNK

    def step(S, xs):
        rc, kc, vc, wc = xs
        o, S2 = _wkv_chunk(rc.astype(jnp.float32), kc.astype(jnp.float32),
                           vc.astype(jnp.float32), wc, p["u"], S)
        return S2, o

    split = lambda a: a.reshape(B, H, nc, CHUNK, M).transpose(2, 0, 1, 3, 4)
    S, o = jax.lax.scan(step, S, (split(r), split(k), split(v), split(logw)))
    o = o.transpose(1, 2, 0, 3, 4).reshape(B, H, Tpad, M)[:, :, :T]
    o = o.transpose(0, 2, 1, 3).reshape(B, T, D).astype(x.dtype)
    o = rms_norm(o, p["ln_x"], cfg.norm_eps) * g
    out = o @ p["wo"]
    new_state = {"S": S, "last": x[:, -1, :]}
    return out, new_state


def channel_mix(cfg: ModelConfig, p, x, state):
    """Squared-ReLU channel mix with token shift."""
    last = state["last_c"] if state is not None else jnp.zeros(
        (x.shape[0], x.shape[2]), x.dtype)
    prev = _token_shift(x, last)
    mix = x[None] + p["mu_c"][:, None, None, :] * (prev - x)[None]
    xk, xr = mix
    kk = jnp.square(jax.nn.relu(xk @ p["ck"]))
    out = jax.nn.sigmoid(xr @ p["cr"]) * (kk @ p["cv"])
    return out, {"last_c": x[:, -1, :]}
