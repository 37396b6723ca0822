"""Plain float32 Qwen3 (Qwen3ForCausalLM): weights from a seed, forward,
loss, gradients and AdamW, in straightforward ``jax.numpy``.

Written from the published description, independent of the code under
test (nothing here imports it).  The benchmark makes the weights with
``init_weights`` and hands the same tree to the program, so this module
also fixes the tree's layout: ``embed`` (V, D); ``final_norm`` (D,);
``layers`` with a leading layer axis: ``norm1``, ``norm2`` (L, D),
``attn`` {``wq`` (L, D, H, hd), ``wk``/``wv`` (L, D, Hkv, hd), ``wo``
(L, H, hd, D), ``q_norm``/``k_norm`` (L, hd)}, ``mlp`` {``w_gate``/``w_up``
(L, D, F), ``w_down`` (L, F, D)}; ``tail`` empty.  An RMSNorm gain is
stored as ``s`` and applied as ``1 + s``.

The layer, as published: x += o(attn(rope(qknorm(q(n1 x))), rope(qknorm(k(n1
x))), v(n1 x))); x += down(silu(gate(n2 x)) * up(n2 x)); causal attention
with grouped KV heads (query head h reads KV head h // (H / Hkv)); rotary
embedding on the two halves of each head; logits = norm(x) @ embed^T; loss
the mean token cross entropy.

``variant`` selects what is computed in the program's place:
  * ``f32``: the reference (every matmul at ``Precision.HIGHEST``);
  * ``fp8``: the control, every matmul operand rounded to float8_e4m3fn
    with a per-tensor scale, the nearest precision below bfloat16;
  * ``half``: a fault, the first half of each batch only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
VARIANTS = ("f32", "fp8", "half")


def program_fields(cfg: dict) -> dict:
    """The published configuration in the program's ``ModelConfig`` terms."""
    return dict(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"], act="silu",
        qk_norm=True, rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"])


def init_weights(cfg: dict, key, dtype=jnp.bfloat16):
    """Random weights from ``key``, each projection N(0, 1/fan_in)."""
    L, D, F = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["intermediate_size"])
    H, K, hd, V = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"], cfg["vocab_size"])
    ks = jax.random.split(key, 8)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    zeros = lambda *s: jnp.zeros(s, dtype)
    return {
        "embed": (jax.random.normal(ks[0], (V, D), jnp.float32)
                  * 0.02).astype(dtype),
        "final_norm": zeros(D),
        "layers": {
            "norm1": zeros(L, D), "norm2": zeros(L, D),
            "attn": {"wq": normal(ks[1], (L, D, H, hd), D),
                     "wk": normal(ks[2], (L, D, K, hd), D),
                     "wv": normal(ks[3], (L, D, K, hd), D),
                     "wo": normal(ks[4], (L, H, hd, D), H * hd),
                     "q_norm": zeros(L, hd), "k_norm": zeros(L, hd)},
            "mlp": {"w_gate": normal(ks[5], (L, D, F), D),
                    "w_up": normal(ks[6], (L, D, F), D),
                    "w_down": normal(ks[7], (L, F, D), F)},
        },
        "tail": [],
    }


def _fp8(x):
    """x rounded to float8_e4m3fn under a per-tensor scale; the gradient
    passes straight through the rounding."""
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / 448.0 + 1e-30)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(variant, spec, a, b):
    if variant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + s)


def _rope(x, theta):
    """x: (B, T, n, hd); rotate the two halves of each head."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.asarray(np.arange(T)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, variant, x, p):
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a, m = p["attn"], p["mlp"]
    h = _rms(x, p["norm1"], eps)
    q = _mm(variant, "btd,dnh->btnh", h, a["wq"])
    k = _mm(variant, "btd,dnh->btnh", h, a["wk"])
    v = _mm(variant, "btd,dnh->btnh", h, a["wv"])
    q = _rope(_rms(q, a["q_norm"], eps), theta)
    k = _rope(_rms(k, a["k_norm"], eps), theta)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    T = x.shape[1]
    s = _mm(variant, "bqnh,bknh->bnqk", q, k) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = _mm(variant, "bnqk,bknh->bqnh", jax.nn.softmax(s, -1), v)
    x = x + _mm(variant, "btnh,nhd->btd", o, a["wo"])
    h = _rms(x, p["norm2"], eps)
    g = jax.nn.silu(_mm(variant, "btd,df->btf", h, m["w_gate"])) \
        * _mm(variant, "btd,df->btf", h, m["w_up"])
    return x + _mm(variant, "btf,fd->btd", g, m["w_down"])


def loss(cfg: dict, params, tokens, labels, variant: str = "f32"):
    """Mean next-token cross entropy, float32 throughout."""
    if variant == "half":
        tokens, labels = tokens[:tokens.shape[0] // 2], \
            labels[:labels.shape[0] // 2]
    x = params["embed"][tokens]
    layer = jax.checkpoint(functools.partial(_layer, cfg, variant))
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p), None), x, params["layers"])
    x = _rms(x, params["final_norm"], float(cfg["rms_norm_eps"]))
    logits = _mm(variant, "btd,vd->btv", x, params["embed"])
    logz = jax.nn.logsumexp(logits, -1)
    true = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - true)


def lr_at(opt: dict, k):
    """Learning rate of step ``k`` (1-based): linear warm-up reaching the
    peak at step ``warmup - 1``, then cosine decay to ``min_lr_frac`` of
    the peak at ``decay_steps``."""
    k = jnp.asarray(k, jnp.float32)
    warm = jnp.minimum(1.0, (k + 1) / max(opt["warmup"], 1))
    prog = jnp.clip((k - opt["warmup"])
                    / max(opt["decay_steps"] - opt["warmup"], 1), 0.0, 1.0)
    lo = opt["min_lr_frac"]
    return opt["lr"] * warm * (lo + (1 - lo) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))


def leaf_names(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree):
    """Euclidean norm of every leaf, in float32."""
    return [jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
            for l in jax.tree.leaves(tree)]


def adamw(opt: dict, p, g, m, v, k):
    """One AdamW step (k is the 1-based step) after a global-norm clip."""
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2, lr = opt["b1"], opt["b2"], lr_at(opt, k)
    kf = jnp.asarray(k, jnp.float32)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    p = jax.tree.map(
        lambda p, m, v: p - lr * ((m / (1 - b1 ** kf))
                                  / (jnp.sqrt(v / (1 - b2 ** kf)) + opt["eps"])
                                  + opt["weight_decay"] * p), p, m, v)
    return p, m, v, gnorm


def train_readings(cfg: dict, opt: dict, key, batches,
                   variant: str = "f32") -> dict:
    """Three AdamW steps from the seeded weights on ``batches[0..2]``.

    Returns each step's loss, every leaf's gradient norm at step 1 (before
    the clip) and the global one, and every leaf's change after the three
    steps."""
    assert variant in VARIANTS, variant
    init = jax.jit(lambda k: jax.tree.map(lambda x: x.astype(jnp.float32),
                                          init_weights(cfg, k)))

    def step(p, m, v, k, tokens, labels):
        lval, g = jax.value_and_grad(
            lambda p: loss(cfg, p, tokens, labels, variant))(p)
        norms = leaf_norms(g)
        p, m, v, gnorm = adamw(opt, p, g, m, v, k)
        return p, m, v, lval, norms, gnorm

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    p = init(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    m, v = zeros(p), zeros(p)
    names = leaf_names(p)
    losses = []
    for k, b in enumerate(batches[:3], start=1):
        p, m, v, lval, norms, gnorm = step(p, m, v, k, b["tokens"],
                                           b["labels"])
        losses.append(float(lval))
        if k == 1:
            grad = dict(zip(names, (float(n) for n in norms)))
            grad_global = float(gnorm)
    del m, v
    delta = jax.jit(lambda p, q: leaf_norms(
        jax.tree.map(lambda a, b: a - b, p, q)))(p, init(key))
    return {"loss": losses, "grad": grad, "grad_global": grad_global,
            "delta": dict(zip(names, (float(n) for n in delta)))}
