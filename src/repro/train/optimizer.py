"""Optimizers, pure JAX: AdamW (configurable moment dtype) and Adafactor
(factored second moment — the memory-scaling answer for the 480B config).

Moments are "TBox-tied" to their parameters: they share the parameter's
sharding (see dist.sharding.opt_state_specs) so the optimizer update is
fully local — no collective touches optimizer state, ever.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptConfig:
    name: str = "adamw"              # adamw | adafactor
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    clip_norm: float = 1.0
    moment_dtype: str = "float32"    # bfloat16 halves optimizer memory
    warmup: int = 100
    decay_steps: int = 10_000
    min_lr_frac: float = 0.1


def schedule(cfg: OptConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, (step + 1) / max(cfg.warmup, 1))
    prog = jnp.clip((step - cfg.warmup) / max(cfg.decay_steps - cfg.warmup, 1),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def _factored_dims(shape):
    """Adafactor factors the two largest trailing dims of >=2D leaves."""
    if len(shape) < 2:
        return None
    return len(shape) - 2, len(shape) - 1


def init_opt_state(cfg: OptConfig, params):
    mdt = jnp.dtype(cfg.moment_dtype)
    state = {"count": jnp.zeros((), jnp.int32)}
    if cfg.name == "adamw":
        state["mu"] = jax.tree.map(lambda p: jnp.zeros(p.shape, mdt), params)
        state["nu"] = jax.tree.map(lambda p: jnp.zeros(p.shape, mdt), params)
        return state

    def vr(p):
        f = _factored_dims(p.shape)
        if f is None:
            return jnp.zeros(p.shape, jnp.float32)
        shape = list(p.shape)
        shape[f[1]] = 1
        return jnp.zeros(tuple(shape), jnp.float32)

    def vc(p):
        f = _factored_dims(p.shape)
        if f is None:
            return jnp.zeros((1,) * p.ndim, jnp.float32)
        shape = list(p.shape)
        shape[f[0]] = 1
        return jnp.zeros(tuple(shape), jnp.float32)

    state["vr"] = jax.tree.map(vr, params)
    state["vc"] = jax.tree.map(vc, params)
    return state


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(tree)))


def clip_by_global_norm(tree, max_norm):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), tree), norm


@jax.named_scope("optimizer")
def apply_updates(cfg: OptConfig, params, grads, state):
    """Returns (new_params, new_state, metrics)."""
    grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    count = state["count"] + 1
    lr = schedule(cfg, count)
    mdt = jnp.dtype(cfg.moment_dtype)

    if cfg.name == "adamw":
        bc1 = 1 - cfg.b1 ** count.astype(jnp.float32)
        bc2 = 1 - cfg.b2 ** count.astype(jnp.float32)

        def upd(p, g, m, v):
            m2 = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * g
            v2 = cfg.b2 * v.astype(jnp.float32) + (1 - cfg.b2) * g * g
            step = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + cfg.eps)
            step = step + cfg.weight_decay * p.astype(jnp.float32)
            p2 = p.astype(jnp.float32) - lr * step
            return p2.astype(p.dtype), m2.astype(mdt), v2.astype(mdt)

        out = jax.tree.map(upd, params, grads, state["mu"], state["nu"])
        leaves, treedef = jax.tree.flatten(out, is_leaf=lambda x: isinstance(x, tuple))
        new_p = treedef.unflatten([l[0] for l in leaves])
        new_mu = treedef.unflatten([l[1] for l in leaves])
        new_nu = treedef.unflatten([l[2] for l in leaves])
        new_state = {"count": count, "mu": new_mu, "nu": new_nu}
        return new_p, new_state, {"grad_norm": gnorm, "lr": lr}

    # adafactor (momentum-less, factored second moment)
    def upd(p, g, vr, vc):
        f = _factored_dims(p.shape)
        g2 = g * g + 1e-30
        decay = 1.0 - (count.astype(jnp.float32)) ** -0.8
        if f is None:
            v2 = decay * vr + (1 - decay) * g2
            precond = g * jax.lax.rsqrt(v2 + cfg.eps)
            vr2, vc2 = v2, vc
        else:
            r, c = f
            vr2 = decay * vr + (1 - decay) * jnp.mean(g2, axis=c, keepdims=True)
            vc2 = decay * vc + (1 - decay) * jnp.mean(g2, axis=r, keepdims=True)
            denom = vr2 * vc2 / jnp.maximum(
                jnp.mean(vr2, axis=r, keepdims=True), 1e-30)
            precond = g * jax.lax.rsqrt(denom + cfg.eps)
        # relative step clipping (RMS of update <= 1)
        rms = jnp.sqrt(jnp.mean(jnp.square(precond)) + 1e-30)
        precond = precond / jnp.maximum(1.0, rms)
        p2 = p.astype(jnp.float32) - lr * (precond
                                           + cfg.weight_decay * p.astype(jnp.float32))
        return p2.astype(p.dtype), vr2, vc2

    out = jax.tree.map(upd, params, grads, state["vr"], state["vc"])
    leaves, treedef = jax.tree.flatten(out, is_leaf=lambda x: isinstance(x, tuple))
    new_p = treedef.unflatten([l[0] for l in leaves])
    new_vr = treedef.unflatten([l[1] for l in leaves])
    new_vc = treedef.unflatten([l[2] for l in leaves])
    new_state = {"count": count, "vr": new_vr, "vc": new_vc}
    return new_p, new_state, {"grad_norm": gnorm, "lr": lr}
