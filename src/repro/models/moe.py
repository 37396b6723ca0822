"""Mixture-of-Experts block: top-k token-choice routing with capacity,
expert-parallel over the `model` mesh axis.

Distribution (EP = the paper's spawn_to / compute-to-data, see DESIGN §2.2):
expert weights are sharded E over `model`; inside a shard_map the tokens
(replicated across model ranks by the enclosing partitioner) are processed
only by the rank owning the chosen expert, and partial outputs are psum'd.
XLA turns the boundary replication + psum into an all-gather/reduce-scatter
pair against the sequence-parallel residual stream.

Dispatch is sort-free: per local expert, take the top-C tokens by router
score (static shapes, capacity drop like GShard).  FLOPs are exactly
capacity_factor × active-expert compute — no dense-dispatch einsum waste.

``axis_name=None`` runs the same code on one device (tests / smoke).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig


def moe_params(cfg: ModelConfig, key, dtype):
    d, f, E = cfg.d_model, cfg.e_ff, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "router": jax.random.normal(k1, (d, E), jnp.float32) * s,
        "w_gate": jax.random.normal(k2, (E, d, f), dtype) * s,
        "w_up": jax.random.normal(k3, (E, d, f), dtype) * s,
        "w_down": jax.random.normal(k4, (E, f, d), dtype) * f ** -0.5,
    }


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = max(1, int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    c = -(-c // 4) * 4                                  # multiple of 4
    return min(n_tokens, c)


def moe_block(cfg: ModelConfig, p, x, *, axis_name: str | None = None,
              axis_size: int = 1):
    """x: (B, T, D) local tokens.  Returns (y, aux_loss)."""
    B, T, D = x.shape
    N = B * T
    E = cfg.n_experts
    xt = x.reshape(N, D)

    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, cfg.top_k)            # (N, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    me = probs.mean(axis=0)                                      # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_ids.reshape(-1)].add(
        1.0 / (N * cfg.top_k))
    aux = E * jnp.sum(me * ce)

    # per-token score for each expert: router prob if chosen, else -inf
    assigned = jnp.full((N, E), -jnp.inf, jnp.float32)
    rows = jnp.arange(N)[:, None].repeat(cfg.top_k, 1).reshape(-1)
    assigned = assigned.at[rows, top_ids.reshape(-1)].set(top_p.reshape(-1))

    C = _capacity(cfg, N)
    E_loc = E // axis_size
    if axis_name is not None:
        rank = jax.lax.axis_index(axis_name)
        e0 = rank * E_loc
    else:
        e0 = 0

    def one_expert(carry, e_idx):
        y = carry
        e = e0 + e_idx
        score = assigned[:, e]                                   # (N,)
        g, idx = jax.lax.top_k(score, C)                         # top-C tokens
        keep = (g > -jnp.inf)
        gate = jnp.where(keep, g, 0.0).astype(x.dtype)           # (C,)
        xe = jnp.take(xt, idx, axis=0)                           # (C, D)
        wg = p["w_gate"][e_idx] if axis_name else p["w_gate"][e]
        wu = p["w_up"][e_idx] if axis_name else p["w_up"][e]
        wd = p["w_down"][e_idx] if axis_name else p["w_down"][e]
        h = jax.nn.silu(xe @ wg) * (xe @ wu)
        out = (h @ wd) * gate[:, None]                           # (C, D)
        y = y.at[idx].add(jnp.where(keep[:, None], out, 0.0))
        return y, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(xt), jnp.arange(E_loc))
    if axis_name is not None:
        y = jax.lax.psum(y, axis_name)
    return y.reshape(B, T, D), aux


def moe_shardmap(cfg: ModelConfig, mesh, p, x):
    """Wrap the MoE in a shard_map over (data, model): tokens sharded over
    `data`, experts over `model`.  Tokens are replicated across model
    ranks (gather) and the partial outputs psum'd."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    pspec_p = {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }

    def inner(p_loc, x_loc):
        y, aux = moe_block(cfg, p_loc, x_loc, axis_name="model",
                           axis_size=mesh.shape["model"])
        return y, jax.lax.pmean(aux, data_axes + ("model",))

    pspec_x = P(data_axes, None, None)
    y, aux = shard_map(
        inner, mesh=mesh,
        in_specs=(pspec_p, pspec_x),
        out_specs=(pspec_x, P()),
        check_vma=False,
    )(p, x)
    return y, aux

