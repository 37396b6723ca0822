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

    y0 = jnp.zeros_like(xt)
    if cfg.unroll_experts:           # flops-calibration path (no while loop)
        y = y0
        for e_idx in range(E_loc):
            y, _ = one_expert(y, jnp.int32(e_idx))
    else:
        y, _ = jax.lax.scan(one_expert, y0, jnp.arange(E_loc))
    if axis_name is not None:
        y = jax.lax.psum(y, axis_name)
    return y.reshape(B, T, D), aux


def moe_shardmap(cfg: ModelConfig, mesh, p, x):
    """Wrap the MoE in a shard_map over (data, model): tokens sharded over
    `data`, experts over `model`.

    Default dispatch replicates tokens across model ranks (gather) and
    psums partial outputs.  With ``cfg.moe_a2a`` that is replaced by true
    expert-parallel routing: each model rank keeps only its T-shard, ships
    its tokens' top-k copies to the owning ranks with an all-to-all,
    processes its local experts, and ships results back — wire bytes drop
    from (full-T gather + psum) to 2 x (tokens*k*cap/ranks) per device
    (the paper's spawn_to: computation moves to the data owner)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_model = mesh.shape["model"]

    pspec_p = {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }

    if cfg.moe_a2a and x.shape[1] % n_model == 0:
        def inner_a2a(p_loc, x_loc):
            y, aux = moe_a2a_block(cfg, p_loc, x_loc, n_model)
            return y, jax.lax.pmean(aux, data_axes + ("model",))

        pspec_x = P(data_axes, "model", None)       # keep the T-shard local
        return shard_map(inner_a2a, mesh=mesh,
                         in_specs=(pspec_p, pspec_x),
                         out_specs=(pspec_x, P()),
                         check_vma=False)(p, x)

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


def moe_a2a_block(cfg: ModelConfig, p, x, n_model: int,
                  axis_name: str = "model"):
    """Expert-parallel MoE with all-to-all dispatch (inside shard_map).

    x: (B_loc, T_loc, D) — this rank's token shard; p holds the local
    expert slice (E_loc, D, F)."""
    B, T, D = x.shape
    N = B * T
    E = cfg.n_experts
    E_loc = E // n_model
    xt = x.reshape(N, D)

    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_ids.reshape(-1)].add(
        1.0 / (N * cfg.top_k))
    aux = E * jnp.sum(me * ce)

    # per-destination send buffers: top-C (token, expert) pairs per rank
    dest = top_ids // E_loc                                  # (N, K)
    C = max(4, -(-int(N * cfg.top_k * cfg.capacity_factor / n_model)
                 // 4) * 4)
    C = min(C, N * cfg.top_k)

    flat_tok = jnp.arange(N)[:, None].repeat(cfg.top_k, 1).reshape(-1)
    flat_exp = top_ids.reshape(-1)
    flat_gate = top_p.reshape(-1)
    flat_dest = dest.reshape(-1)

    send_x = jnp.zeros((n_model, C, D), x.dtype)
    send_tok = jnp.full((n_model, C), -1, jnp.int32)
    send_eloc = jnp.zeros((n_model, C), jnp.int32)
    send_gate = jnp.zeros((n_model, C), jnp.float32)
    for r in range(n_model):
        score = jnp.where(flat_dest == r, flat_gate, -jnp.inf)
        g, idx = jax.lax.top_k(score, C)
        keep = g > -jnp.inf
        send_x = send_x.at[r].set(
            jnp.where(keep[:, None], jnp.take(xt, flat_tok[idx], axis=0), 0))
        send_tok = send_tok.at[r].set(
            jnp.where(keep, flat_tok[idx], -1))
        send_eloc = send_eloc.at[r].set(flat_exp[idx] % E_loc)
        send_gate = send_gate.at[r].set(jnp.where(keep, g, 0.0))

    recv_x = jax.lax.all_to_all(send_x, axis_name, 0, 0)
    recv_tok = jax.lax.all_to_all(send_tok, axis_name, 0, 0)
    recv_eloc = jax.lax.all_to_all(send_eloc, axis_name, 0, 0)
    rx = recv_x.reshape(n_model * C, D)
    r_eloc = recv_eloc.reshape(-1)
    r_valid = recv_tok.reshape(-1) >= 0

    # process local experts over the received buffer
    out = jnp.zeros((n_model * C, D), x.dtype)
    for e in range(E_loc):
        sel = jnp.logical_and(r_valid, r_eloc == e)
        xe = jnp.where(sel[:, None], rx, 0)
        h = jax.nn.silu(xe @ p["w_gate"][e]) * (xe @ p["w_up"][e])
        out = out + jnp.where(sel[:, None], h @ p["w_down"][e], 0)

    back = jax.lax.all_to_all(out.reshape(n_model, C, D), axis_name, 0, 0)
    y = jnp.zeros((N, D), x.dtype)
    tok = jnp.maximum(send_tok, 0).reshape(-1)
    gate = jnp.where(send_tok >= 0, send_gate, 0.0).reshape(-1)
    y = y.at[tok].add(back.reshape(-1, D) * gate[:, None].astype(x.dtype))
    return y.reshape(B, T, D), aux
