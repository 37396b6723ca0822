"""Public model API: init / forward / loss / cache / decode + batch specs."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import transformer as T
from .transformer import forward_hidden  # noqa: F401  (re-export)

init_params = T.init_params
init_cache = T.init_cache
forward = T.forward
decode_step = T.decode_step


def loss_fn(cfg: ModelConfig, params, batch, mesh=None,
            remat_policy=T.RECOMPUTE):
    """Causal-LM cross entropy (+ MoE load-balance aux).

    With ``cfg.chunked_ce = n`` the head matmul + CE run per sequence-chunk
    inside a scan, so the (B,T,V) logits (bf16 *and* the f32 cast) never
    materialize — the §Perf memory-term optimization.  ``remat_policy``
    is the layer scan's checkpoint policy under ``cfg.remat``."""
    (x, aux), head = T.forward_hidden(cfg, params, batch, mesh=mesh,
                                      remat_policy=remat_policy)
    return _lm_head_loss(cfg, x, head, batch) + 0.01 * aux


@jax.named_scope("lm_head_loss")
def _lm_head_loss(cfg: ModelConfig, x, head, batch):
    """Mean next-token NLL of the final hidden states through the head."""
    labels = batch["labels"]
    if cfg.chunked_ce:
        if cfg.prefix_len and "prefix_embeds" in batch:
            x = x[:, -labels.shape[1]:, :]
        B, Tlen, D = x.shape
        n = cfg.chunked_ce
        C = Tlen // n

        def chunk(carry, xs):
            xc, lc = xs                                  # (B,C,D), (B,C)
            logits = jnp.einsum("bcd,dv->bcv", xc, head).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            true = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
            return carry + jnp.sum(logz - true), None

        xs = (x.reshape(B, n, C, D).swapaxes(0, 1),
              labels.reshape(B, n, C).swapaxes(0, 1))
        total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), xs)
        return total / (B * Tlen)

    logits = jnp.einsum("btd,dv->btv", x, head)
    if cfg.prefix_len and "prefix_embeds" in batch:
        logits = logits[:, -labels.shape[1]:, :]       # loss on text positions
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    return (logz - true_logit).mean()


def build_batch_spec(cfg: ModelConfig, global_batch: int, seq_len: int,
                     mode: str = "train"):
    """ShapeDtypeStructs for every model input (stand-ins for compiling
    a step without data)."""
    if mode in ("train", "prefill"):
        spec = {
            "tokens": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32),
        }
        if mode == "train":
            spec["labels"] = jax.ShapeDtypeStruct((global_batch, seq_len),
                                                  jnp.int32)
        if cfg.prefix_len:
            spec["prefix_embeds"] = jax.ShapeDtypeStruct(
                (global_batch, cfg.prefix_len, cfg.d_model),
                jnp.dtype(cfg.dtype))
        return spec
    # decode: one new token against a cache of length seq_len
    return {"tokens": jax.ShapeDtypeStruct((global_batch, 1), jnp.int32)}
