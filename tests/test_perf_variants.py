"""The mesh path against the off-mesh path, and the chunked loss against
the full one.

The mesh cases run on a 1x1 (data, model) mesh, so the sharding
constraints and the MoE shard_map execute for real (one shard), and must
reproduce what the same call computes with no mesh.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import (decode_step, forward, init_cache, init_params,
                          loss_fn)
from repro.models.moe import moe_block, moe_params, moe_shardmap

KEY = jax.random.PRNGKey(0)


def mesh11():
    return make_mesh((1, 1), ("data", "model"))


def test_chunked_ce_matches_full():
    cfg = dataclasses.replace(configs.smoke("gemma_7b"), dtype="float32")
    params = init_params(cfg, KEY)
    rng = np.random.default_rng(0)
    b = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)), jnp.int32)}
    b["labels"] = jnp.roll(b["tokens"], -1, 1)
    full = float(loss_fn(cfg, params, b))
    chunked = float(loss_fn(dataclasses.replace(cfg, chunked_ce=4), params, b))
    np.testing.assert_allclose(chunked, full, rtol=1e-5)


def _forward(mesh):
    cfg = dataclasses.replace(configs.smoke("gemma_7b"), dtype="float32")
    params = init_params(cfg, KEY)
    logits, _ = forward(cfg, params, {"tokens": jnp.zeros((2, 32), jnp.int32)},
                        mesh=mesh)
    return [(logits, 1e-4, 1e-4)]


def _decode(mesh):
    """Six decode steps of a granite block against a 64-token cache."""
    cfg = dataclasses.replace(configs.smoke("granite_34b"), dtype="float32")
    params = init_params(cfg, KEY)
    toks = jnp.asarray(np.random.default_rng(2).integers(0, cfg.vocab, (2, 6)),
                       jnp.int32)
    cache = init_cache(cfg, 2, 64)
    outs = []
    for t in range(6):
        lg, cache = decode_step(cfg, params, cache, toks[:, t:t + 1],
                                mesh=mesh)
        outs.append(lg)
    return [(jnp.concatenate(outs, axis=1), 2e-3, 2e-3)]


def _moe(mesh):
    """The expert block, with capacity for every token, on the mesh
    through its shard_map and off it directly."""
    cfg = dataclasses.replace(configs.smoke("qwen3_moe_235b"),
                              dtype="float32", capacity_factor=8.0)
    p = moe_params(cfg, KEY, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)) * 0.3
    y, aux = moe_block(cfg, p, x) if mesh is None \
        else moe_shardmap(cfg, mesh, p, x)
    return [(y, 2e-3, 2e-3), (aux, 1e-4, 0)]


@pytest.mark.parametrize("run", [_forward, _decode, _moe],
                         ids=["forward", "decode", "moe"])
def test_mesh_path_matches_off_mesh(run):
    for (a, rtol, atol), (b, _, _) in zip(run(mesh11()), run(None)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)
