"""Deterministic synthetic LM data pipeline, shardable across the mesh.

Markov-chain token streams (not uniform noise) so the loss actually falls
during the example runs; batches are placed with the same NamedSharding the
train step expects, so input transfer is one host->device scatter.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import NamedSharding

from repro.dist.sharding import batch_specs


def synthetic_batches(vocab: int, global_batch: int, seq_len: int,
                      seed: int = 0, prefix_len: int = 0, d_model: int = 0,
                      dtype="bfloat16"):
    """Infinite iterator of {"tokens", "labels"[, "prefix_embeds"]} numpy."""
    rng = np.random.default_rng(seed)
    # sparse Markov transition: each symbol prefers ~8 successors
    succ = rng.integers(0, vocab, size=(vocab, 8))
    while True:
        toks = np.empty((global_batch, seq_len + 1), np.int32)
        toks[:, 0] = rng.integers(0, vocab, size=global_batch)
        choice = rng.integers(0, 8, size=(global_batch, seq_len))
        for t in range(seq_len):
            toks[:, t + 1] = succ[toks[:, t], choice[:, t]]
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if prefix_len:
            batch["prefix_embeds"] = rng.standard_normal(
                (global_batch, prefix_len, d_model)).astype(dtype)
        yield batch


def shard_batch(mesh, batch):
    """Place a host batch onto the mesh with the canonical input sharding;
    a leaf already on the mesh stays as it is."""
    if mesh is None:
        return jax.tree.map(jax.numpy.asarray, batch)

    def put(x, spec):
        sharding = getattr(x, "sharding", None)
        if isinstance(sharding, NamedSharding) and sharding.mesh == mesh:
            return x
        return jax.device_put(x, NamedSharding(mesh, spec))

    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    return jax.tree.map(put, batch, batch_specs(mesh, abstract))
