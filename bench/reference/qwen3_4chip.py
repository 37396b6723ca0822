"""The plain float32 Qwen3 reference of ``qwen3.py``, spread over the chips
of one host, for a configuration whose float32 training state no chip
holds (qwen3-1.7b: weights, gradients and both moments, 27.5 GB).

The weights, the layer, AdamW, ``Precision.HIGHEST`` and the variants are
``qwen3.py``'s own, loaded from beside this file; nothing here imports the
code under test.  What differs is where the numbers live, not what is
computed:

  * every float32 leaf (weights, gradients, moments) is split over the
    configuration's ``chips`` devices along its largest axis that they
    divide (replicated where none does); the rows of a batch, and the
    activations of each row, are split over them too where they divide;
  * the cross entropy runs over blocks of ``CE_ROWS`` positions of every
    row, each under ``jax.checkpoint``, so that one block's (rows, vocab)
    logits live at a time and not the batch's (5 GB at 4 x 2,048).
    ``fp8`` rounds the head's two operands whole, as ``qwen3.loss`` does.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _plain():
    path = Path(__file__).with_name("qwen3.py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_qwen3_of_4chip", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


plain = _plain()
HI, VARIANTS = plain.HI, plain.VARIANTS
program_fields = plain.program_fields
init_weights = plain.init_weights
leaf_names, leaf_norms, adamw = plain.leaf_names, plain.leaf_norms, plain.adamw

AXIS = "chips"
CE_ROWS = 512


def chips_mesh(cfg: dict) -> Mesh:
    """The configuration's ``chips`` first devices, on one axis."""
    return Mesh(np.asarray(jax.devices()[:cfg["chips"]]), (AXIS,))


def split(mesh: Mesh, shape) -> NamedSharding:
    """Along the largest axis the chips divide; replicated where none."""
    fits = [i for i, d in enumerate(shape) if d % mesh.size == 0]
    spec = [None] * len(shape)
    if fits:
        spec[max(fits, key=lambda i: shape[i])] = AXIS
    return NamedSharding(mesh, P(*spec))


def by_rows(mesh: Mesh, shape) -> NamedSharding:
    """Rows (the leading axis) over the chips where they divide."""
    lead = AXIS if shape and shape[0] % mesh.size == 0 else None
    return NamedSharding(mesh, P(lead, *[None] * (len(shape) - 1)))


def loss(cfg: dict, params, tokens, labels, variant: str = "f32",
         mesh: Mesh | None = None):
    """``qwen3.loss``, with the cross entropy in blocks of positions and,
    given a ``mesh``, the residual stream kept split by rows."""
    if variant == "half":
        tokens, labels = tokens[:tokens.shape[0] // 2], \
            labels[:labels.shape[0] // 2]
    keep = (lambda x: x) if mesh is None else (
        lambda x: jax.lax.with_sharding_constraint(x, by_rows(mesh, x.shape)))
    x = keep(params["embed"][tokens])
    layer = jax.checkpoint(functools.partial(plain._layer, cfg, variant))
    x, _ = jax.lax.scan(lambda x, p: (keep(layer(x, p)), None), x,
                        params["layers"])
    x = plain._rms(x, params["final_norm"], float(cfg["rms_norm_eps"]))
    head = params["embed"]
    if variant == "fp8":
        x, head = plain._fp8(x), plain._fp8(head)
    B, T, D = x.shape
    rows = min(CE_ROWS, T)
    assert T % rows == 0, (T, rows)
    xs = x.reshape(B, T // rows, rows, D).swapaxes(0, 1)
    ls = labels.reshape(B, T // rows, rows).swapaxes(0, 1)

    @jax.checkpoint
    def block(total, xl):
        xb, lb = xl
        logits = jnp.einsum("btd,vd->btv", xb, head, precision=HI)
        logz = jax.nn.logsumexp(logits, -1)
        true = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        return total + jnp.sum(logz - true), None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (B * T)


def train_readings(cfg: dict, opt: dict, key, batches,
                   variant: str = "f32") -> dict:
    """``qwen3.train_readings`` over the configuration's chips: three
    AdamW steps from the seeded weights on ``batches[0..2]``; each step's
    loss, every leaf's gradient norm at step 1 (before the clip) and the
    global one, and every leaf's change after the three steps."""
    assert variant in VARIANTS, variant
    mesh = chips_mesh(cfg)
    place = jax.tree.map(lambda s: split(mesh, s.shape),
                         jax.eval_shape(lambda k: init_weights(cfg, k), key))
    init = jax.jit(lambda k: jax.tree.map(lambda x: x.astype(jnp.float32),
                                          init_weights(cfg, k)),
                   out_shardings=place)
    rows = by_rows(mesh, np.shape(batches[0]["tokens"]))

    def step(p, m, v, k, tokens, labels):
        lval, g = jax.value_and_grad(
            lambda p: loss(cfg, p, tokens, labels, variant, mesh))(p)
        g = jax.lax.with_sharding_constraint(g, place)
        norms = leaf_norms(g)
        p, m, v, gnorm = adamw(opt, p, g, m, v, k)
        return p, m, v, lval, norms, gnorm

    step = jax.jit(step, in_shardings=(place, place, place, None, rows, rows),
                   out_shardings=(place, place, place, None, None, None),
                   donate_argnums=(0, 1, 2))
    p = init(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=place)
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
