"""Sharding rules: the partition map between logical tensors and the mesh.

The DSM core gives every object one logical address and a per-server
partition of the physical backing (GlobalHeap); this module is the same
contract for the JAX stack.  Every spec produced here goes through
``_fit``, which drops any mesh axis that does not evenly divide the
corresponding tensor dimension — so one rule table serves every
architecture and every mesh shape, degrading gracefully to replication
instead of failing to partition (ownership can always fall back to a
single owner; it can never be ambiguous).

Layout contract (see ``models/layers.py``):
  * attention projections:  wq (D, H, hd)   wk/wv (D, Hkv, hd)   wo (H, hd, D)
  * MLP:                    w_gate/w_up (D, F)   w_down (F, D)
  * MoE experts:            (E, D, F) / (E, F, D), expert dim over ``model``
  * scan-stacked trees carry a leading layer-group dim — rules match the
    *trailing* dims, so stacked and unrolled trees share one table.

Rule flags (process-wide, like the mesh registry):
  * ``dp_only``       — pure ZeRO-3: every leaf FSDP-sharded along its first
                        dividing dim over *all* mesh axes; batch over all axes.
  * ``serve_weights`` — TP-only weights (no FSDP data axes): serving has no
                        optimizer state to amortize, and re-gathering weights
                        per token dominates decode collectives.
  * ``ulysses``       — inputs arrive sequence-sharded over ``model``; the
                        attention all-to-all (``ulysses_heads``) re-shards
                        seq<->heads around the score computation.
"""

from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import tree_flatten_with_path, tree_unflatten

# ---------------------------------------------------------------------------
#  mesh + rule-flag registry
# ---------------------------------------------------------------------------
_MESH = None
_FLAGS = {"ulysses": False, "dp_only": False, "serve_weights": False}


def set_mesh(mesh):
    """Install (or clear, with ``None``) the process-wide mesh."""
    global _MESH
    _MESH = mesh
    return mesh


def current_mesh():
    return _MESH


def set_rule_flags(**flags):
    """Update rule flags; unknown keys are rejected."""
    for k, v in flags.items():
        if k not in _FLAGS:
            raise ValueError(f"unknown rule flag {k!r}")
        _FLAGS[k] = bool(v)
    return dict(_FLAGS)


def rule_flags() -> dict:
    return dict(_FLAGS)


# ---------------------------------------------------------------------------
#  divisor fitting
# ---------------------------------------------------------------------------
def _axis_size(mesh, axes) -> int | None:
    """Product of the named axes' sizes; None if any axis is absent."""
    n = 1
    for a in axes if isinstance(axes, tuple) else (axes,):
        sz = dict(mesh.shape).get(a)
        if sz is None:
            return None
        n *= sz
    return n


def _fit(mesh, spec, shape) -> P:
    """Fit ``spec`` to ``shape``: drop every axis that does not divide.

    Tuple entries keep their longest dividing *prefix* (partial sharding
    beats replication); plain entries are kept or dropped whole.  A spec
    longer than the shape is truncated; shorter is padded with None.
    """
    entries = tuple(spec)[:len(shape)]
    entries = entries + (None,) * (len(shape) - len(entries))
    out = []
    for dim, axes in zip(shape, entries):
        if axes is None:
            out.append(None)
        elif isinstance(axes, tuple):
            kept = axes
            while kept:
                n = _axis_size(mesh, kept)
                if n is not None and dim % n == 0:
                    break
                kept = kept[:-1]
            out.append(kept if kept else None)
        else:
            n = _axis_size(mesh, axes)
            out.append(axes if n is not None and dim % n == 0 else None)
    return P(*out)


def _pod_data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in dict(mesh.shape))


def _dp_axes(mesh) -> tuple:
    """Axes that carry the batch: (pod, data) normally, every axis under
    dp_only, nothing for weight specs under serve_weights (see callers)."""
    if _FLAGS["dp_only"]:
        return tuple(dict(mesh.shape))
    return _pod_data_axes(mesh)


# ---------------------------------------------------------------------------
#  parameter rules
# ---------------------------------------------------------------------------
# (path regex, trailing-dim tokens).  First match wins; tokens are
# "dp" (FSDP axes), "model" (TP axis), or None (replicated).  Rules name
# only the trailing dims — scan stacking pads None on the left.
_PARAM_RULES: tuple[tuple[str, tuple], ...] = (
    # attention projections
    (r"attn/wq$",            ("dp", "model", None)),
    (r"attn/(wk|wv)$",       ("dp", "model", None)),
    (r"attn/wo$",            ("model", None, "dp")),
    # dense MLP
    (r"mlp/(w_gate|w_up)$",  ("dp", "model")),
    (r"mlp/w_down$",         ("model", "dp")),
    # MoE: expert dim over model (expert parallelism), D FSDP-sharded
    (r"moe/(w_gate|w_up)$",  ("model", "dp", None)),
    (r"moe/w_down$",         ("model", None, "dp")),
    (r"moe/router$",         (None, "model")),
    # RG-LRU recurrent block
    (r"rec/(w_in|w_gate)$",  ("dp", "model")),
    (r"rec/(wa|wx)$",        ("dp", "model")),
    (r"rec/w_out$",          ("model", "dp")),
    (r"rec/conv$",           (None, "model")),
    # RWKV time-mix / channel-mix (flat under the layer dict)
    (r"(wr|wk|wv|wg|wo|cr)$", ("dp", "model")),
    (r"ck$",                 ("dp", "model")),
    (r"cv$",                 ("model", "dp")),
    # embeddings / head
    (r"embed$",              ("model", "dp")),
    (r"lm_head$",            ("dp", "model")),
)
_COMPILED_RULES = tuple((re.compile(rx), spec) for rx, spec in _PARAM_RULES)


def _path_str(path) -> str:
    toks = []
    for k in path:
        if hasattr(k, "key"):
            toks.append(str(k.key))
        elif hasattr(k, "idx"):
            toks.append(str(k.idx))
        else:                                           # pragma: no cover
            toks.append(str(k))
    return "/".join(toks)


def _resolve(token, mesh):
    if token == "dp":
        if _FLAGS["serve_weights"]:
            return None
        return _pod_data_axes(mesh) or None
    if token == "model":
        return "model"
    return token


def _zero3_spec(mesh, shape) -> P:
    """dp_only: FSDP-shard the dim covering the most mesh axes (longest
    dividing prefix of the full axis tuple); earliest dim wins ties."""
    axes = tuple(dict(mesh.shape))
    best = None                                  # (coverage, dim, kept)
    for i, dim in enumerate(shape):
        kept = axes
        while kept:
            n = _axis_size(mesh, kept)
            if n is not None and dim % n == 0:
                break
            kept = kept[:-1]
        if kept:
            cov = _axis_size(mesh, kept)
            if best is None or cov > best[0]:
                best = (cov, i, kept)
    entries = [None] * len(shape)
    if best is not None:
        entries[best[1]] = best[2]
    return P(*entries)


def param_specs(mesh, params):
    """PartitionSpec pytree mirroring ``params`` (abstract or concrete)."""
    flat, treedef = tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        shape = leaf.shape
        if _FLAGS["dp_only"]:
            specs.append(_zero3_spec(mesh, shape))
            continue
        name = _path_str(path)
        for rx, tokens in _COMPILED_RULES:
            if rx.search(name):
                resolved = tuple(_resolve(t, mesh) for t in tokens)
                resolved = resolved[-len(shape):] if shape else ()
                full = (None,) * (len(shape) - len(resolved)) + resolved
                specs.append(_fit(mesh, P(*full), shape))
                break
        else:
            specs.append(P(*([None] * len(shape))))
    return tree_unflatten(treedef, specs)


def opt_state_specs(mesh, opt_state, params):
    """Moments are TBox-tied to their parameters: each moment leaf inherits
    the parameter's spec, re-fitted to its own shape (Adafactor's collapsed
    dims fall back to replication along that dim)."""
    pspecs = param_specs(mesh, params)

    def tied(subtree):
        return jax.tree.map(lambda leaf, s: _fit(mesh, s, leaf.shape),
                            subtree, pspecs)

    return {k: (tied(v) if isinstance(v, dict) else P())
            for k, v in opt_state.items()}


def train_shardings(mesh, params, opt_state=None, batch=None):
    """The ``NamedSharding`` trees of a train step's arguments on ``mesh``:
    parameters by ``param_specs``, optimizer state tied to them by
    ``opt_state_specs``, inputs by ``batch_specs``.  Trees may be abstract
    or concrete; a part not given comes back as None.  Returns
    ``(params, opt_state, batch)``."""
    def named(specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))
    return (named(param_specs(mesh, params)),
            None if opt_state is None
            else named(opt_state_specs(mesh, opt_state, params)),
            None if batch is None else named(batch_specs(mesh, batch)))


# ---------------------------------------------------------------------------
#  data / activation / cache specs
# ---------------------------------------------------------------------------
def batch_specs(mesh, batch):
    """Inputs: batch dim over the dp axes; under the ulysses flag the
    sequence dim is additionally sharded over ``model`` (the attention
    all-to-all re-shards it to heads)."""
    dp = _dp_axes(mesh)
    seq = "model" if (_FLAGS["ulysses"] and not _FLAGS["dp_only"]) else None

    def one(leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        entries = (dp or None,) + (seq,) * (1 if nd > 1 else 0) \
            + (None,) * max(0, nd - 2)
        return _fit(mesh, P(*entries), leaf.shape)

    return jax.tree.map(one, batch)


def activation_spec(mesh, shape) -> P:
    """(B, T, D) residual-stream layout: batch over dp, sequence over
    ``model`` (Megatron-style sequence parallelism).  dp_only drops the
    sequence sharding (pure data parallel)."""
    dp = _dp_axes(mesh)
    if len(shape) == 0:
        return P()
    if _FLAGS["dp_only"]:
        entries = (dp or None,) + (None,) * (len(shape) - 1)
    else:
        entries = (dp or None,) \
            + (("model",) if len(shape) > 1 else ()) \
            + (None,) * max(0, len(shape) - 2)
    return _fit(mesh, P(*entries), shape)


def shard_act(x, mesh=None):
    """Constrain an activation to the canonical layout (no-op off-mesh)."""
    mesh = mesh if mesh is not None else _MESH
    if mesh is None:
        return x
    spec = activation_spec(mesh, x.shape)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def ulysses_heads(x, mesh=None):
    """Ulysses sequence parallelism: re-shard (B, T, H, hd) from
    sequence-over-model to heads-over-model.  XLA lowers the constraint
    flip to a single all-to-all; identity when no mesh is installed."""
    mesh = mesh if mesh is not None else _MESH
    if mesh is None or "model" not in dict(mesh.shape):
        return x
    dp = _pod_data_axes(mesh)
    spec = _fit(mesh, P(dp or None, None, "model", None), x.shape)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def cache_specs(mesh, cache):
    """KV / recurrent-state caches: batch over dp; 4-D leaves (attention
    k/v (B, S, Hkv, hd), rwkv S (B, H, M, M)) shard dim 1 over ``model``
    so decode attention can keep every cache shard local."""
    dp = _dp_axes(mesh)
    # dp_only already spreads the batch over `model`; reusing it on the
    # sequence dim would duplicate the axis in one spec
    seq = None if _FLAGS["dp_only"] else "model"

    def one(leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        if nd >= 4:
            entries = (dp or None, seq) + (None,) * (nd - 2)
        elif nd >= 2:
            entries = (dp or None,) + (None,) * (nd - 1)
        else:
            entries = (None,)
        return _fit(mesh, P(*entries), leaf.shape)

    return jax.tree.map(one, cache)
