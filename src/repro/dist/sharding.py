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

No state: every function takes the mesh it lays out for; ``shard_act``
alone also takes None (one device), and then does nothing.  Batch and
activations go over the data axes (``pod``, ``data``); ``model`` carries
the residual stream's sequence.  The dense projections (attention and
MLP) are sharded on d_model alone, over the data axes and ``model``
together: each chip holds a slice of every weight, a layer gathers it
whole, and its gradient comes back to the owner in one reduce-scatter.
The other weight rules put the FSDP half on the data axes and a second
dim (experts, RG-LRU/RWKV widths, vocabulary) on ``model``.
"""

from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import tree_flatten_with_path, tree_unflatten

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


def _dp_axes(mesh) -> tuple:
    """Axes that carry the batch, and the FSDP half of the weights."""
    return tuple(a for a in ("pod", "data") if a in dict(mesh.shape))


# ---------------------------------------------------------------------------
#  parameter rules
# ---------------------------------------------------------------------------
# (path regex, trailing-dim tokens).  First match wins; tokens are
# "dp" (FSDP axes), "model" (TP axis), "all" (the dp axes and ``model``
# together, on one dim), or None (replicated).  Rules name only the
# trailing dims — scan stacking pads None on the left.
_PARAM_RULES: tuple[tuple[str, tuple], ...] = (
    # dense projections: d_model over every axis at once.  A one-dim tile
    # lets XLA turn each layer's weight-gradient all-reduce + slice into
    # one reduce-scatter; a two-dim tile blocks that rewrite.
    (r"attn/(wq|wk|wv)$",    ("all", None, None)),
    (r"attn/wo$",            (None, None, "all")),
    (r"mlp/(w_gate|w_up)$",  ("all", None)),
    (r"mlp/w_down$",         (None, "all")),
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
        return _dp_axes(mesh) or None
    if token == "all":
        axes = _dp_axes(mesh) + (("model",) if "model" in dict(mesh.shape)
                                 else ())
        return axes or None
    return token


def param_specs(mesh, params):
    """PartitionSpec pytree mirroring ``params`` (abstract or concrete)."""
    flat, treedef = tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        shape = leaf.shape
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
    """Inputs: batch dim over the dp axes, the rest replicated."""
    dp = _dp_axes(mesh)

    def one(leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        return _fit(mesh, P(dp or None, *(None,) * (nd - 1)), leaf.shape)

    return jax.tree.map(one, batch)


def activation_spec(mesh, shape) -> P:
    """(B, T, D) residual-stream layout: batch over dp, sequence over
    ``model`` (Megatron-style sequence parallelism)."""
    dp = _dp_axes(mesh)
    if len(shape) == 0:
        return P()
    entries = (dp or None,) \
        + (("model",) if len(shape) > 1 else ()) \
        + (None,) * max(0, len(shape) - 2)
    return _fit(mesh, P(*entries), shape)


def shard_act(x, mesh):
    """Constrain an activation to the canonical layout on ``mesh``; with
    no mesh, ``x`` as it is."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, activation_spec(mesh, x.shape)))


def cache_specs(mesh, cache):
    """KV / recurrent-state caches: batch over dp; 4-D leaves (attention
    k/v (B, S, Hkv, hd), rwkv S (B, H, M, M)) shard dim 1 over ``model``
    so decode attention can keep every cache shard local."""
    dp = _dp_axes(mesh)

    def one(leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        if nd >= 4:
            entries = (dp or None, "model") + (None,) * (nd - 2)
        elif nd >= 2:
            entries = (dp or None,) + (None,) * (nd - 1)
        else:
            entries = (None,)
        return _fit(mesh, P(*entries), leaf.shape)

    return jax.tree.map(one, cache)
