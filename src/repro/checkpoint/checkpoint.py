"""Color-versioned checkpointing with elastic resharding.

DRust's fault-tolerance design (§4.2.3) applied to training state:

  * write-backs are batched per ownership epoch — the checkpoint hook fires
    at the train step's mutable-borrow drop, and only every
    ``every_n_epochs`` (the controller's pressure/latency trade);
  * the checkpoint is addressed by the state's *colored address*: restore
    verifies it resumes the exact write epoch (no torn state);
  * leaves are stored per logical address with their global shapes, so a
    checkpoint taken on one mesh restores onto any other mesh ("promote the
    backup on a different cluster" — elastic resharding is a re-partition
    of the PGAS, not a format change).

Format: one ``.npz`` per snapshot + a JSON manifest (leaf paths, shapes,
dtypes, color, step).

``quantize=True`` stores large float leaves int8 on disk
(``repro.dist.compression.quantize_int8``: symmetric per-tensor scale,
``|x - q*scale| <= scale/2`` — the error-feedback bound, asserted at save
time) and dequantizes transparently on restore; small leaves (norms,
scalars, integer steps) stay exact.  ~4x smaller snapshots for the cost of
one quantization step of noise — the same trade the wire compression makes.
"""

from __future__ import annotations

import json
import weakref
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jaxstate import ColoredAddr, OwnedState


def _flatten(tree: Any):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        out[path] = leaf
    return out, treedef


def save(path: str | Path, tree: Any, *, color: int = 0, step: int = 0,
         extra: dict | None = None, quantize: bool = False,
         min_quant_size: int = 64) -> Path:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    leaves, _ = _flatten(tree)
    arrays = {}
    manifest_leaves = {}
    for k, v in leaves.items():
        a = np.asarray(v)
        if a.dtype.kind == "V" or str(a.dtype) == "bfloat16":
            a = np.asarray(jnp.asarray(v).astype(jnp.float32))
        entry = {"shape": list(a.shape), "dtype": str(a.dtype)}
        if quantize and a.dtype.kind == "f" and a.size >= min_quant_size:
            from repro.dist.compression import quantize_int8
            q, scale = quantize_int8(a)
            q, scale = np.asarray(q), np.asarray(scale, dtype=np.float32)
            # Error-feedback bound (repro.dist.compression): the on-disk
            # representation may never be more than half a quantization
            # step from the live value.
            err = np.max(np.abs(a.astype(np.float32)
                                - q.astype(np.float32) * scale))
            assert err <= float(scale) / 2 + 1e-12, \
                f"{k}: int8 checkpoint error {err} exceeds scale/2"
            arrays[k + "::q"] = q
            arrays[k + "::scale"] = scale
            entry["quantized"] = True
        else:
            arrays[k] = a
        manifest_leaves[k] = entry
    np.savez(str(path) + ".npz", **arrays)
    manifest = {
        "color": color, "step": step,
        "leaves": manifest_leaves,
        "extra": extra or {},
    }
    Path(str(path) + ".json").write_text(json.dumps(manifest, indent=1))
    return path


def restore(path: str | Path, like: Any, *, mesh=None, specs=None) -> tuple:
    """Restore into the structure of ``like`` (abstract or concrete pytree).
    With ``mesh``+``specs`` the leaves are placed with NamedSharding —
    restoring onto a different mesh reshards transparently."""
    path = Path(path)
    manifest = json.loads(Path(str(path) + ".json").read_text())
    data = np.load(str(path) + ".npz")
    leaves_like, treedef = _flatten(like)
    specs_flat = None
    if specs is not None:
        specs_flat, _ = _flatten(specs)
    out = {}
    for k, ref_leaf in leaves_like.items():
        if manifest["leaves"].get(k, {}).get("quantized"):
            from repro.dist.compression import dequantize_int8
            arr = np.asarray(
                dequantize_int8(jnp.asarray(data[k + "::q"]),
                                jnp.asarray(data[k + "::scale"])))
        else:
            arr = data[k]
        want = jnp.dtype(ref_leaf.dtype)
        a = jnp.asarray(arr).astype(want)
        if mesh is not None and specs_flat is not None and k in specs_flat:
            a = jax.device_put(a, jax.sharding.NamedSharding(
                mesh, specs_flat[k]))
        out[k] = a
    restored = treedef.unflatten([out[k] for k in leaves_like])
    return restored, manifest


class CheckpointManager:
    """Epoch-batched async-style checkpointing for an OwnedState."""

    def __init__(self, directory: str | Path, state: OwnedState,
                 every_n_epochs: int = 1, keep: int = 3,
                 quantize: bool = False):
        self.dir = Path(directory)
        self._state = weakref.ref(state)   # weak: see ReplicaSlot
        self.every = every_n_epochs
        self.keep = keep
        self.quantize = quantize           # int8 on disk, exact manifest
        self.saved: list[tuple[int, Path]] = []
        state.on_epoch.append(self._hook)

    def _hook(self, addr: ColoredAddr, tree: Any) -> None:
        if addr.color % self.every != 0:
            return
        p = self.dir / f"ckpt_{addr.color:08d}"
        save(p, tree, color=addr.color, step=addr.color,
             quantize=self.quantize)
        self.saved.append((addr.color, p))
        while len(self.saved) > self.keep:
            _, old = self.saved.pop(0)
            for suffix in (".npz", ".json"):
                Path(str(old) + suffix).unlink(missing_ok=True)

    @property
    def state(self) -> OwnedState:
        return self._state()

    def latest(self) -> tuple[int, Path] | None:
        return self.saved[-1] if self.saved else None

    def restore_latest(self, like: Any, mesh=None, specs=None):
        if not self.saved:
            raise FileNotFoundError("no checkpoints saved")
        color, p = self.saved[-1]
        tree, manifest = restore(p, like, mesh=mesh, specs=specs)
        self.state._tree = tree
        self.state.addr = ColoredAddr(self.state.addr.name, manifest["color"])
        return tree, manifest
