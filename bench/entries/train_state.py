"""One chip: ``repro.train.TrainState.step`` with a ``ReplicaSlot`` backup
attached, as ``repro.launch.train`` runs it.

Every step is one ownership epoch: the state is borrowed mutably, the
jitted step runs with its buffers donated, and the borrow's drop bumps the
colour and flushes the backup.  The harness's span ``SPAN_BACKUP`` is
opened and closed by hooks of its own at the front and back of the
state's epoch hooks, so it covers the flush from outside.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.trace import SPAN_BACKUP


class Runner:
    def __init__(self, model_cfg, opt_cfg, init, key, cell: dict, devices):
        from repro.train import TrainState
        params = jax.jit(init)(key)
        self.ts = TrainState(model_cfg, opt_cfg, params)
        self.slot = self.ts.replicate()
        self._open = []
        self.ts.state.on_epoch.insert(0, self._begin)
        self.ts.state.on_epoch.append(self._end)
        self.metrics = {}

    def _begin(self, *_):
        span = jax.profiler.TraceAnnotation(SPAN_BACKUP)
        span.__enter__()
        self._open.append(span)

    def _end(self, *_):
        self._open.pop().__exit__(None, None, None)

    def step(self, batch: dict) -> float:
        """Host batch in; the step's loss once it has reached the host."""
        self.metrics = self.ts.step(jax.tree.map(jnp.asarray, batch))
        return float(self.metrics["loss"])

    def grad_norm(self) -> float:
        return float(self.metrics["grad_norm"])

    def params(self):
        return self.ts.state.read()[0]

    def first_moment(self):
        return self.ts.state.read()[1]["mu"]

    def exact_numbers(self) -> dict:
        """The backup against the state it backs up, after the window:
        ``backup_mismatch`` counts the colours that differ and the
        elements whose bits differ (0 when the backup is exact)."""
        color, backup = self.slot.backup
        live = self.ts.state.read()
        diff = jax.jit(_bit_diff)(live, backup)
        return {"backup_mismatch": float(int(diff) + (color != self.ts.color))}

    def close(self) -> None:
        self.ts.state.on_epoch.clear()
        self.slot.backup = None
        del self.ts, self.slot


def _bit_diff(a, b):
    def one(x, y):
        if x.shape != y.shape or x.dtype != y.dtype:
            return jnp.asarray(np.prod(x.shape) or 1, jnp.int32)
        u = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        return jnp.sum(jax.lax.bitcast_convert_type(x, u)
                       != jax.lax.bitcast_convert_type(y, u), dtype=jnp.int32)
    return sum(jax.tree.leaves(jax.tree.map(one, a, b)))
