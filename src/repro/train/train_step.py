"""Train step factory: loss + grad + optimizer update, with microbatch
gradient accumulation and the ownership-epoch hook.

The returned function is pure (pjit-friendly); the TrainState wrapper puts
params/opt_state under ``OwnedState`` so each step is a mutable-borrow epoch:
the color bump at drop is what serving replicas / checkpointers key their
zero-communication refresh on (DESIGN §2.2).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.jaxstate import OwnedState, ReplicaSlot
from repro.models import loss_fn
from repro.models.config import ModelConfig
from .optimizer import OptConfig, apply_updates, init_opt_state


def make_train_step(cfg: ModelConfig, opt: OptConfig, mesh=None,
                    microbatches: int = 1):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    With microbatches > 1, the global batch is split along axis 0 and
    gradients accumulate in f32 across a lax.scan (sequential — the standard
    memory/throughput trade; see EXPERIMENTS §Perf for where it pays off).
    """

    def lf(p, b):
        return loss_fn(cfg, p, b, mesh=mesh)

    def grads_of(params, batch):
        if microbatches <= 1:
            return jax.value_and_grad(lf)(params, batch)

        def micro(carry, mb):
            loss_acc, g_acc = carry
            loss, g = jax.value_and_grad(lf)(params, mb)
            g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                 g_acc, g)
            return (loss_acc + loss, g_acc), None

        split = jax.tree.map(
            lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                + x.shape[1:]), batch)
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, g), _ = jax.lax.scan(micro, (jnp.zeros(()), g0), split)
        inv = 1.0 / microbatches
        return loss * inv, jax.tree.map(lambda x: x * inv, g)

    def train_step(params, opt_state, batch):
        loss, grads = grads_of(params, batch)
        new_params, new_opt, metrics = apply_updates(opt, params, grads,
                                                     opt_state)
        metrics = dict(metrics, loss=loss)
        return new_params, new_opt, metrics

    return train_step


def _owned_step(fn, state: OwnedState):
    """``fn`` jitted, donating ``(params, opt_state)`` only while ``state``
    has no holder besides its owner, else without donation.  Neither
    variant compiles before its first call.  The closure holds the state,
    not the ``TrainState``: no reference cycle."""
    donating = jax.jit(fn, donate_argnums=(0, 1))
    keeping = jax.jit(fn)

    def step(params, opt_state, batch):
        return (keeping if state.holders else donating)(params, opt_state,
                                                        batch)
    return step


class TrainState:
    """Host-side ownership wrapper around (params, opt_state).

    Each ``step`` is one write epoch: mutable borrow -> update (the call of
    the jitted step is a ``train.dispatch`` profiler span) -> color bump on
    drop.  The step donates the state's buffers only while the owner is
    their sole holder; ``replicate()`` attaches a §4.2.3 backup slot, which
    keeps each epoch's arrays as they are, so from then on the step writes
    fresh buffers instead.  While the profiler records, the span carries
    stat ``donated``: 1 when the step donated the state, else 0.
    """

    def __init__(self, cfg: ModelConfig, opt: OptConfig, params,
                 mesh=None, microbatches: int = 1, jit: bool = True):
        self.cfg, self.opt = cfg, opt
        opt_state = init_opt_state(opt, params)
        self.state = OwnedState("train_state", (params, opt_state))
        fn = make_train_step(cfg, opt, mesh=mesh, microbatches=microbatches)
        self._jit = jit
        self._step = _owned_step(fn, self.state) if jit else fn
        self.replicas: list[ReplicaSlot] = []
        self.metrics: dict[str, Any] = {}

    def replicate(self) -> ReplicaSlot:
        slot = ReplicaSlot(self.state)
        self.replicas.append(slot)
        return slot

    @property
    def color(self) -> int:
        return self.state.color

    def step(self, batch):
        with self.state.borrow_mut() as ref:
            params, opt_state = ref.deref_mut()
            span = jax.profiler.TraceAnnotation
            stats = ({"donated": int(self._jit and not self.state.holders)}
                     if span.is_enabled() else {})
            with span("train.dispatch", **stats):
                params, opt_state, metrics = self._step(params, opt_state,
                                                        batch)
            ref.set((params, opt_state))
        self.metrics = metrics
        return metrics

    def params(self):
        return self.state.read()[0]

    def restore_from_backup(self):
        """Failure path: promote the newest backup (checkpoint/restart)."""
        if not self.replicas:
            raise RuntimeError("no replica slot attached")
        self.replicas[-1].promote()
        return self.state.color
