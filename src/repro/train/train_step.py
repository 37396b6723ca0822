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
from repro.dist.sharding import train_shardings
from repro.models import loss_fn
from repro.models.config import ModelConfig
from .data import shard_batch
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


def jit_train_step(fn, shardings=None, donate: bool = True):
    """``fn`` jitted, donating ``(params, opt_state)`` when ``donate``.
    With ``shardings`` (``dist.sharding.train_shardings``: params,
    opt_state, batch), the inputs are placed by them and the new state
    lands where the old one was; a batch sharding of None leaves the
    batch where its argument is."""
    donated = (0, 1) if donate else ()
    if shardings is None:
        return jax.jit(fn, donate_argnums=donated)
    p, o, b = shardings
    return jax.jit(fn, in_shardings=(p, o, b), out_shardings=(p, o, None),
                   donate_argnums=donated)


def place_train_state(mesh, opt: OptConfig, params):
    """``params`` and a new optimizer state for them on ``mesh`` by
    ``train_shardings``; the moments are made where they live, never whole
    on one chip.  Returns (params, opt_state, shardings)."""
    init = functools.partial(init_opt_state, opt)
    p, o, _ = train_shardings(mesh, params, jax.eval_shape(init, params))
    params = jax.device_put(params, p)
    return params, jax.jit(init, out_shardings=o)(params), (p, o, None)


class _OwnedStep:
    """``fn`` jitted twice over the same shardings: one variant donates
    ``(params, opt_state)``, run while ``state`` has no holder besides its
    owner; the other keeps them.  Neither compiles before its first call.
    It holds the state, not the ``TrainState``: no reference cycle."""

    def __init__(self, fn, state: OwnedState, shardings=None):
        self.state = state
        self.donating = jit_train_step(fn, shardings, donate=True)
        self.keeping = jit_train_step(fn, shardings, donate=False)

    def jitted(self):
        """The variant the next call runs."""
        return self.keeping if self.state.holders else self.donating

    def __call__(self, params, opt_state, batch):
        return self.jitted()(params, opt_state, batch)


class TrainState:
    """Host-side ownership wrapper around (params, opt_state).

    Each ``step`` is one write epoch: mutable borrow -> update (the call of
    the jitted step is a ``train.dispatch`` profiler span) -> color bump on
    drop.  The step donates the state's buffers only while the owner is
    their sole holder; ``replicate()`` attaches a §4.2.3 backup slot, which
    keeps each epoch's arrays as they are, so from then on the step writes
    fresh buffers instead.

    With a ``mesh``, the state is placed on it at construction
    (``place_train_state``), both variants of the step keep it there, and
    a host batch is put on ``batch_specs``.  While the profiler records,
    the span carries stats ``donated`` (1 when the step donated the state,
    else 0) and ``chips`` (the devices the state spans), and on a mesh
    ``collective_bytes``: the step's per-chip wire bytes by
    ``launch.dryrun.collective_bytes``, worked out from each variant's
    compiled program at its first call.
    """

    def __init__(self, cfg: ModelConfig, opt: OptConfig, params,
                 mesh=None, microbatches: int = 1, jit: bool = True):
        self.cfg, self.opt, self.mesh = cfg, opt, mesh
        self.microbatches = microbatches
        if mesh is None:
            shardings = None
            opt_state = init_opt_state(opt, params)
        else:
            params, opt_state, shardings = place_train_state(mesh, opt,
                                                             params)
        self.state = OwnedState("train_state", (params, opt_state))
        fn = make_train_step(cfg, opt, mesh=mesh, microbatches=microbatches)
        self._jit = jit
        self._owned = _OwnedStep(fn, self.state, shardings) if jit else None
        self._step = self._owned if jit else fn
        self._wire: dict[bool, int] = {}
        self.replicas: list[ReplicaSlot] = []
        self.metrics: dict[str, Any] = {}

    def replicate(self) -> ReplicaSlot:
        slot = ReplicaSlot(self.state)
        self.replicas.append(slot)
        return slot

    @property
    def color(self) -> int:
        return self.state.color

    @property
    def chips(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    def _wire_bytes(self, params, opt_state, batch) -> int:
        """The collectives' per-chip wire bytes of the variant about to
        run, from its compiled program, which its call then reuses."""
        keeping = bool(self.state.holders)
        if keeping not in self._wire:
            from repro.launch.dryrun import collective_bytes, layer_trips
            text = self._owned.jitted().lower(params, opt_state,
                                              batch).compile().as_text()
            trips = layer_trips(self.cfg, self.microbatches)
            self._wire[keeping] = sum(collective_bytes(
                text, while_mult=trips).values())
        return self._wire[keeping]

    def step(self, batch):
        sharded = self.mesh is not None and self._jit
        if self.mesh is not None:
            batch = shard_batch(self.mesh, batch)
        with self.state.borrow_mut() as ref:
            params, opt_state = ref.deref_mut()
            wire = self._wire_bytes(params, opt_state, batch) \
                if sharded else None
            span = jax.profiler.TraceAnnotation
            stats = {}
            if span.is_enabled():
                stats = {"donated": int(self._jit and not self.state.holders),
                         "chips": self.chips}
                if sharded:
                    stats["collective_bytes"] = wire
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
