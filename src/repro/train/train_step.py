"""Train step factory: loss + grad + optimizer update, with microbatch
gradient accumulation and the ownership-epoch hook.

The returned function is pure (pjit-friendly); the TrainState wrapper puts
params/opt_state under ``OwnedState`` so each step is a mutable-borrow epoch:
the color bump at drop is what serving replicas / checkpointers key their
zero-communication refresh on (DESIGN §2.2).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.jaxstate import OwnedState, ReplicaSlot
from repro.dist.collectives import collective_bytes, layer_trips
from repro.dist.sharding import train_shardings
from repro.models import loss_fn
from repro.models.config import ModelConfig
from repro.models.transformer import RECOMPUTE, SAVE_PROJECTIONS, saved_bytes
from .data import shard_batch
from .optimizer import OptConfig, apply_updates, init_opt_state

HBM_FALLBACK = 16 * 2**30         # one v5e chip, where the device gives none


def make_train_step(cfg: ModelConfig, opt: OptConfig, mesh=None,
                    microbatches: int = 1, remat_policy=RECOMPUTE):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    With microbatches > 1, the global batch is split along axis 0 and
    gradients accumulate in f32 across a lax.scan (sequential — the standard
    memory/throughput trade; see EXPERIMENTS §Perf for where it pays off).
    ``remat_policy`` is the layer scan's checkpoint policy under
    ``cfg.remat``: ``RECOMPUTE`` runs each layer's forward again in the
    backward; ``SAVE_PROJECTIONS`` is for a caller that has found its
    program fits the chip (``compile_fitting``).
    """

    def lf(p, b):
        return loss_fn(cfg, p, b, mesh=mesh, remat_policy=remat_policy)

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


def step_saved_bytes(cfg: ModelConfig, batch, mesh=None,
                     microbatches: int = 1) -> int:
    """What the step's layer scan keeps a chip for the backward under
    ``SAVE_PROJECTIONS`` (``transformer.saved_bytes``), for the residual
    stream one microbatch of ``batch`` makes: its rows, and its tokens
    after any prefix embeddings."""
    B, T = batch["tokens"].shape
    if cfg.prefix_len and "prefix_embeds" in batch:
        T += batch["prefix_embeds"].shape[1]
    return saved_bytes(cfg, (B // microbatches, T), mesh)


def fits(compiled, mesh=None, limit: int | None = None) -> bool:
    """Whether ``compiled`` runs beside what its chips hold now: a chip's
    bytes in use (the step's arguments among them) + the outputs it
    writes beside its donated arguments + its temporaries, within the
    chip's ``bytes_limit`` (or ``limit``).  A device that gives no memory
    stats (the CPU, a described topology) counts as a v5e
    (``HBM_FALLBACK``) that holds just the program's arguments."""
    mem = compiled.memory_analysis()
    devices = jax.devices()[:1] if mesh is None else list(mesh.devices.flat)
    try:
        stats = [d.memory_stats() for d in devices]
    except jax.errors.JaxRuntimeError:        # no device behind a description
        stats = [None]
    in_use, device_limit = mem.argument_size_in_bytes, HBM_FALLBACK
    if all(s and {"bytes_in_use", "bytes_limit"} <= s.keys() for s in stats):
        in_use = max(s["bytes_in_use"] for s in stats)
        device_limit = min(s["bytes_limit"] for s in stats)
    return in_use + mem.output_size_in_bytes - mem.alias_size_in_bytes \
        + mem.temp_size_in_bytes <= (limit or device_limit)


class Fitted(NamedTuple):
    """The remat policy a step was compiled with, the bytes a chip keeps
    under it for the backward (0 when it recomputes), the jitted step
    and its compiled program."""
    policy: Any
    saved: int
    jitted: Any
    compiled: Any


def compile_fitting(jit_for, args, cfg: ModelConfig, mesh=None,
                    microbatches: int = 1,
                    limit: int | None = None) -> Fitted:
    """The step for ``args`` (params, opt_state, batch; arrays or
    abstract), its layer scan saving the projection outputs where that
    program ``fits`` its chips, else recomputing each layer.
    ``jit_for(policy)`` is the step jitted under a remat policy, with the
    donation it runs with: the compiled program's outputs and
    temporaries, not an estimate of them, decide.  The compiler's own
    refusal (HBM exhausted) counts as not fitting.  A step whose layer
    scan saves nothing (``step_saved_bytes`` 0) compiles once, recomputing;
    one that fits compiles once, saving.  ``limit`` stands in for the
    chips' ``bytes_limit``."""
    saved = step_saved_bytes(cfg, args[2], mesh, microbatches)
    if saved:
        jitted = jit_for(SAVE_PROJECTIONS)
        try:
            compiled = jitted.lower(*args).compile()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
        else:
            if fits(compiled, mesh, limit):
                return Fitted(SAVE_PROJECTIONS, saved, jitted, compiled)
    jitted = jit_for(RECOMPUTE)
    return Fitted(RECOMPUTE, 0, jitted, jitted.lower(*args).compile())


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


class _Variant(NamedTuple):
    jitted: Any
    saved: int                    # ``remat_saved_bytes``
    wire: dict | None             # ``collective_bytes`` by kind, on a mesh


class _OwnedStep:
    """The step over the same shardings in two variants: one donates
    ``(params, opt_state)``, run while ``state`` has no holder besides its
    owner; the other keeps them.  A variant compiles at its first call
    for a batch shape, its remat policy chosen then by what that program
    needs of the chips (``compile_fitting``), and not before.  It holds
    the state, not the ``TrainState``: no reference cycle."""

    def __init__(self, make, state: OwnedState, cfg: ModelConfig,
                 shardings=None, mesh=None, microbatches: int = 1):
        self.make, self.state, self.cfg = make, state, cfg
        self.shardings, self.mesh = shardings, mesh
        self.microbatches = microbatches
        self.variants: dict[Any, _Variant] = {}
        self.latest: dict[bool, Any] = {}

    def variant(self, params, opt_state, batch) -> _Variant:
        """The variant the next call runs, compiled at its first call;
        on a mesh with its collectives' per-chip wire bytes by
        ``dist.collectives.collective_bytes``."""
        donate = not self.state.holders
        leaves, tree = jax.tree.flatten(batch)
        key = (donate, tree, tuple((x.shape, x.dtype) for x in leaves))
        if key not in self.variants:
            fitted = compile_fitting(
                lambda policy: jit_train_step(self.make(policy),
                                              self.shardings, donate),
                (params, opt_state, batch), self.cfg, self.mesh,
                self.microbatches)
            wire = None
            if self.mesh is not None:
                wire = collective_bytes(
                    fitted.compiled.as_text(),
                    while_mult=layer_trips(self.cfg, self.microbatches))
            self.variants[key] = _Variant(fitted.jitted, fitted.saved, wire)
            self.latest[donate] = fitted.jitted
        return self.variants[key]

    @property
    def donating(self):
        """The donating variant as its latest first call compiled it."""
        return self.latest[True]

    @property
    def keeping(self):
        """The keeping variant as its latest first call compiled it."""
        return self.latest[False]

    def __call__(self, params, opt_state, batch):
        return self.variant(params, opt_state, batch).jitted(
            params, opt_state, batch)


class TrainState:
    """Host-side ownership wrapper around (params, opt_state).

    Each ``step`` is one write epoch: mutable borrow -> update (the call of
    the jitted step is a ``train.dispatch`` profiler span) -> color bump on
    drop.  The step donates the state's buffers only while the owner is
    their sole holder; ``replicate()`` attaches a §4.2.3 backup slot, which
    keeps each epoch's arrays as they are, so from then on the step writes
    fresh buffers instead.  Its layer scan keeps the projection outputs
    for the backward where that program fits the chips, else recomputes
    each layer (``compile_fitting``); without ``jit`` it recomputes.

    With a ``mesh``, the state is placed on it at construction
    (``place_train_state``), both variants of the step keep it there, and
    a host batch is put on ``batch_specs``.  While the profiler records,
    the span carries stats ``donated`` (1 when the step donated the state,
    else 0), ``chips`` (the devices the state spans) and
    ``remat_saved_bytes`` (the bytes a chip keeps for the backward, 0 when
    the layer scan recomputes each layer), and on a mesh
    ``collective_bytes`` and ``reduce_scatter_bytes``: the step's per-chip
    wire bytes, all kinds and reduce-scatters alone, all worked out
    from each variant's compiled program at its first call.
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
        make = functools.partial(make_train_step, cfg, opt, mesh,
                                 microbatches)
        self._jit = jit
        self._owned = _OwnedStep(make, self.state, cfg, shardings, mesh,
                                 microbatches) if jit else None
        self._step = self._owned if jit else make()
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

    def step(self, batch):
        if self.mesh is not None:
            batch = shard_batch(self.mesh, batch)
        with self.state.borrow_mut() as ref:
            params, opt_state = ref.deref_mut()
            variant = self._owned.variant(params, opt_state, batch) \
                if self._jit else _Variant(self._step, 0, None)
            span = jax.profiler.TraceAnnotation
            stats = {}
            if span.is_enabled():
                stats = {"donated": int(self._jit and not self.state.holders),
                         "chips": self.chips,
                         "remat_saved_bytes": variant.saved}
                if variant.wire is not None:
                    stats["collective_bytes"] = sum(variant.wire.values())
                    stats["reduce_scatter_bytes"] = \
                        variant.wire["reduce-scatter"]
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
