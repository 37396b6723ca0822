"""The program's own profiler spans and named scopes.

A train step under the profiler writes ``train.dispatch`` (the call of the
jitted step), ``ownership.epoch`` (the colour bump and the epoch hooks)
and, inside it, ``replica.flush`` (the backup snapshot) once per step.
The dispatch says whether the step donated the state (stat ``donated``),
over how many devices it lies (``chips``: 1 here, with no mesh) and
what its layer scan keeps for the backward (``remat_saved_bytes``, the
count of ``step_saved_bytes`` where the step saves),
the flush the bytes of the snapshot it keeps (``held``) and the bytes it
copies (``nbytes``, 0); the lowered step carries the ``attention``,
``mlp``, ``lm_head_loss`` and ``optimizer`` scopes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import span_stats
from bench import trace as tr
from repro import configs
from repro.core.jaxstate import OwnedState, ReplicaSlot
from repro.models import init_params
from repro.train import OptConfig, TrainState
from repro.train.optimizer import init_opt_state
from repro.train.train_step import make_train_step, step_saved_bytes

SPANS = ("train.dispatch", "ownership.epoch", "replica.flush")
STEPS = 3


def _saved(ts, batch) -> int:
    """What the step keeps for its backward when it saves, as it does at
    this size."""
    saved = step_saved_bytes(ts.cfg, batch)
    assert saved > 0
    return saved


def _setup():
    cfg = configs.smoke("qwen3-0.6b")
    rng = np.random.default_rng(0)
    batch = {k: jnp.asarray(rng.integers(0, cfg.vocab, (2, 16),
                                         dtype=np.int32))
             for k in ("tokens", "labels")}
    return cfg, OptConfig(lr=1e-3, warmup=5), batch


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three steps of the smoke configuration with a backup slot, under
    the profiler; the trace, the stats of the spans and the state."""
    cfg, opt, batch = _setup()
    ts = TrainState(cfg, opt, init_params(cfg, jax.random.PRNGKey(0)))
    slot = ts.replicate()
    float(ts.step(batch)["loss"])                 # compile outside the trace
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        for _ in range(STEPS):
            float(ts.step(batch)["loss"])
    finally:
        jax.profiler.stop_trace()
    [path] = out.glob("**/*.xplane.pb")
    return tr.load(path), span_stats.read(path, SPANS), ts, slot


def test_each_span_once_per_step_and_nested(traced):
    trace, _, _, _ = traced
    dispatch, epoch, flush = (trace.spans(n) for n in SPANS)
    assert len(dispatch) == len(epoch) == len(flush) == STEPS
    for d, ep, fl in zip(dispatch, epoch, flush):
        assert d[1] <= ep[0]                          # dispatch, then epoch
        assert ep[0] <= fl[0] and fl[1] <= ep[1]      # flush inside epoch
    assert all(d[0] > ep[1] for d, ep in zip(dispatch[1:], epoch))


def test_span_stats_match_the_state(traced):
    """The slot keeps the state without a copy, and the step, whose state
    the slot holds, does not donate it."""
    _, stats, ts, slot = traced
    held = sum(x.nbytes for x in jax.tree.leaves(ts.state.read()))
    assert [st for _, _, st in stats["replica.flush"]] == [
        {"nbytes": 0, "held": held}] * STEPS
    assert [st for _, _, st in stats["ownership.epoch"]] == [{}] * STEPS
    assert [st for _, _, st in stats["train.dispatch"]] == [
        {"donated": 0, "chips": 1,
         "remat_saved_bytes": _saved(ts, _setup()[2])}] * STEPS
    assert slot.flushes == STEPS + 1


def test_dispatch_donates_without_a_slot(tmp_path):
    """With no slot the state has no other holder: every traced dispatch
    donates it, and no flush happens."""
    cfg, opt, batch = _setup()
    ts = TrainState(cfg, opt, init_params(cfg, jax.random.PRNGKey(0)))
    float(ts.step(batch)["loss"])                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(STEPS):
            float(ts.step(batch)["loss"])
    finally:
        jax.profiler.stop_trace()
    [path] = tmp_path.glob("**/*.xplane.pb")
    stats = span_stats.read(path, SPANS)
    assert [st for _, _, st in stats["train.dispatch"]] == [
        {"donated": 1, "chips": 1,
         "remat_saved_bytes": _saved(ts, batch)}] * STEPS
    assert len(stats["ownership.epoch"]) == STEPS
    assert stats["replica.flush"] == []


def test_flush_bytes_counted_anew_while_tracing(tmp_path):
    """Each traced flush counts the bytes of the tree it keeps, also where
    a leaf changes dtype or shape and the structure stays, and copies
    none."""
    state = OwnedState("t", {"w": jnp.zeros(4)})
    slot = ReplicaSlot(state)
    state.write({"w": jnp.zeros(4)})                   # profiler off
    jax.profiler.start_trace(str(tmp_path))
    try:
        state.write({"w": jnp.zeros(4)})
        state.write({"w": jnp.zeros(4, jnp.bfloat16)})
        state.write({"w": jnp.zeros(6, jnp.bfloat16)})
    finally:
        jax.profiler.stop_trace()
    [path] = tmp_path.glob("**/*.xplane.pb")
    flushes = span_stats.read(path, ["replica.flush"])["replica.flush"]
    assert [st for _, _, st in flushes] == [
        {"nbytes": 0, "held": 16}, {"nbytes": 0, "held": 8},
        {"nbytes": 0, "held": 12}]
    assert slot.flushes == 4


def test_lowered_step_carries_the_named_scopes():
    cfg, opt, batch = _setup()
    params = init_params(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, opt))
    text = step.lower(params, init_opt_state(opt, params),
                      batch).as_text(debug_info=True)
    # scope names are components of the ops' name paths, e.g.
    # "jit(train_step)/optimizer/add" or "jvp(lm_head_loss)/..."
    parts = {part for path in re.findall(r'loc\("([^"]*/[^"]*)"', text)
             for part in re.split(r"[/()]", path)}
    assert {"attention", "mlp", "lm_head_loss", "optimizer"} <= parts
