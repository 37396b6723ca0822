"""Training + serving substrate tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import BorrowError
from repro.core.jaxstate import OwnedState, StateCache
from repro.models import init_params
from repro.train import (OptConfig, TrainState, init_opt_state,
                         make_train_step, synthetic_batches)

KEY = jax.random.PRNGKey(0)


def _setup(arch="qwen3_0_6b", **opt_kw):
    cfg = configs.smoke(arch)
    params = init_params(cfg, KEY)
    opt = OptConfig(lr=3e-3, warmup=2, decay_steps=50, **opt_kw)
    return cfg, params, opt


def test_loss_decreases():
    cfg, params, opt = _setup()
    ts = TrainState(cfg, opt, params)
    data = synthetic_batches(cfg.vocab, 8, 64)
    losses = [float(ts.step(jax.tree.map(jnp.asarray, next(data)))["loss"])
              for _ in range(12)]
    assert losses[-1] < losses[0], f"no improvement: {losses}"
    assert ts.color == 12               # one epoch per step


def test_microbatch_grads_match_full_batch():
    import dataclasses
    cfg, _, opt = _setup()
    cfg = dataclasses.replace(cfg, dtype="float32")   # bf16 hides equality
    params = init_params(cfg, KEY)
    data = synthetic_batches(cfg.vocab, 8, 32)
    batch = jax.tree.map(jnp.asarray, next(data))
    s1 = make_train_step(cfg, opt, microbatches=1)
    s4 = make_train_step(cfg, opt, microbatches=4)
    o1 = init_opt_state(opt, params)
    o4 = init_opt_state(opt, params)
    p1, _, m1 = jax.jit(s1)(params, o1, batch)
    p4, _, m4 = jax.jit(s4)(params, o4, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=2e-2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-3)


def test_adafactor_runs_and_improves():
    cfg, params, opt = _setup(name="adafactor")
    ts = TrainState(cfg, opt, params)
    data = synthetic_batches(cfg.vocab, 8, 64)
    losses = [float(ts.step(jax.tree.map(jnp.asarray, next(data)))["loss"])
              for _ in range(10)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_adafactor_memory_factored():
    cfg, params, _ = _setup()
    fac = init_opt_state(OptConfig(name="adafactor"), params)
    adam = init_opt_state(OptConfig(name="adamw"), params)
    bytes_fac = sum(l.size * l.dtype.itemsize
                    for l in jax.tree.leaves(fac))
    bytes_adam = sum(l.size * l.dtype.itemsize
                     for l in jax.tree.leaves(adam))
    assert bytes_fac < bytes_adam * 0.1     # factored moments are tiny


def test_backup_promotion_restores_epoch():
    cfg, params, opt = _setup()
    ts = TrainState(cfg, opt, params)
    slot = ts.replicate()
    data = synthetic_batches(cfg.vocab, 4, 32)
    ts.step(jax.tree.map(jnp.asarray, next(data)))
    good = jax.tree.leaves(ts.params())[0].copy()
    color = ts.color
    # corrupt the live buffers OUT-OF-BAND (a crash is not a write epoch —
    # a protocol-level write would legitimately become the newest backup)
    p, o = ts.state._tree
    ts.state._tree = (jax.tree.map(jnp.zeros_like, p), o)
    ts.restore_from_backup()
    restored = jax.tree.leaves(ts.params())[0]
    np.testing.assert_array_equal(np.asarray(restored, np.float32),
                                  np.asarray(good, np.float32))


def test_replica_slot_drops_old_backup_and_promotes_newest(monkeypatch):
    """Each flush keeps exactly that epoch's arrays, with no copy, and
    releases the previous snapshot; promotion restores the newest epoch
    and colour."""
    import weakref
    from repro.core.jaxstate import ReplicaSlot
    state = OwnedState("t", {"w": jnp.zeros(4)})
    slot = ReplicaSlot(state)
    assert state.holders == 1
    copies = []
    monkeypatch.setattr(jnp, "copy", lambda x: copies.append(x))
    previous = None
    for v in (1.0, 2.0, 3.0):
        tree = {"w": jnp.full(4, v)}
        state.write(tree)
        assert slot.backup[0] == state.color
        assert slot.backup[1] is tree and slot.backup[1]["w"] is tree["w"]
        if previous is not None:
            assert previous() is None           # the old snapshot released
        previous = weakref.ref(tree["w"])
        del tree
    assert copies == []
    assert slot.flushes == 3 and slot.backup[0] == 3
    state._tree = {"w": jnp.zeros(4)}       # crash: live buffers lost
    slot.promote()
    assert state.color == 3
    np.testing.assert_array_equal(np.asarray(state.read()["w"]),
                                  np.full(4, 3.0))


def test_slot_snapshot_outlives_the_next_step():
    """With a slot attached the step does not donate: every leaf of the
    slot's snapshot stays alive and bit-equal to its epoch's state while
    the next step runs, up to the flush that replaces it."""
    cfg, params, opt = _setup()
    ts = TrainState(cfg, opt, params)
    slot = ts.replicate()
    data = synthetic_batches(cfg.vocab, 4, 32)
    seen = {}
    checked = []

    def before_flush(addr, tree):             # runs ahead of the slot's
        color, held = slot.backup
        assert color == addr.color - 1
        leaves = jax.tree.leaves(held)
        assert not any(x.is_deleted() for x in leaves)
        for x, want in zip(leaves, seen[color]):
            np.testing.assert_array_equal(np.asarray(x), want)
        assert not any(a is b for a, b in zip(leaves, jax.tree.leaves(tree)))
        checked.append(color)

    for step in range(4):
        ts.step(jax.tree.map(jnp.asarray, next(data)))
        held = jax.tree.leaves(slot.backup[1])
        live = jax.tree.leaves(ts.state.read())
        assert all(a is b for a, b in zip(held, live))    # the epoch's own
        # copies: a zero-copy view would keep the buffer from donation
        seen[ts.color] = [np.array(x, copy=True) for x in live]
        if step == 0:
            ts.state.on_epoch.insert(0, before_flush)
    assert checked == [1, 2, 3]


def test_train_state_without_slot_donates():
    """With no holder besides the owner the step donates the state: the
    previous epoch's leaves are deleted once the next step has run."""
    cfg, params, opt = _setup()
    ts = TrainState(cfg, opt, params)
    data = synthetic_batches(cfg.vocab, 4, 32)
    for _ in range(2):
        old = jax.tree.leaves(ts.state.read())
        ts.step(jax.tree.map(jnp.asarray, next(data)))
        assert all(x.is_deleted() for x in old)
    assert ts.state.holders == 0


def test_dropped_train_state_frees_without_cyclic_gc(tmp_path):
    """The backup slot and the checkpoint manager hold the state weakly:
    dropping a TrainState releases its buffers (state and backup) at once,
    not at some later cyclic garbage collection."""
    import gc
    import weakref
    from repro.checkpoint import CheckpointManager
    cfg, params, opt = _setup()
    data = synthetic_batches(cfg.vocab, 4, 32)
    gc.disable()
    try:
        ts = TrainState(cfg, opt, params)
        slot = ts.replicate()
        mgr = CheckpointManager(tmp_path, ts.state)
        ts.step(jax.tree.map(jnp.asarray, next(data)))
        assert slot.backup is not None and mgr.latest() is not None
        state, holder = weakref.ref(ts.state), weakref.ref(slot)
        del ts, slot, mgr
        assert state() is None and holder() is None    # backup gone too
    finally:
        gc.enable()


def test_owned_state_borrow_rules():
    s = OwnedState("t", {"w": jnp.zeros(4)})
    r = s.borrow()
    with pytest.raises(BorrowError):
        s.borrow_mut()
    r.drop()
    with s.borrow_mut() as m:
        m.set({"w": jnp.ones(4)})
        with pytest.raises(BorrowError):
            s.read()
    assert s.color == 1


def test_state_cache_zero_comm_on_color_hit():
    s = OwnedState("t", {"w": jnp.zeros(8)})
    cache = StateCache()
    cache.fetch(s); cache.fetch(s); cache.fetch(s)
    assert cache.refreshes == 1 and cache.hits == 2
    with s.borrow_mut() as m:
        m.set({"w": jnp.ones(8)})
    cache.fetch(s)
    assert cache.refreshes == 2         # refetch only after the color bump


def test_gradient_compression_error_feedback():
    from repro.dist.compression import quantize_int8, dequantize_int8
    x = jnp.asarray(np.random.default_rng(0).standard_normal(1024) * 0.01)
    q, scale = quantize_int8(x)
    err1 = x - dequantize_int8(q, scale)
    assert float(jnp.abs(err1).max()) <= float(scale) / 2 + 1e-9
    # error feedback: quantizing (residual + next grad) keeps bias bounded
    total = dequantize_int8(q, scale)
    q2, s2 = quantize_int8(err1 + x)
    total = total + dequantize_int8(q2, s2)
    np.testing.assert_allclose(np.asarray(total), np.asarray(2 * x),
                               atol=float(s2))


def test_serve_engine_drains_and_shares_prefixes():
    from repro.serve import ServeEngine
    cfg = configs.smoke("qwen3_0_6b")
    params = init_params(cfg, KEY)
    weights = OwnedState("w", params)
    eng = ServeEngine(cfg, weights, slots=2, max_len=128)
    rng = np.random.default_rng(0)
    prefix = list(rng.integers(0, cfg.vocab, cfg.attn_chunk))
    reqs = [eng.submit(prefix + [int(i)], max_new=4) for i in range(4)]
    steps = 0
    while eng.queue or eng.active:
        eng.step()
        steps += 1
        assert steps < 200
    assert all(r.done and len(r.generated) == 4 for r in reqs)
    st = eng.stats()
    assert st["kv"]["hits"] >= 3        # prefix page reused across requests
    assert st["weight_refreshes"] == 1  # weights never changed: one fetch


def test_kvcache_protocol_semantics():
    from repro.serve.kvcache import PagedKVCache
    kv = PagedKVCache(page_size=8, capacity_pages=8)
    p = kv.alloc_page((1, 2, 3))
    assert not p.full                   # capacity 8, 3 tokens
    c0 = p.addr.color
    kv.append(p, 4)
    assert p.addr.color == c0 + 1       # append bumps the color
    kv.seal(p)
    q = kv.lookup_prefix((1, 2, 3, 4))
    assert q is p
    kv.borrow(q); kv.borrow(q)
    with pytest.raises(BorrowError):
        kv.append(q, 5)                 # shared page: copy-on-write required
    forked = kv.fork(q)
    kv.append(forked, 5)
    kv.drop(q); kv.drop(q)
    # eviction only reclaims refcount-0 pages
    for i in range(6):
        kv.seal(kv.alloc_page((9, i)))
    assert kv.evict(10) > 0
