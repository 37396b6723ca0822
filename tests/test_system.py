"""End-to-end behaviour tests for the whole system."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs


def test_end_to_end_train_with_failure_recovery():
    """Train a reduced model, inject a failure mid-run, recover from the
    epoch backup, and still end with a lower loss than we started."""
    from repro.models import init_params
    from repro.train import OptConfig, TrainState, synthetic_batches
    cfg = configs.smoke("starcoder2_3b")
    ts = TrainState(cfg, OptConfig(lr=3e-3, warmup=2, decay_steps=60),
                    init_params(cfg, jax.random.PRNGKey(0)))
    ts.replicate()
    data = synthetic_batches(cfg.vocab, 8, 64)
    losses = []
    for step in range(14):
        losses.append(float(ts.step(jax.tree.map(jnp.asarray,
                                                 next(data)))["loss"]))
        if step == 7:
            ts.restore_from_backup()    # simulated node failure
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))


def test_end_to_end_serve_with_online_weight_update():
    """Serve while a writer bumps the weight color: replicas refresh via the
    colored cache, requests complete, zero invalidation traffic."""
    from repro.core.jaxstate import OwnedState
    from repro.models import init_params
    from repro.serve import ServeEngine
    cfg = configs.smoke("qwen3_0_6b")
    weights = OwnedState("w", init_params(cfg, jax.random.PRNGKey(0)))
    eng = ServeEngine(cfg, weights, slots=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(list(rng.integers(0, cfg.vocab, 6)), max_new=3)
            for _ in range(4)]
    steps = 0
    while eng.queue or eng.active:
        eng.step()
        steps += 1
        if steps == 3:                  # online update mid-serving
            with weights.borrow_mut() as m:
                m.set(jax.tree.map(lambda x: x, m.deref_mut()))
        assert steps < 100
    assert all(r.done for r in reqs)
    assert eng.weight_cache.refreshes == 2


def test_dsm_and_ml_stack_share_protocol_semantics():
    """The same coherence rules govern both layers: a write epoch changes
    the colored address in the DSM *and* in the JAX state store."""
    from repro.core import Cluster
    from repro.core.jaxstate import OwnedState
    cl = Cluster(2, backend="drust")
    t0 = cl.main_thread(0)
    t1 = cl.main_thread(0); t1.server = 1
    box = cl.backend.alloc(t0, 64, b"v0")
    g_seen = box.g
    cl.backend.read(t1, box)
    cl.backend.write(t1, box, b"v1")
    assert box.g != g_seen

    state = OwnedState("params", {"w": jnp.zeros(2)})
    addr_seen = state.addr
    with state.borrow_mut() as m:
        m.set({"w": jnp.ones(2)})
    assert state.addr != addr_seen


def test_lower_layers_import_nothing_from_the_launcher():
    """The training, model and distribution layers stand below
    ``repro.launch``: importing them, and a ``TrainState`` step on a mesh
    (which reads its compiled program's collectives), load none of it."""
    code = textwrap.dedent("""
        import sys
        import jax, jax.numpy as jnp
        import repro.dist, repro.models, repro.train
        from repro import configs
        from repro.models import init_params
        from repro.train import OptConfig, TrainState
        cfg = configs.smoke("qwen3_0_6b")
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ts = TrainState(cfg, OptConfig(), init_params(cfg, jax.random.key(0)),
                        mesh=mesh)
        tokens = jnp.zeros((2, 16), jnp.int32)
        ts.step({"tokens": tokens, "labels": tokens})
        print(sorted(m for m in sys.modules if m.startswith("repro.launch")))
    """)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=root,
        env=dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
