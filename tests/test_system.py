"""End-to-end behaviour tests for the whole system."""

import subprocess
import sys

import pytest

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


def test_dryrun_smoke_subprocess():
    """The dry-run harness itself: 8 host devices, 2x4 mesh, reduced arch."""
    import os
    env = dict(os.environ,
               DRYRUN_XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen3-0.6b",
         "--shape", "train_4k", "--mesh", "2x4", "--smoke",
         "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "ALL 1 cells OK" in out.stdout


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %ag = bf16[16,256,4096]{2,1,0} all-gather(%x), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}, metadata={op_name="jit(f)/while/body/ag"}
  %ar = f32[1024]{0} all-reduce(%y), channel_id=2, replica_groups=[4,2]<=[8], to_apply=%add, metadata={op_name="jit(f)/ar"}
  %rs = f32[64,64]{1,0} reduce-scatter(%z), channel_id=3, replica_groups=[2,4]<=[8], dimensions={0}
"""
    out = collective_bytes(hlo, while_mult=10)
    ag = 16 * 256 * 4096 * 2 * (3 / 4) * 10        # in while: x10
    ar = 1024 * 4 * 2 * (1 / 2)
    rs = 64 * 64 * 4 * 3
    assert abs(out["all-gather"] - ag) / ag < 1e-6
    assert abs(out["all-reduce"] - ar) / ar < 1e-6
    assert abs(out["reduce-scatter"] - rs) / rs < 1e-6
