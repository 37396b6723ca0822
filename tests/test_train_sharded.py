"""``TrainState`` on a (data 2, model 2) mesh of four host devices, in a
child process (the device count is fixed before JAX starts): it places
the state over the four, keeps it there through both variants of its
step, trains as the one-device ``TrainState`` and the plain float32
reference do on the same seeded weights, keeps the ownership epoch
(colour, backup slot, promotion), and puts ``chips``,
``remat_saved_bytes``, ``collective_bytes`` and ``reduce_scatter_bytes``
on ``train.dispatch`` only while the profiler records."""

import json
import math
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent

CHILD = textwrap.dedent('''
    import json, pathlib, sys, tempfile
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import span_stats
    from bench.reference import qwen3 as ref
    from bench.tests.tiny import TINY_MODEL
    from repro.dist.collectives import collective_bytes, layer_trips
    from repro.launch.mesh import make_mesh
    from repro.models.config import ModelConfig
    from repro.train import OptConfig, TrainState, shard_batch
    from repro.train.train_step import step_saved_bytes

    CFG = {"name": "tiny", "rope_theta": 1e6, "rms_norm_eps": 1e-6,
           "tie_word_embeddings": True, "torch_dtype": "float32",
           **TINY_MODEL}
    OPT = {"lr": 1e-3, "warmup": 5, "decay_steps": 100, "b1": 0.9,
           "b2": 0.95, "eps": 1e-8, "weight_decay": 0.01, "clip_norm": 1.0,
           "min_lr_frac": 0.1, "moment_dtype": "float32"}
    KEY = jax.random.key(3)
    mcfg = ModelConfig(**ref.program_fields(CFG))
    opt = OptConfig(name="adamw", **OPT)
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(3):
        t = rng.integers(0, CFG["vocab_size"], (4, 17), dtype=np.int32)
        batches.append({"tokens": t[:, :-1], "labels": t[:, 1:]})
    mesh = make_mesh((2, 2), ("data", "model"))

    def weights():
        # the reference's own weights: bfloat16 values held in float32
        return jax.tree.map(lambda x: x.astype(jnp.float32),
                            ref.init_weights(CFG, KEY))

    def shardings(tree):
        return [str(x.sharding.spec) if hasattr(x.sharding, "spec")
                else None for x in jax.tree.leaves(tree)]

    def train(ts):
        """Three steps; each loss, every leaf's gradient norm at step 1
        (from the first moment, as the benchmark reads it) and the
        parameters' shardings after each step."""
        losses, after = [], []
        for i, b in enumerate(batches):
            m = ts.step(b if ts.mesh is not None
                        else jax.tree.map(jnp.asarray, b))
            losses.append(float(m["loss"]))
            if i == 0:
                scale = max(1.0, float(m["grad_norm"])) / (1 - OPT["b1"])
                mu = ts.state.read()[1]["mu"]
                grad = [float(n) * scale for n in ref.leaf_norms(mu)]
            after.append(shardings(ts.state.read()))
        return losses, grad, after

    out = {}
    with jax.default_matmul_precision("highest"):
        one = TrainState(mcfg, opt, weights())
        out["one"] = train(one)[:2]
        ts = TrainState(mcfg, opt, weights(), mesh=mesh)
        placed = jax.tree.leaves(ts.state.read())
        out["placed_devices"] = sorted(
            {len(x.sharding.device_set) for x in placed})
        out["placed"] = shardings(ts.state.read())
        big = [x for x in jax.tree.leaves(ts.params()) if x.size >= 1024]
        out["share_on_device0"] = sum(
            s.data.nbytes for x in big for s in x.addressable_shards
            if s.device == jax.devices()[0]) / sum(x.nbytes for x in big)
        losses, grad, donating = train(ts)
        out["sharded"] = [losses, grad]
        out["donating_shardings"] = donating
        out["color"] = ts.color
        r = ref.train_readings(CFG, OPT, KEY, batches)
        out["reference"] = [r["loss"], list(r["grad"].values())]

        # the donating step consumed the state it was given
        before = jax.tree.leaves(ts.state.read())[0]
        ts.step(batches[0])
        out["donated_old_deleted"] = before.is_deleted()
        slot = ts.replicate()
        before = jax.tree.leaves(ts.state.read())[0]
        keeping = []
        for b in batches:
            ts.step(b)
            keeping.append(shardings(ts.state.read()))
        out["keeping_shardings"] = keeping
        out["kept_old_alive"] = not before.is_deleted()
        color, backup = slot.backup
        out["backup_color"] = color
        out["color_before_restore"] = ts.color
        out["restored_color"] = ts.restore_from_backup()
        out["restored_is_backup"] = all(
            a is b for a, b in zip(jax.tree.leaves(ts.state.read()),
                                   jax.tree.leaves(backup)))
        out["restored_shardings"] = shardings(ts.state.read())
        out["step_after_restore"] = float(ts.step(batches[1])["loss"])

        # stats on train.dispatch: none while the profiler is off
        seen = []
        real = jax.profiler.TraceAnnotation

        class Recorder(real):
            def __init__(self, name, **kw):
                if name == "train.dispatch":
                    seen.append(kw)
                super().__init__(name, **kw)

        jax.profiler.TraceAnnotation = Recorder
        fresh = TrainState(mcfg, opt, weights(), mesh=mesh)
        fresh.step(batches[0])
        out["stats_off"] = list(seen)
        trace_dir = tempfile.mkdtemp()
        jax.profiler.start_trace(trace_dir)
        for b in batches[1:]:
            float(fresh.step(b)["loss"])
        jax.profiler.stop_trace()
        jax.profiler.TraceAnnotation = real
        [path] = pathlib.Path(trace_dir).glob("**/*.xplane.pb")
        out["stats_on"] = [st for _, _, st in span_stats.read(
            path, ["train.dispatch"])["train.dispatch"]]
        text = fresh._owned.donating.lower(
            *fresh.state.read(), shard_batch(mesh, batches[0]))
        text = text.compile().as_text()
        wire = collective_bytes(text, while_mult=layer_trips(mcfg))
        out["wire_bytes"] = sum(wire.values())
        out["rs_bytes"] = wire["reduce-scatter"]
        out["remat_saved"] = step_saved_bytes(mcfg, batches[0], mesh)
    print(json.dumps(out))
''')


@pytest.fixture(scope="module")
def run():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)],
                         capture_output=True, text=True, timeout=600,
                         cwd=ROOT, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_placed_over_four_devices(run):
    assert run["placed_devices"] == [4]
    assert any("data" in s and "model" in s for s in run["placed"])
    assert run["share_on_device0"] < 0.3


def test_both_variants_keep_their_shardings(run):
    assert run["donating_shardings"] == [run["placed"]] * 3
    assert run["keeping_shardings"] == [run["placed"]] * 3
    assert run["restored_shardings"] == run["placed"]


def test_sharded_matches_one_device_and_reference(run):
    one, sharded, ref = run["one"], run["sharded"], run["reference"]
    np.testing.assert_allclose(sharded[0], one[0], rtol=1e-5)
    np.testing.assert_allclose(sharded[1], one[1], rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(sharded[0], ref[0], rtol=1e-4)
    np.testing.assert_allclose(sharded[1], ref[1], rtol=2e-3, atol=1e-6)


def test_colour_bumps_once_a_step(run):
    assert run["color"] == 3
    assert run["color_before_restore"] == 3 + 1 + 3


def test_slot_stops_donation_and_restores_on_the_mesh(run):
    assert run["donated_old_deleted"]
    assert run["kept_old_alive"]
    assert run["restored_color"] == run["backup_color"] \
        == run["color_before_restore"]
    assert run["restored_is_backup"]
    assert math.isfinite(run["step_after_restore"])


def test_dispatch_stats_only_while_recording(run):
    assert run["stats_off"] == [{}]
    assert run["wire_bytes"] > 0
    assert run["remat_saved"] > 0
    assert run["stats_on"] == [{"donated": 1, "chips": 4,
                                "remat_saved_bytes": run["remat_saved"],
                                "collective_bytes": run["wire_bytes"],
                                "reduce_scatter_bytes": run["rs_bytes"]}] * 2
