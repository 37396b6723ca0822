"""Pipeline-parallel tests: degenerate single-stage path in-process, real
2-stage pipeline in a 2-device subprocess."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.pipeline import pipeline_apply
from repro.launch.mesh import make_mesh


def test_single_stage_degenerate():
    mesh = make_mesh((1,), ("pod",))
    w = jnp.full((1, 4, 4), 2.0)          # one stage: y = x @ 2I-ish
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 3, 4)),
                    jnp.float32)
    y = pipeline_apply(lambda p, xb: xb @ p, mesh, w, x, n_microbatches=4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w[0]),
                               rtol=1e-5)


def test_two_stage_pipeline_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.pipeline import pipeline_apply
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("pod",))
        rng = np.random.default_rng(0)
        W = jnp.asarray(rng.standard_normal((2, 4, 4)) * 0.5, jnp.float32)
        x = jnp.asarray(rng.standard_normal((8, 3, 4)), jnp.float32)
        stage = lambda p, xb: jnp.tanh(xb @ p)
        y = pipeline_apply(stage, mesh, W, x, n_microbatches=4)
        expected = jnp.tanh(jnp.tanh(x @ W[0]) @ W[1])
        np.testing.assert_allclose(np.asarray(y), np.asarray(expected),
                                   rtol=1e-4, atol=1e-5)
        print("PIPELINE_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    assert "PIPELINE_OK" in out.stdout
