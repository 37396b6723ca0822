"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phases and checks pass at the reduced smoke sizes on the CPU (the
four-chip phase on four host devices, in a child process)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SMOKE_TRAIN = ["--arch", "qwen3-0.6b", "--steps", "8", "--batch", "8",
               "--seq", "64"]
SMOKE_SERVE = ["--arch", "qwen3-0.6b", "--requests", "8", "--slots", "4",
               "--max-new", "8"]


def _child_env(**extra):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(str(ROOT))


def test_refuses_without_tpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=_child_env(), cwd=ROOT)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_refuses_outside_a_checkout(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    env = _child_env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_train_and_serve_phases_pass_at_smoke_size(chip_smoke):
    clock = chip_smoke.CompileClock()
    train = chip_smoke.train_phase(SMOKE_TRAIN, clock)
    assert train["steps"] == 8 and train["last_loss"] < train["first_loss"]
    serve = chip_smoke.serve_phase(SMOKE_SERVE, clock)
    assert serve["requests"] == 8 and serve["tokens"] == 64
    assert serve["logit_rel_rms_err"] <= chip_smoke.LOGIT_RMS_TOL


def test_four_chip_phase_on_four_host_devices():
    code = textwrap.dedent("""
        import json, sys
        sys.path.insert(0, sys.argv[1])
        import chip_smoke
        from repro import configs
        print(json.dumps(chip_smoke.four_chip_phase(
            configs.smoke("qwen3-0.6b"), batch=8, seq=64)))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code, str(ROOT)], capture_output=True,
        text=True, timeout=600, cwd=ROOT, env=_child_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(res["sharded_losses"]) == 3
    assert res["param_share_on_device0"] < 0.3


def test_compile_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to one fixed, gitignored directory of the checkout.
    (The cache itself is never turned on here.)"""
    import jax

    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
