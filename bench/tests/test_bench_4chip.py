"""The four-chip cell at a tiny size on four host devices, in a child
process (the device count is fixed before JAX starts): a sound run is
correct; with the timed path broken underneath (each fault a cell can
have) it is not; and the control, the sharded reference computed with
every matmul operand in float8_e4m3fn, is not, by the cell's own limits,
on three seeds."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench.registry import ROOT

CELL = "qwen3-1.7b.train.4chip"
SEEDS = (11, 12, 13)

CHILD = textwrap.dedent('''
    import json, sys, tempfile, time
    from pathlib import Path
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    import jax

    from bench import check, harness
    from bench.tests.faults import FAULTS, run_with_fault
    from bench.tests.tiny import CPU_PEAK, tiny_registry

    name, seeds = sys.argv[2], [int(s) for s in sys.argv[3].split(",")]
    reg = tiny_registry(Path(tempfile.mkdtemp()))
    out = {}
    sound = harness.run(reg, name, 7, 0.5, False, time.perf_counter(),
                        jax.devices()[:4], peak=CPU_PEAK)
    out["sound"] = {k: sound[k] for k in ("correct", "attempted", "failed",
                                          "checks", "device")}
    out["sound"]["metrics"] = sorted(sound["metrics"])
    out["faults"] = {f: run_with_fault(reg, name, f)["correct"]
                     for f in FAULTS}
    cell = harness.Cell(reg, name)
    out["control"] = {}
    for seed in seeds:
        pool = cell.pool(seed)
        ref = harness.reference_readings(cell, seed, pool)
        control = harness.reference_readings(cell, seed, pool, "fp8")
        out["control"][seed] = check.judge(check.gaps(control, ref),
                                           cell.limits)[0]
    print(json.dumps(out))
''')


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(ROOT), CELL,
         ",".join(map(str, SEEDS))],
        capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(runs):
    sound = runs["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["device"]["count"] == 4
    assert set(sound["checks"]) == {"grad_gap", "update_gap"}
    assert sound["metrics"] == ["setup_s", "train_tokens_per_s"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct(runs, fault):
    assert runs["faults"][fault] is False


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(runs, seed):
    assert runs["control"][str(seed)] is False
