"""The command refuses to run without the chips it needs, and outside a
checkout, and prints no result then."""

import json
import os
import shutil
import subprocess
import sys

from bench.registry import ROOT


def _run(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, "--workload", "qwen3-0.6b.train.short-rows",
         "--seed", "2147483659", "--seconds", "10", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    res = _run(ROOT, str(ROOT / "bench" / "run.py"))
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert "metrics" not in res.stdout


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    res = _run(tmp_path, str(tmp_path / "bench" / "run.py"))
    assert res.returncode != 0
    assert "metrics" not in res.stdout
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert spec["command"] == ["python3", "bench/run.py"]
