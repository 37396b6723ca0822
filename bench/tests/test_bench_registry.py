"""The harness is driven by data: a configuration, a cell and a metric
are found by name, from files alone."""

import json
import shutil

from bench.registry import BENCH, ROOT, Registry


def test_registry_finds_added_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "qwen3-0.6b.json").read_text())
    (bench / "configs" / "extra-model.json").write_text(
        json.dumps({**cfg, "name": "extra-model", "num_hidden_layers": 4}))
    cell = json.loads(
        (bench / "workloads" / "qwen3-0.6b.train.short-rows.json").read_text())
    (bench / "workloads" / "extra-model.train.8x256.json").write_text(
        json.dumps(cell))
    (bench / "metrics" / "extra_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec["configs"].append({"name": "extra-model", "source": "x",
                            "file": "bench/configs/extra-model.json",
                            "reduced": ["num_hidden_layers"]})
    spec["workloads"].append({"name": "extra-model.train.8x256",
                              "config": "extra-model",
                              "traffic": "train.8x256", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "extra_metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "setup_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    reg = Registry(tmp_path)
    found = reg.cell("extra-model.train.8x256")
    assert found["entry"] == "train_state" and found["chips"] == 1
    assert reg.config(found["config"])["num_hidden_layers"] == 4
    assert reg.traffic(found["traffic"])["seq"] == 256
    assert hasattr(reg.generator(reg.traffic(found["traffic"])), "pool")
    assert hasattr(reg.reference(reg.config(found["config"])),
                   "train_readings")
    assert hasattr(reg.entry(found["entry"]), "Runner")
    names = [m["name"] for m in reg.metrics("extra-model.train.8x256",
                                            "per_layer")]
    assert "extra_metric" in names
    assert reg.reader("extra_metric")(None) == 42.0


def test_every_benchmark_name_has_its_files():
    reg = Registry(ROOT)
    for w in reg.spec["workloads"]:
        cell = reg.cell(w["name"])
        assert hasattr(reg.entry(cell["entry"]), "Runner")
        assert set(cell["limits"]) >= {"grad_gap", "update_gap"}
        reg.traffic(cell["traffic"])
        reg.reference(reg.config(cell["config"]))
    for c in reg.spec["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert reg.config(c["name"])["source"] == c["source"]
    for m in reg.spec["per_layer"]:
        assert callable(reg.reader(m["name"]))
