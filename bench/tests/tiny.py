"""A copy of the benchmark with every configuration cut to a size the CPU
runs in seconds: the same files, read by the same code."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench.registry import BENCH, ROOT, Registry

TINY_MODEL = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  vocab_size=512)
TINY_SEQ = 32
# a stand-in for the chip's peaks entry, for runs on the CPU
CPU_PEAK = {"bf16_flop_per_s": 1e12}


def tiny_registry(tmp: Path) -> Registry:
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for p in (tmp / "bench" / "configs").glob("*.json"):
        p.write_text(json.dumps({**json.loads(p.read_text()), **TINY_MODEL}))
    for p in (tmp / "bench" / "traffic").glob("*.json"):
        p.write_text(json.dumps({**json.loads(p.read_text()),
                                 "seq": TINY_SEQ}))
    return Registry(tmp)
