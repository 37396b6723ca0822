"""The benchmark's FLOP count and peaks table."""

import json

import pytest

from bench import flops
from bench.registry import BENCH


def test_flops_smoke_size_by_hand():
    cfg = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
           "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
           "vocab_size": 10}
    # per layer: wq 8*2*4 + wk,wv 2*8*1*4 + wo 2*4*8 + mlp 3*8*16
    per_layer = 64 + 64 + 64 + 384
    assert flops.matmul_params(cfg) == 2 * per_layer + 10 * 8
    # causal attention, batch 3 x seq 5: fwd 2 products * 2 flops * 5*5 * 2
    # heads * 4 dims / 2 per sequence and layer; training is 3x forward
    attn = 3 * (2 * 2 * 5 * 5 * 2 * 4 / 2) * 2 * 3
    assert flops.train_flops_per_step(cfg, 3, 5) == pytest.approx(
        6 * flops.matmul_params(cfg) * 15 + attn)


def test_qwen3_0_6b_matmul_params():
    cfg = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    assert flops.matmul_params(cfg) == pytest.approx(596e6, rel=1e-3)


def test_peaks_known_and_unknown():
    assert flops.peak("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    assert flops.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert "source" in flops.peak("TPU v5 lite")
    with pytest.raises(KeyError):
        flops.peak("cpu")
