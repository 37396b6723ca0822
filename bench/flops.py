"""Model FLOPs of a training step, from the configuration and the batch
shape alone, and the chip's peaks.

A step's model FLOPs are 6 per matmul parameter per token (forward 2,
backward 4), the tied output head included, plus causal attention: the
score and value products cost 2 * 2 * T * T * H * hd per sequence and
layer in the forward pass, of which causality keeps half, and the backward
pass costs twice the forward.  Recomputation under remat is not counted.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul for every token: the layers'
    projections and the output head (the embedding lookup is a gather)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * f
    return cfg["num_hidden_layers"] * (attn + mlp) + cfg["vocab_size"] * d


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Training FLOPs of causal attention per token at row length ``seq``."""
    per_seq_fwd = 2 * 2 * seq * seq * cfg["num_attention_heads"] \
        * cfg["head_dim"] / 2
    return 3 * per_seq_fwd * cfg["num_hidden_layers"] / seq


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    tokens = batch * seq
    return tokens * (6 * matmul_params(cfg)
                     + attention_flops_per_token(cfg, seq))


def peak(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]
