"""The plain reference against the program's loss, gradients and AdamW,
at a small size on the CPU, both in float32 on the same seeded weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import qwen3 as ref
from bench.tests.tiny import TINY_MODEL

CFG = {"name": "tiny", "rope_theta": 1e6, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": True, "torch_dtype": "float32", **TINY_MODEL}
OPT = {"lr": 1e-3, "warmup": 5, "decay_steps": 100, "b1": 0.9, "b2": 0.95,
       "eps": 1e-8, "weight_decay": 0.01, "clip_norm": 1.0,
       "min_lr_frac": 0.1, "moment_dtype": "float32"}


@pytest.fixture(scope="module")
def case():
    from repro.models.config import ModelConfig
    mcfg = ModelConfig(**ref.program_fields(CFG))
    params = ref.init_weights(CFG, jax.random.key(3), jnp.float32)
    # nonzero norm gains, so that they are exercised
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: x + 0.1 if "norm" in jax.tree_util.keystr(p) else x,
        params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, CFG["vocab_size"], (2, 17), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    return mcfg, params, batch


def test_loss_and_grads_match_program(case):
    from repro.models import loss_fn
    mcfg, params, batch = case
    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(
            lambda p: loss_fn(mcfg, p, batch))(params)
        got, gg = jax.value_and_grad(lambda p: ref.loss(
            CFG, p, batch["tokens"], batch["labels"]))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6)


def test_adamw_matches_program(case):
    from repro.train import OptConfig, apply_updates, init_opt_state
    _, params, _ = case
    grads = jax.tree.map(lambda x: jnp.sin(3 * x) + 0.5, params)
    opt = OptConfig(name="adamw", **OPT)
    state = init_opt_state(opt, params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    p_ref, m, v = params, zeros, zeros
    p_prog = params
    for k in (1, 2, 3):
        p_prog, state, _ = apply_updates(opt, p_prog, grads, state)
        p_ref, m, v, _ = ref.adamw(OPT, p_ref, grads, m, v, k)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_prog)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_train_readings_shape_and_variants():
    cfg = {**CFG, "torch_dtype": "bfloat16"}
    rng = np.random.default_rng(1)
    batches = []
    for _ in range(3):
        t = rng.integers(0, cfg["vocab_size"], (2, 9), dtype=np.int32)
        batches.append({"tokens": t[:, :-1], "labels": t[:, 1:]})
    key = jax.random.key(0)
    base = ref.train_readings(cfg, OPT, key, batches)
    assert len(base["loss"]) == 3 and base["grad_global"] > 0
    assert set(base["grad"]) == set(base["delta"])
    assert all(v > 0 for v in base["delta"].values())
    for variant in ("fp8", "half"):
        other = ref.train_readings(cfg, OPT, key, batches, variant)
        assert other["loss"] != base["loss"]
