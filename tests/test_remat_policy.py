"""The layer scan's remat policy on tiny configurations: the step keeps
the projection outputs where its compiled program fits the chip, and
recomputes every layer where it does not (``train_step.compile_fitting``);
``transformer.saved_bytes`` counts exactly the stacked outputs the lowered
program carries from the forward to the backward; and the policy leaves
the loss and the gradients as they were: bit for bit on the CPU where the
layers are scanned, within bf16 rounding where they are unrolled."""

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import init_params, loss_fn
from repro.models.transformer import RECOMPUTE, SAVE_PROJECTIONS, saved_bytes
from repro.train import OptConfig, init_opt_state, make_train_step
from repro.train.train_step import (compile_fitting, jit_train_step,
                                    step_saved_bytes)

B, T = 3, 16
DTYPE_BYTES = {"bf16": 2, "f32": 4}


def _setup(arch, **changes):
    cfg = dataclasses.replace(configs.smoke(arch), **changes)
    rng = np.random.default_rng(0)
    batch = {k: jnp.asarray(rng.integers(0, cfg.vocab, (B, T), np.int32))
             for k in ("tokens", "labels")}
    if cfg.prefix_len:
        batch["prefix_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.prefix_len, cfg.d_model)) * 0.1,
            jnp.bfloat16)
    return cfg, init_params(cfg, jax.random.PRNGKey(0)), batch


def _loss(cfg, policy):
    return functools.partial(loss_fn, cfg, remat_policy=policy)


def _fit(cfg, params, batch, donate, limit=None):
    """``compile_fitting`` as ``TrainState`` calls it, and the peak bytes
    (arguments + fresh outputs + temporaries) of the program it chose."""
    opt = OptConfig()
    args = (params, init_opt_state(opt, params), batch)
    fitted = compile_fitting(
        lambda policy: jit_train_step(make_train_step(
            cfg, opt, remat_policy=policy), donate=donate),
        args, cfg, limit=limit)
    mem = fitted.compiled.memory_analysis()
    return fitted, (mem.argument_size_in_bytes + mem.output_size_in_bytes
                    - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


@pytest.mark.parametrize("donate", [True, False],
                         ids=["donating", "slot-held"])
def test_saves_the_projections_where_they_fit(donate):
    cfg, params, batch = _setup("qwen3-0.6b")
    fitted, _ = _fit(cfg, params, batch, donate)
    widths = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.hd + cfg.d_model \
        + 2 * cfg.d_ff
    assert fitted.policy is SAVE_PROJECTIONS
    assert fitted.saved == cfg.n_layers * B * T * widths * 2   # bf16
    assert fitted.saved == step_saved_bytes(cfg, batch)


def test_recomputes_where_the_saving_program_does_not_fit():
    """A synthetic limit: the saving program is chosen up to the last
    byte it needs (its own compiled peak), and not one byte below it,
    where the step recomputes each layer, as before saving existed."""
    cfg, params, batch = _setup("qwen3-0.6b")
    need = _fit(cfg, params, batch, False)[1]
    assert _fit(cfg, params, batch, False, limit=need)[0].policy \
        is SAVE_PROJECTIONS
    recomputing = _fit(cfg, params, batch, False, limit=need - 1)[0]
    assert recomputing.policy is RECOMPUTE and recomputing.saved == 0


@pytest.mark.parametrize("arch,changes", [
    ("rwkv6-3b", {}), ("qwen3-moe-235b-a22b", {}),
    ("recurrentgemma-9b", {}), ("qwen3-0.6b", {"remat": False})],
    ids=["rwkv", "moe", "hybrid", "remat-off"])
def test_blocks_it_does_not_count_recompute(arch, changes):
    cfg = dataclasses.replace(configs.smoke(arch), **changes)
    assert saved_bytes(cfg, (B, T)) == 0


def _stacked_bytes(cfg, params, batch, policy):
    """Bytes of the (layers, B, T, ...) buffers in the lowered gradient's
    widest ``while`` signature: what the forward scan hands the
    backward."""
    positions = T + (cfg.prefix_len if "prefix_embeds" in batch else 0)
    lead = f"{cfg.n_layers}x{B}x{positions}x"
    text = jax.jit(jax.grad(_loss(cfg, policy))).lower(
        params, batch).as_text()
    return max(
        sum(math.prod(map(int, dims.split("x"))) * DTYPE_BYTES[dt]
            for dims, dt in re.findall(
                r"tensor<((?:\d+x)+\d+)x(bf16|f32)>", sig)
            if dims.startswith(lead))
        for sig in re.findall(r"stablehlo\.while\(([^\n]*)", text))


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "pixtral-12b"])
def test_count_equals_the_lowered_programs_stacked_outputs(arch):
    """Saving adds to the scan's stacked residuals exactly the bytes the
    count reports (the layer inputs are stacked under both)."""
    cfg, params, batch = _setup(arch)
    saved = step_saved_bytes(cfg, batch)
    assert saved > 0
    assert _stacked_bytes(cfg, params, batch, SAVE_PROJECTIONS) \
        - _stacked_bytes(cfg, params, batch, RECOMPUTE) == saved


def _worst_gap(a, b) -> float:
    """The largest norm of a leaf's difference over the norm of b's."""
    return max(float(jnp.linalg.norm((x - y).astype(jnp.float32))
                     / jnp.linalg.norm(y.astype(jnp.float32)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("arch,scan", [("qwen3-0.6b", True),
                                       ("qwen3-0.6b", False),
                                       ("pixtral-12b", True)],
                         ids=["qwen3-scan", "qwen3-unrolled", "pixtral-scan"])
def test_both_policies_give_the_same_loss_and_gradients(arch, scan):
    """Saving changes where the backward's values come from, not the
    arithmetic.  Scanned, loss and gradients agree bit for bit on the
    CPU.  Unrolled, XLA fuses the recomputed layers otherwise and rounds
    their bf16 values elsewhere: the loss agrees and the gradients differ
    by less than either differs from the same step in float32."""
    cfg, params, batch = _setup(arch, scan_layers=scan)
    saving = jax.jit(jax.value_and_grad(_loss(cfg, SAVE_PROJECTIONS)))(
        params, batch)
    recomputing = jax.jit(jax.value_and_grad(_loss(cfg, RECOMPUTE)))(
        params, batch)
    assert saving[0] == recomputing[0]
    if scan:
        for a, b in zip(jax.tree.leaves(saving),
                        jax.tree.leaves(recomputing)):
            assert jnp.array_equal(a, b)
        return
    f32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(jax.grad(_loss(f32, RECOMPUTE)))(
            jax.tree.map(lambda x: x.astype(jnp.float32), params), batch)
    assert _worst_gap(saving[1], recomputing[1]) \
        < _worst_gap(recomputing[1], exact)
