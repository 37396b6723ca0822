"""Compile rehearsals for a described TPU v5e: no chip is attached, but the
TPU compiler refuses here what the chip would refuse (unaligned blocks,
primitives Mosaic cannot lower, programs that do not fit HBM).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around these
compiles (an entry compiled for a described chip cannot be read back).
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro import configs
from repro.dist.collectives import collective_bytes, layer_trips
from repro.dist.sharding import train_shardings
from repro.models import build_batch_spec, init_cache, init_params
from repro.models.transformer import RECOMPUTE, SAVE_PROJECTIONS
from repro.train import OptConfig, init_opt_state, make_train_step
from repro.train.train_step import compile_fitting, jit_train_step

HBM_BYTES = 16 * 2**30                # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _stacked_bytes(text: str, lead: str) -> int:
    """Bytes of the bf16 buffers whose shape starts with ``lead`` in the
    widest ``while`` of a compiled program: the layer scan's stacked
    residuals, which the forward hands the backward."""
    return max(sum(math.prod(map(int, dims.split(","))) * 2
                   for dims in re.findall(r"bf16\[([\d,]+)\]", shape)
                   if dims.startswith(lead))
               for shape in re.findall(r"^\s*%\S+ = (.*) while\(", text,
                                       re.M))


def _peak(compiled) -> int:
    """A chip's bytes for the program: arguments + the outputs it writes
    beside its donated arguments + temporaries."""
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes


def _fitted(cfg, opt, args, donate, mesh=None, shardings=None):
    """``compile_fitting`` as ``TrainState`` calls it, and the jitted steps
    it tried, in order (their compiles are cached)."""
    tried = []

    def jit_for(policy):
        tried.append(jit_train_step(
            make_train_step(cfg, opt, mesh, remat_policy=policy), shardings,
            donate))
        return tried[-1]

    return compile_fitting(jit_for, args, cfg, mesh), tried


def _saves_its_projections(cfg, fitted, chip_rows) -> None:
    """The layer scan saves its projection outputs, and the compiled
    program's forward scan carries exactly the chooser's bytes of them
    beside the stacked layer inputs, whose first dims after the layers
    are ``chip_rows`` (a chip's share of batch and sequence)."""
    assert fitted.policy is SAVE_PROJECTIONS and fitted.saved > 0
    layer_inputs = cfg.n_layers * math.prod(chip_rows) * cfg.d_model * 2
    lead = ",".join(map(str, (cfg.n_layers,) + chip_rows)) + ","
    assert _stacked_bytes(fitted.compiled.as_text(), lead) \
        == fitted.saved + layer_inputs


def _qwen_abstract():
    cfg = configs.get("qwen3_0_6b")
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    return cfg, params


# (kernel module, argument shapes/dtypes) at the widths of the configs
# that use each kernel
_BF16, _F32, _I32 = jnp.bfloat16, jnp.float32, jnp.int32
KERNELS = {
    # qwen3-0.6b decode: 4 slots, 16/8 heads, head_dim 128, 8k cache
    "decode_attention": ([(4, 16, 128), (4, 8, 8192, 128),
                          (4, 8, 8192, 128)], [_BF16] * 3 + [_I32], (4,)),
    # qwen3-0.6b prefill/train attention at 2k tokens
    "flash_attention": ([(1, 16, 2048, 128), (1, 8, 2048, 128),
                         (1, 8, 2048, 128)], [_BF16] * 3, None),
    # qwen3-moe-235b expert share: d_model 4096, expert d_ff 1536
    "moe_gmm": ([(8, 512, 4096), (8, 4096, 1536)], [_BF16] * 2, None),
    # rwkv6-3b: 40 heads of 64 over 512 tokens
    "rwkv_scan": ([(1, 40, 512, 64)] * 4 + [(40, 64)], [_BF16] * 5, None),
    # recurrentgemma-9b: lru width 4096
    "rglru_scan": ([(1, 512, 4096)] * 2, [_F32] * 2, None),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    import importlib
    fn = getattr(importlib.import_module(f"repro.kernels.{name}"), name)
    shapes, dtypes, extra = KERNELS[name]
    if extra is not None:
        shapes = shapes + [extra]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in zip(shapes, dtypes)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_decode_step_fits_one_v5e(one_chip):
    """The engine's decode step at full width (4 slots, max_len 256)."""
    from repro.serve.serve_step import make_serve_step
    cfg, params = _qwen_abstract()
    cache = jax.eval_shape(functools.partial(init_cache, cfg, 4, 256))
    tokens = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        _placed(params, one_chip), _placed(cache, one_chip), tokens).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("donate", [True, False],
                         ids=["donating", "slot-held"])
def test_qwen3_train_step_fits_one_v5e(one_chip, donate):
    """The launcher's train step at full width (batch 8x256, AdamW with
    f32 moments) fits one chip with the ReplicaSlot backup it keeps, its
    layer scan saving the projection outputs.  Donating (no slot holds
    the state): state + a backup's worth + temporaries.  Not donating
    (the slot's snapshot is the step's own arguments, as in the
    short-rows cell): arguments + outputs + temporaries."""
    cfg, params = _qwen_abstract()
    opt = OptConfig()
    opt_state = jax.eval_shape(functools.partial(init_opt_state, opt), params)
    batch = build_batch_spec(cfg, 8, 256)
    fitted, _ = _fitted(cfg, opt, (_placed(params, one_chip),
                                   _placed(opt_state, one_chip),
                                   _placed(batch, one_chip)), donate)
    _saves_its_projections(cfg, fitted, (8, 256))
    mem = fitted.compiled.memory_analysis()
    second = (mem.argument_size_in_bytes if donate
              else mem.output_size_in_bytes)
    assert mem.argument_size_in_bytes + second + mem.temp_size_in_bytes \
        < HBM_BYTES


def test_qwen3_sharded_train_step_compiles_for_2x2(topo):
    """The (data 2, model 2) train step that ``chip_smoke.py --four-chips``
    runs, as its ``TrainState`` builds it: shardings from
    ``train_shardings``, partitioned over four chips."""
    cfg, params = _qwen_abstract()
    opt = OptConfig()
    opt_state = jax.eval_shape(functools.partial(init_opt_state, opt), params)
    batch = build_batch_spec(cfg, 8, 256)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    fitted, _ = _fitted(cfg, opt, (params, opt_state, batch), True, mesh,
                        train_shardings(mesh, params, opt_state, batch))
    assert fitted.policy is SAVE_PROJECTIONS
    compiled = fitted.compiled
    mem = compiled.memory_analysis()
    state_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves((params, opt_state)))
    # every chip holds about a quarter of the state, not all of it
    assert mem.argument_size_in_bytes < 0.3 * state_bytes
    assert "all-reduce" in compiled.as_text()


@pytest.mark.parametrize("donate,seq", [(True, 2048), (False, 2048),
                                        (False, 4096)],
                         ids=["donating", "slot-held", "slot-held-4096"])
def test_qwen3_1_7b_train_state_step_fits_2x2(topo, donate, seq):
    """qwen3-1.7b's ``TrainState`` step on a (data 2, model 2) mesh at
    4 x ``seq`` tokens, its shardings from ``train_shardings``, its jit
    from ``jit_train_step`` and its remat policy from ``compile_fitting``,
    as ``TrainState`` makes them: it fits one chip, each chip holds about
    a quarter of the state, and the step has collectives.  At 4 x 2,048
    both variants save their layers' projection outputs (donating, as in
    the four-chip cell; slot-held, as the launcher runs it).  At 4 x
    4,096 slot-held, saving would need more than a chip has, so the
    layers are recomputed, as before saving existed."""
    cfg = configs.get("qwen3-1.7b")
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    opt = OptConfig()
    opt_state = jax.eval_shape(functools.partial(init_opt_state, opt), params)
    batch = build_batch_spec(cfg, 4, seq)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    shardings = train_shardings(mesh, params, opt_state, batch)
    args = (params, opt_state, batch)
    fitted, tried = _fitted(cfg, opt, args, donate, mesh, shardings)
    if seq == 2048:
        _saves_its_projections(cfg, fitted, (2, 1024))
    else:
        assert fitted.policy is RECOMPUTE and fitted.saved == 0
        assert _peak(tried[0].lower(*args).compile()) > HBM_BYTES
    compiled = fitted.compiled
    mem = compiled.memory_analysis()
    state_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves((params, opt_state)))
    assert state_bytes > HBM_BYTES                # no one chip holds it
    assert mem.argument_size_in_bytes < 0.3 * state_bytes
    assert _peak(compiled) < HBM_BYTES
    wire = collective_bytes(compiled.as_text(), layer_trips(cfg))
    assert wire["all-gather"] > 0 and wire["all-reduce"] > 0


def _computations(text: str) -> dict:
    """Compiled HLO text by computation: name -> its instruction lines."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY\s+)?%([\w.-]+)\s+\(", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None and line.startswith("  %"):
            comps[name].append(line)
    return comps


def _dims(hlo_type: str) -> list:
    """Every bf16 shape in an HLO type, as tuples."""
    return [tuple(map(int, d.split(",")))
            for d in re.findall(r"bf16\[([\d,]+)\]", hlo_type)]


def test_qwen3_1_7b_weight_gradients_reduce_scatter_on_2x2(topo):
    """In the four-chip cell's donating 4 x 2,048 step each dense layer
    weight lives on one dim over all four chips, so its gradient reaches
    the owner through one reduce-scatter: seven ``all-reduce-scatter``
    fusions each land a quarter of a weight, no other all-reduce carries
    a whole one, the layer scan still saves its projections, and each
    chip's arguments are its shards of the state and batch."""
    cfg = configs.get("qwen3-1.7b")
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    opt = OptConfig()
    opt_state = jax.eval_shape(functools.partial(init_opt_state, opt), params)
    batch = build_batch_spec(cfg, 4, 2048)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    shardings = train_shardings(mesh, params, opt_state, batch)
    fitted, _ = _fitted(cfg, opt, (params, opt_state, batch), True, mesh,
                        shardings)
    assert fitted.policy is SAVE_PROJECTIONS
    # a chip's arguments: its shards, plus the tile padding of small leaves
    shard_bytes = sum(math.prod(s.shard_shape(a.shape)) * a.dtype.itemsize
                      for a, s in zip(jax.tree.leaves((params, opt_state,
                                                       batch)),
                                      jax.tree.leaves(shardings)))
    padding = fitted.compiled.memory_analysis().argument_size_in_bytes \
        - shard_bytes
    assert 0 <= padding < 2**20

    D, F, hd = cfg.d_model, cfg.d_ff, cfg.hd
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    weights = {(D, H, hd): 0, (D, Hkv, hd): 0, (H, hd, D): 2, (D, F): 0,
               (F, D): 1}                   # whole shape -> d_model's dim
    comps = _computations(fitted.compiled.as_text())
    landed = []
    for name, lines in comps.items():
        for line in lines:
            call = re.search(r"calls=%(all-reduce-scatter[\w.-]*)", line)
            if call:
                [inner] = [l for l in comps[call.group(1)]
                           if " all-reduce(" in l]
                whole = _dims(inner.split(" all-reduce(")[0])[0]
                if whole in weights:
                    landed.append((whole, _dims(line.split(" fusion(")[0])[0]))
            elif not name.startswith("all-reduce-scatter") \
                    and re.search(r" all-reduce(-start)?\(", line):
                result = line.split(" all-reduce")[0]
                assert not set(_dims(result)) & set(weights), line[:200]
    quarter = []
    for shape in [(D, H, hd), (D, Hkv, hd), (D, Hkv, hd), (H, hd, D),
                  (D, F), (D, F), (F, D)]:
        cut = list(shape)
        cut[weights[shape]] //= 4
        quarter.append((shape, tuple(cut)))
    assert sorted(landed) == sorted(quarter)
