"""Direct unit tests for the repro.dist subsystem: sharding rules under
odd mesh sizes and the four-chip cell's layout, int8 compression error
bounds, and the HLO collective-bytes parser."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro import configs
from repro.dist import compression
from repro.dist.collectives import collective_bytes
from repro.dist.sharding import (_fit, _path_str, activation_spec,
                                 batch_specs, cache_specs, opt_state_specs,
                                 param_specs)
from repro.models import build_batch_spec, init_cache, init_params

KEY = jax.random.PRNGKey(0)


def fake_mesh(**shape):
    return types.SimpleNamespace(shape=shape)


def _check_divisible(mesh, abstract, specs):
    flat_p = jax.tree.leaves(abstract)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        for dim, axes in zip(leaf.shape, tuple(spec)):
            if axes is None:
                continue
            n = 1
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                n *= mesh.shape[a]
            assert dim % n == 0, f"{leaf.shape} vs {spec}"


# ---------------------------------------------------------------- sharding
@pytest.mark.parametrize("shape", [dict(data=3, model=5),
                                   dict(data=7, model=2),
                                   dict(pod=3, data=2, model=9),
                                   dict(data=1, model=1)])
@pytest.mark.parametrize("arch", ["qwen3_0_6b", "qwen3_moe_235b", "rwkv6_3b",
                                  "recurrentgemma_9b"])
def test_param_specs_fit_odd_meshes(shape, arch):
    """Every rule degrades to a dividing (or replicated) spec on meshes
    whose sizes share no factor with the tensor dims."""
    m = fake_mesh(**shape)
    cfg = configs.smoke(arch)
    abstract = jax.eval_shape(lambda k: init_params(cfg, k), KEY)
    _check_divisible(m, abstract, param_specs(m, abstract))


def test_fit_handles_absent_axes_and_long_specs():
    m = fake_mesh(data=4)
    # unknown axis drops; spec longer than rank truncates
    assert _fit(m, P("model", "data"), (8,)) == P(None)
    assert _fit(m, P(("data", "model")), (8,)) == P(("data",))
    assert _fit(m, P("data"), (8, 8)) == P("data", None)


def test_opt_state_specs_tie_moments_to_params():
    from repro.train.optimizer import OptConfig, init_opt_state
    m = fake_mesh(data=2, model=4)
    cfg = configs.smoke("qwen3_0_6b")
    params = jax.eval_shape(lambda k: init_params(cfg, k), KEY)
    for name in ("adamw", "adafactor"):
        opt = jax.eval_shape(
            lambda p: init_opt_state(OptConfig(name=name), p), params)
        specs = opt_state_specs(m, opt, params)
        assert specs["count"] == P()
        _check_divisible(m, opt[[k for k in opt if k != "count"][0]],
                         specs[[k for k in specs if k != "count"][0]])
        if name == "adafactor":
            # collapsed factored dims (size 1) must never stay sharded
            for leaf, spec in zip(
                    jax.tree.leaves(opt["vr"]),
                    jax.tree.leaves(specs["vr"],
                                    is_leaf=lambda x: isinstance(x, P))):
                for dim, axes in zip(leaf.shape, tuple(spec)):
                    assert not (dim == 1 and axes is not None)


def test_cache_specs_shard_sequence_over_model():
    m = fake_mesh(data=2, model=4)
    cfg = configs.smoke("qwen3_0_6b")
    cache = jax.eval_shape(lambda: init_cache(cfg, 4, 128))
    specs = cache_specs(m, cache)
    _check_divisible(m, cache, specs)
    k_spec = jax.tree.leaves(
        cache_specs(m, {"k": jax.ShapeDtypeStruct((4, 128, 4, 32),
                                                  jnp.bfloat16)}),
        is_leaf=lambda x: isinstance(x, P))[0]
    assert k_spec[1] == "model" and k_spec[0] in (("data",), "data")


def test_activation_spec_odd_dims_replicate():
    m = fake_mesh(data=3, model=5)
    assert activation_spec(m, (9, 25, 7)) == P(("data",), "model", None)
    assert activation_spec(m, (8, 24, 7)) == P(None, None, None)


# The four-chip cell's layout: qwen3-1.7b on (data 2, model 2), the dense
# projections on d_model over `data` and `model` together (one dim, so each
# layer's weight gradient reduce-scatters), the vocabulary over `model`.
DM = ("data", "model")
QWEN3_1_7B_2X2 = {
    "embed": P("model", "data"),
    "final_norm": P(None),
    "layers/attn/k_norm": P(None, None),
    "layers/attn/q_norm": P(None, None),
    "layers/attn/wk": P(None, DM, None, None),
    "layers/attn/wo": P(None, None, None, DM),
    "layers/attn/wq": P(None, DM, None, None),
    "layers/attn/wv": P(None, DM, None, None),
    "layers/mlp/w_down": P(None, None, DM),
    "layers/mlp/w_gate": P(None, DM, None),
    "layers/mlp/w_up": P(None, DM, None),
    "layers/norm1": P(None, None),
    "layers/norm2": P(None, None),
}


@pytest.fixture(scope="module")
def qwen3_1_7b_2x2():
    """(mesh, cfg, {leaf path: (shape, spec)}) of qwen3-1.7b's parameters
    on a (data 2, model 2) mesh."""
    m = fake_mesh(data=2, model=2)
    cfg = configs.get("qwen3-1.7b")
    abstract = jax.eval_shape(lambda k: init_params(cfg, k), KEY)
    leaves = jax.tree_util.tree_flatten_with_path(abstract)[0]
    specs = jax.tree.leaves(param_specs(m, abstract),
                            is_leaf=lambda x: isinstance(x, P))
    return m, cfg, {_path_str(path): (leaf.shape, spec)
                    for (path, leaf), spec in zip(leaves, specs)}


@pytest.mark.parametrize("leaf", sorted(QWEN3_1_7B_2X2) + ["batch",
                                                          "activation"])
def test_qwen3_1_7b_layout_on_2x2(qwen3_1_7b_2x2, leaf):
    """Each parameter leaf, the 4 x 2,048 batch and the residual stream
    of the four-chip cell keep the layout they are trained in."""
    m, cfg, layout = qwen3_1_7b_2x2
    if leaf == "batch":
        specs = batch_specs(m, build_batch_spec(cfg, 4, 2048))
        assert specs == {"tokens": P("data", None), "labels": P("data", None)}
    elif leaf == "activation":
        assert activation_spec(m, (4, 2048, cfg.d_model)) \
            == P("data", "model", None)
    else:
        assert sorted(layout) == sorted(QWEN3_1_7B_2X2)
        assert layout[leaf][1] == QWEN3_1_7B_2X2[leaf]


# -------------------------------------------------------------- compression
def test_int8_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    for scale_exp in (-3, 0, 4):
        x = jnp.asarray(rng.standard_normal(4096) * 10.0 ** scale_exp,
                        jnp.float32)
        q, s = compression.quantize_int8(x)
        assert q.dtype == jnp.int8
        err = np.abs(np.asarray(x) - np.asarray(
            compression.dequantize_int8(q, s)))
        assert err.max() <= float(s) / 2 + 1e-12 * 10.0 ** scale_exp


def test_int8_axiswise_tightens_error():
    rng = np.random.default_rng(1)
    # one huge row blows up the global scale; per-row scales stay tight
    x = np.asarray(rng.standard_normal((8, 512)), np.float32)
    x[0] *= 1000.0
    xg = jnp.asarray(x)
    qg, sg = compression.quantize_int8(xg)
    qa, sa = compression.quantize_int8(xg, axis=1)
    err_g = np.abs(x[1:] - np.asarray(compression.dequantize_int8(qg, sg))[1:])
    err_a = np.abs(x[1:] - np.asarray(compression.dequantize_int8(qa, sa))[1:])
    assert err_a.max() < err_g.max() / 10


def test_int8_zero_tensor_safe():
    q, s = compression.quantize_int8(jnp.zeros(16))
    np.testing.assert_array_equal(
        np.asarray(compression.dequantize_int8(q, s)), np.zeros(16))


def test_tree_quantize_roundtrip_and_wire_bytes():
    tree = {"w": jnp.asarray(np.random.default_rng(2)
                             .standard_normal((64, 32)), jnp.float32),
            "norm": jnp.ones(4, jnp.float32),
            "step": jnp.zeros((), jnp.int32)}
    packed = compression.quantize_tree(tree, min_size=64)
    assert isinstance(packed["w"], dict)          # large leaf quantized
    assert isinstance(packed["norm"], jnp.ndarray)  # small leaf exact
    out = compression.dequantize_tree(packed)
    assert out["w"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(tree["w"]),
                               atol=float(packed["w"]["scale"]))
    assert compression.wire_bytes(packed) < compression.wire_bytes(tree) / 3


# -------------------------------------------------------------- collectives
# One HLO line per kind, with the per-device wire bytes the parser must
# give at while_mult 10: result bytes x the ring factor of its group size
# G (x10 inside a while body); a -done line carries no new bytes.  Three
# cases hold more than one line: an all-reduce-scatter fusion counts once,
# as a reduce-scatter of (G-1)/G x its all-reduce's bytes, and that inner
# all-reduce not at all; the pieces of one chained collective count once;
# a tuple's ``/*index=N*/`` comments hide none of its shapes.
_COLLECTIVE_LINES = {
    "all-gather-in-while": (
        "all-gather",
        '%ag = bf16[16,256,4096]{2,1,0} all-gather(%x), channel_id=1, '
        'replica_groups=[2,4]<=[8], dimensions={1}, '
        'metadata={op_name="jit(f)/while/body/ag"}',
        16 * 256 * 4096 * 2 * (3 / 4) * 10),
    "all-reduce": (
        "all-reduce",
        '%ar = f32[1024]{0} all-reduce(%y), channel_id=2, '
        'replica_groups=[4,2]<=[8], to_apply=%add, '
        'metadata={op_name="jit(f)/ar"}',
        1024 * 4 * 2 * (1 / 2)),
    "reduce-scatter": (
        "reduce-scatter",
        '%rs = f32[64,64]{1,0} reduce-scatter(%z), channel_id=3, '
        'replica_groups=[2,4]<=[8], dimensions={0}, '
        'metadata={op_name="jit(f)/rs"}',
        64 * 64 * 4 * 3),
    "all-to-all": (
        "all-to-all",
        '%a2a = bf16[8,128]{1,0} all-to-all(%w), channel_id=4, '
        'replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, '
        'metadata={op_name="jit(f)/a2a"}',
        8 * 128 * 2 * (3 / 4)),
    "collective-permute": (
        "collective-permute",
        '%cp = f32[256]{0} collective-permute(%v), channel_id=5, '
        'source_target_pairs={{0,1},{1,0}}, '
        'metadata={op_name="jit(f)/cp"}',
        256 * 4),
    "done-counts-zero": (
        "all-gather",
        '%agd = bf16[16,256]{1,0} all-gather-done(%ags), '
        'metadata={op_name="jit(f)/agd"}',
        0),
    "all-reduce-scatter-fusion": (
        "reduce-scatter",
        '%all-reduce-scatter.6 (input.7: bf16[6144,2048]) '
        '-> bf16[6144,512] {\n'
        '  %input.7 = bf16[6144,2048]{1,0} parameter(0)\n'
        '  %all-reduce.81 = bf16[6144,2048]{1,0} all-reduce(%input.7), '
        'channel_id=6, replica_groups={{0,1,2,3}}, to_apply=%add\n'
        '  ROOT %ds = bf16[6144,512]{1,0} dynamic-slice(%all-reduce.81)\n'
        '}\n\n'
        'ENTRY %main (p: bf16[6144,2048]) -> bf16[6144,512] {\n'
        '  %fusion.591 = bf16[6144,512]{1,0} fusion(%p), kind=kCustom, '
        'calls=%all-reduce-scatter.6, '
        'metadata={op_name="jit(f)/while/body/dot_general"}',
        6144 * 2048 * 2 * (3 / 4) * 10),
    "chained-all-gather": (
        "all-gather",
        '%all-gather.219 = bf16[2048,8,128]{2,0,1} all-gather(%p0), '
        'replica_groups=[1,4]<=[4], dimensions={0}, '
        'frontend_attributes={chain_id="6"}, '
        'metadata={op_name="jit(f)/ag"}\n'
        '  %all-gather.221 = bf16[2048,8,128]{2,0,1} all-gather(%p1), '
        'replica_groups=[1,4]<=[4], dimensions={0}, '
        'frontend_attributes={chain_id="6"}, '
        'metadata={op_name="jit(f)/ag"}',
        2048 * 8 * 128 * 2 * (3 / 4)),
    "tuple-all-reduce": (
        "all-reduce",
        '%ar.62 = (bf16[64]{0}, bf16[64]{0}, bf16[64]{0}, bf16[64]{0}, '
        'bf16[64]{0}, /*index=5*/bf16[64]{0}, bf16[64]{0}) '
        'all-reduce(%a, %b, %c, %d, %e, /*index=5*/%f, %g), '
        'replica_groups=[1,4]<=[4], to_apply=%add, '
        'metadata={op_name="jit(f)/ar"}',
        7 * 64 * 2 * 2 * (3 / 4)),
}


@pytest.mark.parametrize("case", list(_COLLECTIVE_LINES))
def test_collective_bytes_parser(case):
    kind, line, expected = _COLLECTIVE_LINES[case]
    out = collective_bytes("\n  " + line + "\n", while_mult=10)
    assert out[kind] == pytest.approx(expected, rel=1e-6, abs=0)
    assert all(v == 0 for k, v in out.items() if k != kind)
