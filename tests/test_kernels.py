"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
in interpret mode (the TPU dataflow executed in Python), which every call
here asks for explicitly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypcompat import given, settings, st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def arr(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


@pytest.mark.parametrize("B,H,Hkv,T,hd,bq,bk", [
    (1, 2, 2, 128, 64, 64, 64),       # MHA
    (2, 4, 2, 256, 64, 128, 128),     # GQA
    (1, 4, 1, 128, 128, 64, 64),      # MQA
    (1, 2, 2, 192, 64, 64, 64),       # non-power-of-two T
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, Hkv, T, hd, bq, bk, dtype):
    q = arr(B, H, T, hd, dtype=dtype)
    k = arr(B, Hkv, T, hd, dtype=dtype)
    v = arr(B, Hkv, T, hd, dtype=dtype)
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk,
                              interpret=True)
    exp = ref.attention(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_noncausal():
    q, k, v = arr(1, 2, 64, 64), arr(1, 2, 64, 64), arr(1, 2, 64, 64)
    out = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                              interpret=True)
    exp = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("B,H,Hkv,S,hd", [
    (2, 4, 2, 256, 64),
    (1, 8, 1, 512, 128),              # MQA long cache
    (3, 6, 6, 128, 64),
])
def test_decode_attention(B, H, Hkv, S, hd):
    q = arr(B, H, hd)
    k = arr(B, Hkv, S, hd)
    v = arr(B, Hkv, S, hd)
    lengths = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
    out = ops.decode_attention(q, k, v, lengths, block_k=128,
                               interpret=True)
    exp = ref.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=10, deadline=None)
@given(e=st.sampled_from([1, 2, 4]), c=st.sampled_from([64, 128]),
       d=st.sampled_from([128, 256]), f=st.sampled_from([64, 128]))
def test_moe_gmm_property(e, c, d, f):
    x = arr(e, c, d)
    w = arr(e, d, f)
    out = ops.moe_gmm(x, w, block_c=64, block_f=64, block_d=64,
                      interpret=True)
    exp = ref.moe_gmm(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-3, atol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_dtypes(dtype):
    x = arr(2, 128, 128, dtype=dtype)
    w = arr(2, 128, 128, dtype=dtype)
    out = ops.moe_gmm(x, w, block_c=64, block_f=64, block_d=64,
                      interpret=True)
    exp = ref.moe_gmm(x, w)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("B,H,T,M,chunk", [
    (1, 1, 64, 16, 16),
    (2, 2, 128, 32, 32),
    (1, 2, 96, 16, 32),               # ragged chunk count
])
def test_rwkv_scan(B, H, T, M, chunk):
    r, k, v = arr(B, H, T, M), arr(B, H, T, M), arr(B, H, T, M)
    logw = -0.105 * jax.nn.sigmoid(arr(B, H, T, M))
    u = arr(H, M, scale=0.1)
    o, S = ops.rwkv_scan(r, k, v, logw, u, chunk=chunk, interpret=True)
    oe, Se = ref.rwkv_scan(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oe),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(S), np.asarray(Se),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("B,T,D,chunk,bd", [
    (1, 64, 64, 32, 64),
    (2, 128, 128, 32, 64),
    (2, 256, 64, 64, 32),
])
def test_rglru_scan(B, T, D, chunk, bd):
    a = jax.nn.sigmoid(arr(B, T, D))
    b = arr(B, T, D)
    h = ops.rglru_scan(a, b, chunk=chunk, block_d=bd, interpret=True)
    he = ref.rglru_scan(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he),
                               rtol=2e-3, atol=2e-3)


def test_rglru_scan_strong_decay_stability():
    """Near-zero a (strong decay) must not overflow/NaN."""
    B, T, D = 1, 128, 32
    a = jnp.full((B, T, D), 1e-4, jnp.float32)
    b = arr(B, T, D)
    h = ops.rglru_scan(a, b, chunk=32, block_d=32, interpret=True)
    assert np.isfinite(np.asarray(h)).all()
    np.testing.assert_allclose(np.asarray(h), np.asarray(ref.rglru_scan(a, b)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name,args", [
    ("flash_attention", lambda: (arr(1, 2, 64, 64),) * 3),
    ("decode_attention", lambda: (arr(1, 2, 64), arr(1, 2, 128, 64),
                                  arr(1, 2, 128, 64),
                                  jnp.full((1,), 128, jnp.int32))),
    ("moe_gmm", lambda: (arr(1, 128, 128), arr(1, 128, 128))),
    ("rwkv_scan", lambda: (arr(1, 1, 64, 16),) * 4 + (arr(1, 16),)),
    ("rglru_scan", lambda: (arr(1, 64, 64), arr(1, 64, 64))),
])
def test_no_silent_interpret_fallback(name, args):
    """Off the TPU a kernel runs only when its caller asks for interpret
    mode; otherwise the call raises instead of hiding the device."""
    if jax.default_backend() == "tpu":
        pytest.skip("the kernels compile natively here")
    with pytest.raises(ValueError, match="interpret"):
        getattr(ops, name)(*args())
