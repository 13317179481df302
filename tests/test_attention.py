"""Attention: flash pallas/xla vs oracle; gradients; causality property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers import given, settings, st

from repro.kernels import ops
from repro.kernels.attention import (attention_xla, decode_attention_xla,
                                     flash_attention_pallas)
from repro.kernels.ref import attention_ref

settings.register_profile("fast", max_examples=10, deadline=None)
settings.load_profile("fast")

KEY = jax.random.key(0)


def qkv(b=2, hq=4, hkv=2, s=128, d=32, sk=None):
    sk = sk or s
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (b, hq, s, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (b, hkv, sk, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (b, hkv, sk, d))
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 32])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_flash_matches_ref(causal, window, impl):
    q, k, v = qkv()
    want = attention_ref(q, k, v, causal=causal, window=window)
    if impl == "pallas":
        got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                     bq=32, bk=32, interpret=True)
    else:
        got = attention_xla(q, k, v, causal=causal, window=window,
                            q_chunk=32, kv_chunk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s", [37, 200, 1100])
@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_ops_attention_any_length(impl, s, causal):
    """Prompt lengths that are no multiple of the kernel tile (128) or the
    XLA chunk (1024): the dispatch layer's default tiles must pad and mask,
    not assert."""
    q, k, v = qkv(b=1, hq=4, hkv=2, s=s, d=32)
    want = attention_ref(q, k, v, causal=causal)
    got = ops.attention(q, k, v, impl=impl, causal=causal)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (8, 1)])
def test_gqa_ratios(hq, hkv):
    q, k, v = qkv(hq=hq, hkv=hkv)
    want = attention_ref(q, k, v)
    got = attention_xla(q, k, v, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_gradients_match_ref():
    q, k, v = qkv(s=96, d=16)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    gr = jax.grad(loss(lambda q, k, v: attention_ref(q, k, v, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(lambda q, k, v: attention_xla(
        q, k, v, causal=True, q_chunk=32, kv_chunk=32)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=1e-3)


def test_flash_gradients_ragged_length():
    """The padded-key mask also holds in the custom-VJP backward."""
    q, k, v = qkv(s=100, d=16)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    for causal in (True, False):
        gr = jax.grad(loss(lambda q, k, v: attention_ref(
            q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss(lambda q, k, v: attention_xla(
            q, k, v, causal=causal, q_chunk=32, kv_chunk=32)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)


@given(st.integers(min_value=0, max_value=62))
def test_causality_property(t):
    """Output at position t is independent of tokens > t (the causal-mask
    invariant, checked by perturbing the future)."""
    q, k, v = qkv(b=1, hq=2, hkv=2, s=64, d=8)
    out1 = attention_xla(q, k, v, causal=True, q_chunk=32, kv_chunk=32)
    noise = jnp.zeros_like(k).at[:, :, t + 1:, :].set(99.0)
    out2 = attention_xla(q, k + noise, v + noise, causal=True, q_chunk=32,
                         kv_chunk=32)
    np.testing.assert_allclose(np.asarray(out1[:, :, :t + 1]),
                               np.asarray(out2[:, :, :t + 1]), atol=1e-5)


def test_decode_matches_ref():
    q, k, v = qkv(s=1, sk=128)
    kv_len = jnp.array([57, 128])
    want = attention_ref(q, k, v, causal=False, kv_len=kv_len)
    got = decode_attention_xla(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_decode_window():
    q, k, v = qkv(s=1, sk=128)
    kv_len = jnp.array([100, 128])
    want = attention_ref(q, k, v, causal=False, kv_len=kv_len, window=None)
    # windowed decode only sees the last W entries
    got_w = decode_attention_xla(q, k, v, kv_len, window=16)
    ref_w = attention_ref(
        q, jnp.where(jnp.arange(128)[None, None, :, None]
                     < (kv_len - 1 - 16)[:, None, None, None], -1e9, k),
        v, causal=False, kv_len=kv_len)
    assert np.abs(np.asarray(got_w) - np.asarray(want)).max() > 1e-3
