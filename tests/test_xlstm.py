"""xLSTM cells: chunkwise-parallel mLSTM vs the step recurrence; sLSTM scan;
the serving decode step against its scan-xs/ys form."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import build_model
from repro.models.layers import embed_lookup, rmsnorm
from repro.models.xlstm import (mlstm_block_decode, mlstm_parallel, mlstm_step,
                                slstm_block_decode, slstm_init_state,
                                slstm_scan)
from repro.models.xlstm_lm import XLSTM_STATE_AXES, xlstm_decode_step

KEY = jax.random.key(3)


def make(b=2, h=2, s=64, dk=8, dv=8):
    f = jax.random.fold_in
    q = jax.random.normal(f(KEY, 1), (b, h, s, dk))
    k = jax.random.normal(f(KEY, 2), (b, h, s, dk))
    v = jax.random.normal(f(KEY, 3), (b, h, s, dv))
    ig = jax.random.normal(f(KEY, 4), (b, h, s)) * 0.5
    fg = jax.random.normal(f(KEY, 5), (b, h, s)) * 0.5 + 2.0
    return q, k, v, ig, fg


def recurrent_oracle(q, k, v, ig, fg):
    b, h, s, dk = q.shape
    state = (jnp.zeros((b, h, dk, v.shape[-1])), jnp.zeros((b, h, dk)),
             jnp.full((b, h), -1e30))
    ys = []
    for t in range(s):
        state, y = mlstm_step(state, q[:, :, t], k[:, :, t], v[:, :, t],
                              ig[:, :, t], fg[:, :, t])
        ys.append(y)
    return jnp.stack(ys, axis=2), state


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_mlstm_parallel_matches_recurrence(chunk):
    q, k, v, ig, fg = make()
    want, wstate = recurrent_oracle(q, k, v, ig, fg)
    got, gstate = mlstm_parallel(q, k, v, ig, fg, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=1e-3)
    for a, b_ in zip(gstate, wstate):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4,
                                   rtol=1e-3)


@pytest.mark.parametrize("s", [37, 70])
def test_mlstm_parallel_ragged_length(s):
    """A length that is no multiple of the chunk (a 700-token prompt at
    the default chunk of 256) ends in one shorter chunk, exactly."""
    q, k, v, ig, fg = make(s=s)
    want, wstate = recurrent_oracle(q, k, v, ig, fg)
    got, gstate = mlstm_parallel(q, k, v, ig, fg, chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=1e-3)
    for a, b_ in zip(gstate, wstate):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4,
                                   rtol=1e-3)


def test_mlstm_chunk_invariance():
    q, k, v, ig, fg = make(s=96)
    y1, _ = mlstm_parallel(q, k, v, ig, fg, chunk=16)
    y2, _ = mlstm_parallel(q, k, v, ig, fg, chunk=48)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4,
                               rtol=1e-3)


def test_mlstm_state_carry():
    """Processing [first half] then [second half with carried state] equals
    processing the whole sequence."""
    q, k, v, ig, fg = make(s=64)
    full, _ = mlstm_parallel(q, k, v, ig, fg, chunk=16)
    h1, st = mlstm_parallel(q[:, :, :32], k[:, :, :32], v[:, :, :32],
                            ig[:, :, :32], fg[:, :, :32], chunk=16)
    h2, _ = mlstm_parallel(q[:, :, 32:], k[:, :, 32:], v[:, :, 32:],
                           ig[:, :, 32:], fg[:, :, 32:], chunk=16, state=st)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(full[:, :, 32:]),
                               atol=2e-4, rtol=1e-3)


def test_mlstm_exp_gate_stability():
    """Large input gates must not overflow (running-max stabilization)."""
    q, k, v, ig, fg = make(s=32)
    y, st = mlstm_parallel(q, k, v, ig + 40.0, fg, chunk=8)
    assert bool(jnp.isfinite(y).all())
    assert all(bool(jnp.isfinite(s).all()) for s in st)


def test_slstm_scan_shapes_and_stability():
    b, s, h, dh = 2, 16, 4, 8
    gates = jax.random.normal(jax.random.fold_in(KEY, 9), (b, s, h, dh, 4))
    r_w = jax.random.normal(jax.random.fold_in(KEY, 10), (4, h, dh, dh)) * 0.1
    hs, state = slstm_scan(gates, r_w, slstm_init_state(b, h, dh))
    assert hs.shape == (b, s, h, dh)
    assert bool(jnp.isfinite(hs).all())
    # recurrence actually feeds back: zeroing r_w changes outputs
    hs0, _ = slstm_scan(gates, r_w * 0.0, slstm_init_state(b, h, dh))
    assert float(jnp.abs(hs - hs0).max()) > 1e-4


def xs_ys_decode_step(params, cache, tokens, cfg):
    """The decode step as it was before the state moved into the scan
    carry: the stacked state enters both layer scans as xs and leaves as
    ys.  Kept as the reference the carried form must reproduce."""
    x = embed_lookup(params["embed"], tokens)

    def group_body(carry, inp):
        mparams, sparams, mc, mcc, mn, mm, sc, scc, sn, sh, sm = inp

        def inner(c, lp_state):
            lp, conv, cc, nn, m_ = lp_state
            out, conv, (cc, nn, m_) = mlstm_block_decode(
                lp, c, cfg.n_heads, conv, (cc, nn, m_),
                norm_eps=cfg.norm_eps)
            return out, (conv, cc, nn, m_)

        carry, mstates = jax.lax.scan(inner, carry,
                                      (mparams, mc, mcc, mn, mm))
        carry, s_conv, s_state = slstm_block_decode(
            sparams, carry, cfg.n_heads, sc, (scc, sn, sh, sm),
            norm_eps=cfg.norm_eps)
        return carry, (mstates, s_conv, *s_state)

    x, outs = jax.lax.scan(
        group_body, x,
        (params["mlstm"], params["slstm"], cache["m_conv"], cache["m_c"],
         cache["m_n"], cache["m_m"], cache["s_conv"], cache["s_c"],
         cache["s_n"], cache["s_h"], cache["s_m"]))
    (m_conv, m_c, m_n, m_m), s_conv, s_c, s_n, s_h, s_m = outs
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, -1].astype(jnp.float32),
                        params["lm_head"].astype(jnp.float32))
    logits = logits[:, :cfg.vocab_size]
    cache = {"m_conv": m_conv, "m_c": m_c, "m_n": m_n, "m_m": m_m,
             "s_conv": s_conv, "s_c": s_c, "s_n": s_n, "s_h": s_h,
             "s_m": s_m, "pos": cache["pos"] + 1}
    return logits, cache


def _slot_cache(model, batch, key):
    """A serving-layout cache with a different live state and position in
    every slot (the state as prefill leaves it: non-negative normaliser,
    finite stabiliser)."""
    shapes = model.cache_shapes(batch, 0)
    cache = {}
    for i, name in enumerate(XLSTM_STATE_AXES):
        s = shapes[name]
        v = jax.random.normal(jax.random.fold_in(key, i), s.shape)
        cache[name] = (jnp.abs(v) if name == "m_n" else v).astype(s.dtype)
    cache["pos"] = jnp.arange(batch, dtype=jnp.int32) * 7 + 3
    return cache


@pytest.mark.parametrize("slstm_every", [4, 8])
def test_decode_step_state_in_carry_matches_xs_ys(slstm_every):
    """The carried-state decode step gives the logits and every state leaf
    of the xs/ys form, step after step, jitted with the cache donated as
    the engine calls it."""
    cfg = dataclasses.replace(smoke_config("xlstm-350m"), n_layers=8,
                              slstm_every=slstm_every)
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    batch = 3
    ref_cache = _slot_cache(model, batch, jax.random.key(2))
    cache = jax.tree.map(jnp.copy, ref_cache)
    step = jax.jit(lambda p, c, t: xlstm_decode_step(p, c, t, cfg),
                   donate_argnums=(1,))
    ref_step = jax.jit(lambda p, c, t: xs_ys_decode_step(p, c, t, cfg))
    for t in range(4):
        toks = jax.random.randint(jax.random.fold_in(KEY, 20 + t),
                                  (batch, 1), 0, cfg.vocab_size)
        want_logits, ref_cache = ref_step(params, ref_cache, toks)
        logits, cache = step(params, cache, toks)
        assert bool(jnp.isfinite(logits).all())
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(want_logits), rtol=0,
                                   atol=1e-6)
        assert set(cache) == set(ref_cache)
        for name in cache:
            assert cache[name].shape == ref_cache[name].shape, name
            assert cache[name].dtype == ref_cache[name].dtype, name
            np.testing.assert_allclose(
                np.asarray(cache[name], np.float32),
                np.asarray(ref_cache[name], np.float32), rtol=0, atol=1e-6,
                err_msg=f"{name} after step {t}")
    np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                  np.arange(batch) * 7 + 3 + 4)
