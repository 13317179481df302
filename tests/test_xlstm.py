"""xLSTM cells: chunkwise-parallel mLSTM vs the step recurrence; sLSTM scan."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.xlstm import (mlstm_parallel, mlstm_step, slstm_init_state,
                                slstm_scan)

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
