"""Mamba2 SSD (state-space dual) chunked scan.

The TPU adaptation story (DESIGN.md §2): the sequence is chunked so that the
intra-chunk work becomes MXU matmuls (the SSD insight) and the inter-chunk
recurrence is a short scan — the same intra-lane / inter-lane split as the
paper's 3-step reduction (C3).  When the sequence axis is sharded, the chunk
boundary hand-off is a slide-by-1 (C2's cheapest configuration).

Semantics (oracle: ``ref.ssd_ref``): per head h with A = -exp(a_log):
  h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t outer B_t ;   y_t = C_t . h_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _chunk_body(h_in, xc, dtc, a, bc, cc):
    """One chunk, all heads vectorized.

    xc: (Q, H, P), dtc: (Q, H), a: (H,), bc/cc: (Q, H, N), h_in: (H, P, N).
    Returns (y (Q, H, P), h_out)."""
    dA = dtc * a                                   # (Q, H)
    s = jnp.cumsum(dA, axis=0)                     # inclusive log-decay
    st = s.T                                       # (H, Q)
    # intra-chunk: scores[h, i, j] = (C_i . B_j) * exp(s_i - s_j), j <= i
    cb = jnp.einsum("ihn,jhn->hij", cc, bc)
    ii = jnp.arange(s.shape[0])
    causal = (ii[:, None] >= ii[None, :])[None]
    decay = jnp.exp(st[:, :, None] - st[:, None, :])
    scores = jnp.where(causal, cb * decay, 0.0)
    dtx = dtc[..., None] * xc                      # (Q, H, P)
    y = jnp.einsum("hij,jhp->ihp", scores, dtx)
    # inter-chunk: contribution of the carried state
    y = y + jnp.exp(st).T[..., None] * jnp.einsum("ihn,hpn->ihp", cc, h_in)
    # state update
    decay_out = jnp.exp(st[:, -1:] - st)           # (H, Q)
    dh = jnp.einsum("hj,jhp,jhn->hpn", decay_out, dtx, bc)
    h_out = jnp.exp(st[:, -1])[:, None, None] * h_in + dh
    return y, h_out


def ssd_xla(x, dt, a_log, b_mat, c_mat, *, d_skip=None, h0=None, chunk=64):
    """Chunked SSD scan in pure jnp (production path; differentiable).

    x: (B, S, H, P), dt: (B, S, H), a_log: (H,), b_mat/c_mat: (B, S, G, N).
    Returns (y, h_final (B, H, P, N))."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    rep = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    a = -jnp.exp(a_log.astype(jnp.float32))

    f32 = jnp.float32
    xc = jnp.moveaxis(x.astype(f32).reshape(bsz, nc, chunk, h, p), 1, 0)
    dtc = jnp.moveaxis(dt.astype(f32).reshape(bsz, nc, chunk, h), 1, 0)
    bc = jnp.moveaxis(b_mat.astype(f32).reshape(bsz, nc, chunk, g, n), 1, 0)
    cc = jnp.moveaxis(c_mat.astype(f32).reshape(bsz, nc, chunk, g, n), 1, 0)

    body = jax.vmap(_chunk_body, in_axes=(0, 0, 0, None, 0, 0))

    # checkpoint per chunk: backward re-materializes the (B,H,Q,Q)
    # decay/score blocks instead of saving all nc of them (zamba2 train_4k
    # held ~17 GB/device of them before this; see EXPERIMENTS.md §Perf)
    @jax.checkpoint
    def step(h_state, inputs):
        xb, dtb, bb, cb_ = inputs
        bb = jnp.repeat(bb, rep, axis=2)           # (B, Q, H, N)
        cb_ = jnp.repeat(cb_, rep, axis=2)
        y, h_state = body(h_state, xb, dtb, a, bb, cb_)
        return h_state, y

    h_state = (jnp.zeros((bsz, h, p, n), f32) if h0 is None
               else h0.astype(f32))
    h_final, ys = jax.lax.scan(step, h_state, (xc, dtc, bc, cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p)
    if d_skip is not None:
        y = y + d_skip[None, None, :, None] * x.astype(f32)
    return y.astype(x.dtype), h_final


def ssd_step_xla(h_state, xt, dtt, a_log, bt, ct, *, d_skip=None):
    """Single-token recurrent step (decode path, O(1) per token).

    h_state: (B, H, P, N), xt: (B, H, P), dtt: (B, H), bt/ct: (B, G, N)."""
    h = xt.shape[1]
    rep = h // bt.shape[1]
    a = -jnp.exp(a_log.astype(jnp.float32))
    bt = jnp.repeat(bt.astype(jnp.float32), rep, axis=1)
    ct = jnp.repeat(ct.astype(jnp.float32), rep, axis=1)
    decay = jnp.exp(dtt.astype(jnp.float32) * a)
    dx = dtt[..., None].astype(jnp.float32) * xt.astype(jnp.float32)
    h_state = (decay[..., None, None] * h_state
               + dx[..., None] * bt[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", h_state, ct)
    if d_skip is not None:
        y = y + d_skip[None, :, None] * xt.astype(jnp.float32)
    return y.astype(xt.dtype), h_state


# ---------------------------------------------------------------------------
# Pallas kernel: grid (B, H, n_chunks), state carried in VMEM scratch across
# the sequential chunk axis.
# ---------------------------------------------------------------------------

def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref,
                *, nc: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xc = x_ref[0, :, 0, :].astype(jnp.float32)     # (Q, P)
    dtc = dt_ref[0, :, 0].astype(jnp.float32)      # (Q,)
    a = a_ref[0].astype(jnp.float32)               # scalar
    bc = b_ref[0, :, 0, :].astype(jnp.float32)     # (Q, N)
    cc = c_ref[0, :, 0, :].astype(jnp.float32)     # (Q, N)

    dA = dtc * a
    s = jnp.cumsum(dA)
    cb = jnp.dot(cc, bc.T, preferred_element_type=jnp.float32)   # (Q, Q)
    q = s.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    scores = jnp.where(ii >= jj, cb * jnp.exp(s[:, None] - s[None, :]), 0.0)
    dtx = dtc[:, None] * xc                         # (Q, P)
    h_in = h_ref[...]                               # (P, N)
    y = jnp.dot(scores, dtx, preferred_element_type=jnp.float32)
    y = y + jnp.exp(s)[:, None] * jnp.dot(cc, h_in.T,
                                          preferred_element_type=jnp.float32)
    decay_out = jnp.exp(s[-1] - s)                  # (Q,)
    dh = jnp.dot((decay_out[:, None] * dtx).T, bc,
                 preferred_element_type=jnp.float32)
    h_new = jnp.exp(s[-1]) * h_in + dh
    h_ref[...] = h_new
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nc - 1)
    def _flush():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, a_log, b_mat, c_mat, *, chunk=64, interpret=False):
    """Pallas SSD (TPU target).  Same contract as ``ssd_xla`` minus
    d_skip/h0 (applied by the wrapper)."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    rep = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    a = -jnp.exp(a_log.astype(jnp.float32))
    grid = (bsz, h, nc)
    y, h_final = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b, hh, c: (b, c, hh, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, hh, c: (b, c, hh)),
            pl.BlockSpec((1,), lambda b, hh, c: (hh,)),
            pl.BlockSpec((1, chunk, 1, n), lambda b, hh, c, r=rep: (b, c, hh // r, 0)),
            pl.BlockSpec((1, chunk, 1, n), lambda b, hh, c, r=rep: (b, c, hh // r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b, hh, c: (b, c, hh, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, h, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a, b_mat, c_mat)
    return y, h_final
