"""xLSTM blocks: chunkwise-parallel mLSTM (matrix memory) and recurrent
sLSTM (scalar memory), per arXiv:2405.04517.

The mLSTM chunked form mirrors the SSD kernel's intra/inter-chunk split
(C3's intra-lane/inter-lane structure): within a chunk the recurrence is a
decay-masked attention matmul; across chunks a (C, n, m) state is carried
with running-max stabilization of the exponential gates.  Decode is O(1)
per token, which qualifies the arch for ``long_500k``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .layers import PT, rmsnorm, silu


# ---------------------------------------------------------------------------
# mLSTM cell - chunkwise parallel (training / prefill).
# ---------------------------------------------------------------------------

def _mlstm_chunk(carry, qc, kc, vc, lf, li):
    """One chunk, one batch of heads.

    carry: (C (B,H,dk,dv), n (B,H,dk), m (B,H));
    qc/kc: (B,H,Q,dk), vc: (B,H,Q,dv); lf/li: (B,H,Q) log f / log i.
    Stored state is true state scaled by exp(-m)."""
    c_in, n_in, m_in = carry
    f_cum = jnp.cumsum(lf, axis=-1)                    # F_i, inclusive
    g = li - f_cum                                     # g_j
    m_tilde = jnp.maximum(m_in[..., None], jax.lax.cummax(g, axis=2))
    m_total = f_cum + m_tilde                          # recurrent m_t
    # intra-chunk decay matrix D_ij = exp(g_j - m_tilde_i), j <= i
    d_mat = jnp.exp(g[:, :, None, :] - m_tilde[:, :, :, None])
    q_idx = np.arange(lf.shape[-1])
    causal = (q_idx[:, None] >= q_idx[None, :])[None, None]
    d_mat = jnp.where(causal, d_mat, 0.0)
    s = jnp.einsum("bhid,bhjd->bhij", qc, kc) * d_mat  # masked scores
    inter_w = jnp.exp(m_in[..., None] - m_tilde)       # (B,H,Q)
    num = jnp.einsum("bhij,bhjv->bhiv", s, vc) \
        + inter_w[..., None] * jnp.einsum("bhid,bhdv->bhiv", qc, c_in)
    den = jnp.sum(s, axis=-1) + inter_w * jnp.einsum("bhid,bhd->bhi", qc, n_in)
    y = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_total))[..., None]
    # chunk-out state (stabilized at m_out = m_total[..., -1])
    m_last = m_tilde[..., -1]
    w_out = jnp.exp(g - m_last[..., None])             # (B,H,Q)
    c_out = jnp.einsum("bhjd,bhjv->bhdv", kc * w_out[..., None], vc) \
        + jnp.exp(m_in - m_last)[..., None, None] * c_in
    n_out = jnp.einsum("bhjd,bhj->bhd", kc, w_out) \
        + jnp.exp(m_in - m_last)[..., None] * n_in
    return (c_out, n_out, f_cum[..., -1] + m_last), y


def mlstm_parallel(q, k, v, i_gate, f_gate, *, chunk=256, state=None):
    """q/k: (B, H, S, dk), v: (B, H, S, dv), i_gate/f_gate: (B, H, S) raw.
    Any S: the S % chunk positions after the last whole chunk run as one
    shorter chunk.  Returns (y (B,H,S,dv), state)."""
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    nc = s // chunk
    k = k / np.sqrt(dk)
    lf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))
    li = i_gate.astype(jnp.float32)
    seqs = (q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), lf, li)

    def to_chunks(x):
        x = x[:, :, :nc * chunk]
        return jnp.moveaxis(
            x.reshape(b, h, nc, chunk, *x.shape[3:]), 2, 0)

    if state is None:
        state = (jnp.zeros((b, h, dk, dv), jnp.float32),
                 jnp.zeros((b, h, dk), jnp.float32),
                 jnp.full((b, h), -1e30, jnp.float32))

    # checkpoint the chunk body: the backward pass re-materializes the
    # (B,H,Q,Q) decay/score matrices per chunk instead of saving all of
    # them (they dominated xlstm train_4k memory, ~20 GB/device)
    body = jax.checkpoint(_mlstm_chunk)

    def step(carry, inp):
        return body(carry, *inp)

    state, ys = jax.lax.scan(step, state, tuple(map(to_chunks, seqs)))
    y = jnp.moveaxis(ys, 0, 2).reshape(b, h, nc * chunk, dv)
    if s % chunk:
        state, y_tail = body(state, *(x[:, :, nc * chunk:] for x in seqs))
        y = jnp.concatenate([y, y_tail], axis=2)
    return y.astype(v.dtype), state


def mlstm_step(state, q, k, v, i_gate, f_gate):
    """One-token recurrent step.  q/k: (B,H,dk), v: (B,H,dv), gates (B,H)."""
    c, n, m = state
    dk = q.shape[-1]
    k = k.astype(jnp.float32) / np.sqrt(dk)
    q = q.astype(jnp.float32)
    lf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))
    li = i_gate.astype(jnp.float32)
    m_new = jnp.maximum(lf + m, li)
    fp = jnp.exp(lf + m - m_new)
    ip = jnp.exp(li - m_new)
    c = fp[..., None, None] * c + ip[..., None, None] * \
        jnp.einsum("bhd,bhv->bhdv", k, v.astype(jnp.float32))
    n = fp[..., None] * n + ip[..., None] * k
    num = jnp.einsum("bhd,bhdv->bhv", q, c)
    den = jnp.einsum("bhd,bhd->bh", q, n)
    y = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return (c, n, m_new), y.astype(v.dtype)


# ---------------------------------------------------------------------------
# sLSTM cell - strictly recurrent scalar memory.
# ---------------------------------------------------------------------------

def slstm_scan(x_gates, r_w, state, *, segment: int = 64):
    """x_gates: (B, S, H, dh, 4) pre-activations [i, f, z, o] from the input
    path; r_w: (4, H, dh, dh) per-head recurrent weights;
    state: (c, n, h, m) each (B, H, dh).

    Two-level checkpointed scan: the backward pass re-runs one ``segment``
    at a time instead of saving per-step carries for the whole sequence
    (a 4096-step recurrence otherwise holds ~4 GB/layer of (c,n,h,m)
    snapshots)."""

    def step(carry, xt):
        c, n, h, m = carry
        rec = jnp.einsum("ghde,bhe->bghd", r_w, h)      # (B, 4, H, dh)
        it = xt[..., 0] + rec[:, 0]
        ft = xt[..., 1] + rec[:, 1]
        zt = xt[..., 2] + rec[:, 2]
        ot = xt[..., 3] + rec[:, 3]
        lf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(lf + m, it)
        ip = jnp.exp(it - m_new)
        fp = jnp.exp(lf + m - m_new)
        c = fp * c + ip * jnp.tanh(zt)
        n = fp * n + ip
        h = jax.nn.sigmoid(ot) * c / jnp.maximum(n, 1.0)
        return (c, n, h, m_new), h

    xs = jnp.moveaxis(x_gates.astype(jnp.float32), 1, 0)   # (S, B, H, dh, 4)
    s_len = xs.shape[0]
    seg = segment
    while s_len % seg:
        seg -= 1
    if seg <= 1 or s_len <= seg:
        state, hs = jax.lax.scan(step, state, xs)
        return jnp.moveaxis(hs, 0, 1), state            # (B, S, H, dh)
    xseg = xs.reshape(s_len // seg, seg, *xs.shape[1:])

    @jax.checkpoint
    def run_segment(carry, xss):
        return jax.lax.scan(step, carry, xss)

    state, hs = jax.lax.scan(run_segment, state, xseg)
    hs = hs.reshape(s_len, *hs.shape[2:])
    return jnp.moveaxis(hs, 0, 1), state                # (B, S, H, dh)


def slstm_init_state(b, h, dh):
    z = jnp.zeros((b, h, dh), jnp.float32)
    return (z, z, z, jnp.full((b, h, dh), -1e30, jnp.float32))


# ---------------------------------------------------------------------------
# Blocks.
# ---------------------------------------------------------------------------

def mlstm_block_templates(d_model: int, n_heads: int, pf: int = 2,
                          d_conv: int = 4):
    di = pf * d_model
    return {
        "norm": PT((d_model,), "zeros", ("embed",)),
        "up": PT((d_model, 2 * di), "scaled", ("embed", "dinner")),
        "conv_w": PT((d_conv, di), "scaled", (None, "dinner")),
        "conv_b": PT((di,), "zeros", ("dinner",)),
        # block-diagonal per-head projections (xLSTM paper): di^2/H params
        "wq": PT((n_heads, di // n_heads, di // n_heads), "scaled",
                 (None, None, "dinner")),
        "wk": PT((n_heads, di // n_heads, di // n_heads), "scaled",
                 (None, None, "dinner")),
        "wv": PT((n_heads, di // n_heads, di // n_heads), "scaled",
                 (None, None, "dinner")),
        "w_i": PT((di, n_heads), "scaled", ("dinner", None), dtype=jnp.float32),
        "w_f": PT((di, n_heads), "scaled", ("dinner", None), dtype=jnp.float32),
        "b_i": PT((n_heads,), "zeros", (None,), dtype=jnp.float32),
        "b_f": PT((n_heads,), "ones", (None,), dtype=jnp.float32),
        "hnorm": PT((di,), "zeros", ("dinner",)),
        "down": PT((di, d_model), "scaled", ("dinner", "embed")),
    }


def _mlstm_block_inner(p, x, n_heads, *, conv_state=None, mstate=None,
                       chunk=256, norm_eps=1e-6):
    from .mamba2 import _causal_conv
    b, s, d = x.shape
    h = rmsnorm(p["norm"], x, norm_eps)
    up = jnp.einsum("bsd,de->bse", h, p["up"])
    di = up.shape[-1] // 2
    xm, z = up[..., :di], up[..., di:]
    xc, new_conv = _causal_conv(xm, p["conv_w"], p["conv_b"],
                                conv_state=conv_state)
    dh = di // n_heads
    xch = xc.reshape(b, s, n_heads, dh)
    xmh = xm.reshape(b, s, n_heads, dh)
    q = jnp.einsum("bshd,hde->bhse", xch, p["wq"])
    k = jnp.einsum("bshd,hde->bhse", xch, p["wk"])
    v = jnp.einsum("bshd,hde->bhse", xmh, p["wv"])
    ig = jnp.einsum("bse,eh->bsh", xc.astype(jnp.float32), p["w_i"]) + p["b_i"]
    fg = jnp.einsum("bse,eh->bsh", xc.astype(jnp.float32), p["w_f"]) + p["b_f"]
    y, mstate = mlstm_parallel(q, k, v, ig.transpose(0, 2, 1),
                               fg.transpose(0, 2, 1), chunk=chunk,
                               state=mstate)
    y = y.transpose(0, 2, 1, 3).reshape(b, s, di)
    y = rmsnorm(p["hnorm"], y, norm_eps) * silu(z)
    return x + jnp.einsum("bse,ed->bsd", y, p["down"]), (new_conv, mstate)


def mlstm_block(p, x, n_heads, **kw):
    out, _ = _mlstm_block_inner(p, x, n_heads, **kw)
    return out


def mlstm_block_with_state(p, x, n_heads, conv_state, mstate, **kw):
    return _mlstm_block_inner(p, x, n_heads, conv_state=conv_state,
                              mstate=mstate, **kw)


def mlstm_block_decode(p, x, n_heads, conv_state, mstate, *, norm_eps=1e-6):
    """One-token mLSTM block step.  x: (B, 1, d); conv_state: (B, K-1, di);
    mstate: (C, n, m)."""
    b = x.shape[0]
    h = rmsnorm(p["norm"], x, norm_eps)
    up = jnp.einsum("bsd,de->bse", h, p["up"])
    di = up.shape[-1] // 2
    xm, z = up[..., :di], up[..., di:]
    xp = jnp.concatenate([conv_state.astype(xm.dtype), xm], axis=1)
    xc = silu(jnp.einsum("bkc,kc->bc", xp, p["conv_w"]) + p["conv_b"])
    new_conv = xp[:, 1:, :]
    dh = di // n_heads
    xch = xc.reshape(b, n_heads, dh)
    xmh = xm[:, 0].reshape(b, n_heads, dh)
    q = jnp.einsum("bhd,hde->bhe", xch, p["wq"])
    k = jnp.einsum("bhd,hde->bhe", xch, p["wk"])
    v = jnp.einsum("bhd,hde->bhe", xmh, p["wv"])
    ig = jnp.einsum("be,eh->bh", xc.astype(jnp.float32), p["w_i"]) + p["b_i"]
    fg = jnp.einsum("be,eh->bh", xc.astype(jnp.float32), p["w_f"]) + p["b_f"]
    mstate, y = mlstm_step(mstate, q, k, v, ig, fg)
    y = y.reshape(b, 1, di)
    y = rmsnorm(p["hnorm"], y, norm_eps) * silu(z)
    return x + jnp.einsum("bse,ed->bsd", y, p["down"]), new_conv, mstate


def slstm_block_decode(p, x, n_heads, conv_state, state, *, norm_eps=1e-6):
    """One-token sLSTM block step.  conv_state: (B, K-1, d)."""
    b, _, d = x.shape
    dh = d // n_heads
    h = rmsnorm(p["norm"], x, norm_eps)
    xp = jnp.concatenate([conv_state.astype(h.dtype), h], axis=1)
    xc = silu(jnp.einsum("bkc,kc->bc", xp, p["conv_w"]) + p["conv_b"])
    new_conv = xp[:, 1:, :]
    gates = jnp.einsum("bd,dg->bg", xc, p["w_gates"]).astype(jnp.float32)
    gates = gates.reshape(b, 1, n_heads, dh, 4)
    hs, state = slstm_scan(gates, p["r_w"], state)
    y = hs.reshape(b, 1, d).astype(x.dtype)
    y = rmsnorm(p["gnorm"], y, norm_eps)
    return x + jnp.einsum("bsd,de->bse", y, p["out"]), new_conv, state


def slstm_block_templates(d_model: int, n_heads: int, d_conv: int = 4):
    return {
        "norm": PT((d_model,), "zeros", ("embed",)),
        "conv_w": PT((d_conv, d_model), "scaled", (None, "embed")),
        "conv_b": PT((d_model,), "zeros", ("embed",)),
        "w_gates": PT((d_model, d_model * 4), "scaled", ("embed", "dinner")),
        "r_w": PT((4, n_heads, d_model // n_heads, d_model // n_heads),
                  "scaled", (None, None, None, None), dtype=jnp.float32),
        "gnorm": PT((d_model,), "zeros", ("embed",)),
        "out": PT((d_model, d_model), "scaled", ("embed", "embed")),
    }


def slstm_block(p, x, n_heads, *, conv_state=None, state=None,
                norm_eps=1e-6, return_state=False):
    from .mamba2 import _causal_conv
    b, s, d = x.shape
    dh = d // n_heads
    h = rmsnorm(p["norm"], x, norm_eps)
    xc, new_conv = _causal_conv(h, p["conv_w"], p["conv_b"],
                                conv_state=conv_state)
    gates = jnp.einsum("bsd,dg->bsg", xc, p["w_gates"]).astype(jnp.float32)
    gates = gates.reshape(b, s, n_heads, dh, 4)
    if state is None:
        state = slstm_init_state(b, n_heads, dh)
    hs, state = slstm_scan(gates, p["r_w"], state)
    y = hs.reshape(b, s, d).astype(x.dtype)
    y = rmsnorm(p["gnorm"], y, norm_eps)
    out = x + jnp.einsum("bsd,de->bse", y, p["out"])
    if return_state:
        return out, (new_conv, state)
    return out
