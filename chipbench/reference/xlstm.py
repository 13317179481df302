"""xLSTM language model in plain float32 (Beck et al., arXiv:2405.04517).

Layers come in groups: ``slstm_every - 1`` mLSTM blocks, then one sLSTM
block; a final RMSNorm and an untied output projection.

mLSTM block (pre up-projection, paper Fig. 10): ``h = RMSNorm(x)``, an
up-projection to two branches ``xm, z`` of width ``2 d``; a causal
depthwise convolution of width 4 and SiLU on ``xm`` gives ``xc``; per-head
block-diagonal projections give ``q, k`` from ``xc`` and ``v`` from ``xm``;
the input and forget gate pre-activations are linear in ``xc``.  The cell
is the paper's parallel form: ``log D[t, j] = sum_{j<u<=t} log sigmoid(f_u)
+ i_j`` for ``j <= t``, stabilised by ``m_t = max_j log D[t, j]``;
``C = (q k^T / sqrt(d_head)) * exp(log D - m)``, ``h_t = (C v)_t /
max(|sum_j C[t, j]|, exp(-m_t))``.  The output is normed, gated by
``silu(z)`` and projected down into the residual.

sLSTM block: ``h = RMSNorm(x)``, the same causal convolution and SiLU,
one projection to the four gate pre-activations (i, f, z, o) per unit,
plus per-head recurrent matrices on the previous hidden state; the
stabilised exponential-gating recurrence ``m_t = max(log sigmoid(f) +
m_{t-1}, i)``, ``c_t = f' c + i' tanh(z)``, ``n_t = f' n + i'``, ``h_t =
sigmoid(o) c_t / max(n_t, 1)``, then a norm and an output projection into
the residual.

Departures of the program from the paper, followed here so that the same
arrays mean the same function:

* every norm is an RMSNorm over the whole width with its gain stored as
  an offset ``w`` and applied as ``1 + w`` (the paper uses LayerNorm and a
  per-head GroupNorm in the cells' outputs);
* the mLSTM block has no learnable skip connection, and its gates read
  ``xc`` (the paper's reference code reads q, k and v);
* the sLSTM block has no gated feed-forward after it, and its output is
  ``c / max(n, 1)`` (the paper divides by ``n``);
* the group pattern is ``slstm_every - 1`` mLSTM blocks then one sLSTM
  block, so the sLSTM blocks sit at the end of each group (the paper
  places them at chosen positions; the configuration's 7:1 count is the
  paper's);
* the sLSTM state starts with ``m = -1e30``, and the embedding matrices
  are padded to a multiple of 256 rows (logits cover ``vocab_size``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + _f32(w))


def _conv_silu(x, w, b):
    """Causal depthwise convolution along time: x (S, C), w (K, C)."""
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], 0)
    s = x.shape[0]
    out = sum(xp[i:i + s] * _f32(w[i]) for i in range(k)) + _f32(b)
    return jax.nn.silu(out)


def _mlstm_cell(q, k, v, ig, fg, mm):
    """Parallel stabilised mLSTM: q, k, v (H, S, dh); gates (H, S)."""
    s = q.shape[1]
    lf = jax.nn.log_sigmoid(fg)
    cum = jnp.cumsum(lf, axis=-1)                           # (H, S)
    log_d = cum[:, :, None] - cum[:, None, :] + ig[:, None, :]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    log_d = jnp.where(causal[None], log_d, -jnp.inf)
    m = jnp.max(log_d, axis=-1)                             # (H, S)
    c = mm(q, k / np.sqrt(q.shape[-1]), "hsd,htd->hst") \
        * jnp.exp(log_d - m[..., None])
    num = mm(c, v, "hst,htd->hsd")
    den = jnp.sum(c, axis=-1)
    return num / jnp.maximum(jnp.abs(den), jnp.exp(-m))[..., None]


def _mlstm_block(p, x, n_heads, eps, mm):
    s = x.shape[0]
    h = _rms(x, p["norm"], eps)
    up = mm(h, _f32(p["up"]), "sd,de->se")
    di = up.shape[-1] // 2
    xm, z = up[:, :di], up[:, di:]
    xc = _conv_silu(xm, p["conv_w"], p["conv_b"])
    dh = di // n_heads
    xch = xc.reshape(s, n_heads, dh)
    xmh = xm.reshape(s, n_heads, dh)
    q = mm(xch, _f32(p["wq"]), "shd,hde->hse")
    k = mm(xch, _f32(p["wk"]), "shd,hde->hse")
    v = mm(xmh, _f32(p["wv"]), "shd,hde->hse")
    ig = (mm(xc, _f32(p["w_i"]), "se,eh->sh") + _f32(p["b_i"])).T
    fg = (mm(xc, _f32(p["w_f"]), "se,eh->sh") + _f32(p["b_f"])).T
    y = _mlstm_cell(q, k, v, ig, fg, mm)                    # (H, S, dh)
    y = y.transpose(1, 0, 2).reshape(s, di)
    y = _rms(y, p["hnorm"], eps) * jax.nn.silu(z)
    return x + mm(y, _f32(p["down"]), "se,ed->sd")


def _slstm_block(p, x, n_heads, eps, mm):
    s, d = x.shape
    dh = d // n_heads
    h = _rms(x, p["norm"], eps)
    xc = _conv_silu(h, p["conv_w"], p["conv_b"])
    gates = mm(xc, _f32(p["w_gates"]), "sd,dg->sg").reshape(s, n_heads,
                                                             dh, 4)
    r_w = _f32(p["r_w"])                                    # (4, H, dh, dh)

    def step(state, g):
        c, n, hh, m = state
        rec = jnp.einsum("ghde,he->ghd", r_w, hh)
        i, f = g[..., 0] + rec[0], g[..., 1] + rec[1]
        zt, o = g[..., 2] + rec[2], g[..., 3] + rec[3]
        lf = jax.nn.log_sigmoid(f)
        m_new = jnp.maximum(lf + m, i)
        ip, fp = jnp.exp(i - m_new), jnp.exp(lf + m - m_new)
        c = fp * c + ip * jnp.tanh(zt)
        n = fp * n + ip
        hh = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1.0)
        return (c, n, hh, m_new), hh

    zero = jnp.zeros((n_heads, dh), jnp.float32)
    init = (zero, zero, zero, jnp.full((n_heads, dh), -1e30, jnp.float32))
    _, hs = jax.lax.scan(step, init, gates)
    y = _rms(hs.reshape(s, d), p["gnorm"], eps)
    return x + mm(y, _f32(p["out"]), "sd,de->se")


def hidden(params, tokens, model: dict, mm):
    """Final-normed hidden states (S, d_model) of ``tokens`` (S,)."""
    n_heads, eps = model["n_heads"], model["norm_eps"]
    x = _f32(params["embed"]["embedding"])[tokens]

    def group(x, gp):
        mparams, sparams = gp

        def inner(x, lp):
            return _mlstm_block(lp, x, n_heads, eps, mm), None

        x, _ = jax.lax.scan(inner, x, mparams)
        return _slstm_block(sparams, x, n_heads, eps, mm), None

    x, _ = jax.lax.scan(group, x, (params["mlstm"], params["slstm"]))
    return _rms(x, params["final_norm"], eps)


def head(params, model: dict):
    """(d_model, vocab_size) output projection."""
    return _f32(params["lm_head"][:, :model["vocab_size"]])
