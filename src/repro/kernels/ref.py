"""Pure-jnp oracles for every kernel (the ground truth everywhere).

These are deliberately naive: full-materialization attention, sequential SSM
recurrence, direct convolution.  Tests assert the Pallas kernels (interpret
mode) and the ``xla`` production impls against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def matmul_ref(x, w, out_dtype=None):
    out_dtype = out_dtype or x.dtype
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32)).astype(out_dtype)


def dotproduct_ref(x, y):
    return jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32))


def softmax_ref(x, axis=-1):
    x32 = x.astype(jnp.float32)
    m = jnp.max(x32, axis=axis, keepdims=True)
    e = jnp.exp(x32 - m)
    return (e / jnp.sum(e, axis=axis, keepdims=True)).astype(x.dtype)


def exp_ref(x):
    return jnp.exp(x)


def dropout_ref(x, bits, rate):
    """``bits``: uint32 random bits, same shape as x (precomputed; the Ara2
    kernel also streams its mask from memory)."""
    keep = (bits.astype(jnp.float32) / np.float32(2 ** 32)) >= rate
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def conv2d_ref(x, w):
    """x: (C, H, W), w: (C, K, K) -> (H-K+1, W-K+1); the paper's 3x7x7
    single-output-channel convolution."""
    c, h, ww = x.shape
    _, k, _ = w.shape
    out = jnp.zeros((h - k + 1, ww - k + 1), jnp.float32)
    for ci in range(c):
        for ki in range(k):
            for kj in range(k):
                out = out + w[ci, ki, kj] * x[ci, ki:h - k + 1 + ki, kj:ww - k + 1 + kj]
    return out


def jacobi2d_ref(x, steps=1):
    """5-point Jacobi sweeps on the interior; boundary kept."""
    for _ in range(steps):
        inner = 0.2 * (x[1:-1, 1:-1] + x[:-2, 1:-1] + x[2:, 1:-1]
                       + x[1:-1, :-2] + x[1:-1, 2:])
        x = x.at[1:-1, 1:-1].set(inner)
    return x


def dwt_haar_ref(x, levels=1):
    """1-D Haar DWT, in-place layout [approx | detail | detail ...]."""
    n = x.shape[-1]
    out = x.astype(jnp.float32)
    s = 1.0 / np.sqrt(2.0).astype(np.float32)
    length = n
    for _ in range(levels):
        even, odd = out[..., 0:length:2], out[..., 1:length:2]
        lo, hi = (even + odd) * s, (even - odd) * s
        out = out.at[..., :length // 2].set(lo).at[..., length // 2:length].set(hi)
        length //= 2
    return out


def pathfinder_ref(w):
    """w: (rows, cols) costs; returns min-path cost per column (the RiVec
    pathfinder DP: dst[j] = w[i,j] + min(src[j-1], src[j], src[j+1]))."""
    rows, cols = w.shape
    src = w[0]
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
    for i in range(1, rows):
        left = jnp.concatenate([jnp.array([big]), src[:-1]])
        right = jnp.concatenate([src[1:], jnp.array([big])])
        src = w[i] + jnp.minimum(src, jnp.minimum(left, right))
    return src


def fft_ref(x_re, x_im):
    v = jnp.fft.fft(x_re.astype(jnp.complex64) + 1j * x_im.astype(jnp.complex64))
    return jnp.real(v).astype(jnp.float32), jnp.imag(v).astype(jnp.float32)


def roi_align_ref(feat, rois, out_size=7, sampling=2):
    """feat: (C, H, W); rois: (R, 4) [y0, x0, y1, x1] in pixel coords.
    Returns (R, C, out_size, out_size) via average-pooled bilinear samples."""
    c, h, w = feat.shape

    def bilinear(y, x):
        y = jnp.clip(y, 0.0, h - 1.0)
        x = jnp.clip(x, 0.0, w - 1.0)
        y0 = jnp.clip(jnp.floor(y).astype(jnp.int32), 0, h - 2)
        x0 = jnp.clip(jnp.floor(x).astype(jnp.int32), 0, w - 2)
        dy, dx = y - y0, x - x0
        v00 = feat[:, y0, x0]
        v01 = feat[:, y0, x0 + 1]
        v10 = feat[:, y0 + 1, x0]
        v11 = feat[:, y0 + 1, x0 + 1]
        return (v00 * (1 - dy) * (1 - dx) + v01 * (1 - dy) * dx
                + v10 * dy * (1 - dx) + v11 * dy * dx)

    def one_roi(roi):
        y0, x0, y1, x1 = roi
        bin_h = (y1 - y0) / out_size
        bin_w = (x1 - x0) / out_size
        out = []
        for oy in range(out_size):
            row = []
            for ox in range(out_size):
                acc = 0.0
                for sy in range(sampling):
                    for sx in range(sampling):
                        y = y0 + (oy + (sy + 0.5) / sampling) * bin_h
                        x = x0 + (ox + (sx + 0.5) / sampling) * bin_w
                        acc = acc + bilinear(y, x)
                row.append(acc / (sampling * sampling))
            out.append(jnp.stack(row, axis=-1))
        return jnp.stack(out, axis=-2)

    return jax.vmap(one_roi)(rois)


# ---------------------------------------------------------------------------
# Attention / SSM oracles.
# ---------------------------------------------------------------------------

def attention_ref(q, k, v, *, causal=True, window=None, scale=None,
                  kv_len=None):
    """q: (B, Hq, Sq, D), k/v: (B, Hkv, Sk, D); GQA by head broadcast.
    ``window``: sliding-window size (None = full); ``kv_len``: effective kv
    length per batch for decode (positions >= kv_len masked)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq)[:, None] + (sk - sq)   # right-aligned query block
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask = mask[None] & (kpos[None] < kv_len[:, None, None])
        mask = mask[:, None]
    else:
        mask = mask[None, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, block_table, kv_len, *,
                               scale=None):
    """One-token attention against one layer's paged KV pool, fully
    materialized.  q: (B, Hq, 1, D); k_pool/v_pool: (n_blocks, Hkv, bs, D);
    block_table: (B, M); kv_len: (B,) — positions at or past it are masked.
    Gathers the whole table into a dense (B, Hkv, M*bs, D) cache and runs
    :func:`attention_ref`."""
    b = q.shape[0]
    _, hkv, bs, d = k_pool.shape
    m = block_table.shape[1]
    k = jnp.take(k_pool, block_table, axis=0)      # (B, M, Hkv, bs, D)
    v = jnp.take(v_pool, block_table, axis=0)
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, hkv, m * bs, d)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, hkv, m * bs, d)
    return attention_ref(q, k, v, causal=False, scale=scale, kv_len=kv_len)


def paged_prefill_attention_ref(q, k_pool, v_pool, block_table, q_start, *,
                                scale=None, window=None):
    """Causal chunk attention against a paged KV pool, fully materialized.

    q: (B, Hq, Sq, D) — one prompt chunk per batch row, whose first query
    sits at absolute position ``q_start[b]``; k_pool/v_pool:
    (n_blocks, Hkv, bs, D); block_table: (B, M) pool block ids mapping
    logical positions ``[j*bs, (j+1)*bs)``.  Query ``q_start + i`` attends
    every pool position ``<= q_start + i`` (the blocks written by earlier
    chunks plus this chunk's own block).  Gathers the whole table into a
    dense (B, Hkv, M*bs, D) cache — the deliberately naive oracle the
    production paths are tested against."""
    b, hq, sq, d = q.shape
    _, hkv, bs, _ = k_pool.shape
    m = block_table.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    k = jnp.take(k_pool, block_table, axis=0)      # (B, M, Hkv, bs, D)
    v = jnp.take(v_pool, block_table, axis=0)
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, hkv, m * bs, d)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, hkv, m * bs, d)
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = q_start[:, None] + jnp.arange(sq)[None, :]        # (B, Sq)
    kpos = jnp.arange(m * bs)[None, None, :]                 # (1, 1, M*bs)
    mask = kpos <= qpos[:, :, None]
    if window is not None:
        mask &= kpos > qpos[:, :, None] - window
    logits = jnp.where(mask[:, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(x, dt, a_log, b_mat, c_mat, *, d_skip=None, h0=None):
    """Mamba2 SSD, exact sequential recurrence (the oracle).

    x: (B, S, H, P), dt: (B, S, H), a_log: (H,) (A = -exp(a_log) < 0),
    b_mat/c_mat: (B, S, G, N) with H % G == 0, optional d_skip: (H,),
    h0: (B, H, P, N) initial state.  Returns (y, h_final).
    """
    bsz, s, h, p = x.shape
    _, _, g, n = b_mat.shape
    rep = h // g
    a = -jnp.exp(a_log.astype(jnp.float32))

    def step(h_state, inputs):
        xt, dtt, bt, ct = inputs  # (B,H,P), (B,H), (B,G,N), (B,G,N)
        decay = jnp.exp(dtt * a)                       # (B,H)
        bt_h = jnp.repeat(bt, rep, axis=1)             # (B,H,N)
        ct_h = jnp.repeat(ct, rep, axis=1)
        dx = (dtt[..., None] * xt)                     # (B,H,P)
        h_state = decay[..., None, None] * h_state + dx[..., None] * bt_h[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h_state, ct_h)
        return h_state, y

    h_state = jnp.zeros((bsz, h, p, n), jnp.float32) if h0 is None else h0
    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
          jnp.moveaxis(b_mat.astype(jnp.float32), 1, 0),
          jnp.moveaxis(c_mat.astype(jnp.float32), 1, 0))
    h_final, ys = jax.lax.scan(step, h_state, xs)
    y = jnp.moveaxis(ys, 0, 1)  # (B,S,H,P)
    if d_skip is not None:
        y = y + d_skip[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), h_final
