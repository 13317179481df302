"""Attention kernels: Pallas flash forward (TPU target) + chunked-scan XLA
implementation (production path on CPU / for dry-run lowering; differentiable,
O(S) memory via online softmax — never materializes the S x S score matrix).

GQA is native: q (B, Hq, S, D) against k/v (B, Hkv, S, D), Hq % Hkv == 0.
Supports causal masking, sliding windows (gemma3's 5:1 local:global pattern)
and per-batch effective kv lengths (serving).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Pallas flash-attention forward.
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int | None,
                  bq: int, bk: int, sq: int, sk: int, kv_steps: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)           # (bk, d)
    logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    qpos = pl.program_id(2) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0) + (sk - sq)       # right-aligned queries
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < sk                              # keys padded to the tile
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(logits, axis=-1)[:, None]     # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)[:, None]
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...] = m_new, l_new

    @pl.when(ik == kv_steps - 1)
    def _flush():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None, scale=None,
                           bq=128, bk=128, interpret=False):
    """Any sq/sk: a length that is not a multiple of its tile is padded up
    to one; the kernel masks the padded keys (``kpos < sk``) and the
    padded query rows are sliced off the output."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    scale = float(scale if scale is not None else 1.0 / np.sqrt(d))
    bq, bk = min(bq, sq), min(bk, sk)
    q_pad, k_pad = _pad_to(q, bq), _pad_to(k, bk)
    v_pad = _pad_to(v, bk)
    kv_steps = k_pad.shape[2] // bk
    grid = (b, hq, q_pad.shape[2] // bq, kv_steps)
    kern = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             window=window, bq=bq, bk=bk, sq=sq, sk=sk,
                             kv_steps=kv_steps)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, iq, ik, g_=g: (b_, h // g_, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, iq, ik, g_=g: (b_, h // g_, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q_pad.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q_pad, k_pad, v_pad)
    return out[:, :, :sq]


def _pad_to(x, tile):
    """Right-pad axis 2 (the sequence axis) of ``x`` to a multiple of
    ``tile`` with zeros."""
    pad = -x.shape[2] % tile
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


# ---------------------------------------------------------------------------
# Chunked-scan XLA implementation (flash algorithm in pure jnp) with a
# custom-VJP flash backward: residuals are O(S) (out + logsumexp), gradients
# recompute score blocks kv-chunk-wise - the standard flash-attention
# backward, in jnp.  Without this, scan-of-softmax saves O(S^2) residuals
# and a 4k-context training step needs ~15 GB/device (measured in the
# dry-run; see EXPERIMENTS.md §Perf).
# ---------------------------------------------------------------------------

def _mask_block(qpos, kpos, causal, window):
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), jnp.bool_)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    return mask


def _mask_block_f(qpos, kpos, causal, window_f, sk_valid):
    """Float-window variant: window rides as an f32 operand so traced
    per-layer windows (gemma3's 5:1 pattern under scan) work through the
    custom-VJP.  1e30 disables the window.  Keys at or past ``sk_valid``
    are tile padding."""
    mask = jnp.broadcast_to(kpos[None, :] < sk_valid,
                            (qpos.shape[0], kpos.shape[0]))
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    mask &= kpos[None, :].astype(jnp.float32) \
        > qpos[:, None].astype(jnp.float32) - window_f
    return mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q5, kc, vc, window_f, scale, causal, q_offset, kv_chunk,
           sk_valid):
    out, _ = _flash_fwd_impl(q5, kc, vc, window_f, scale, causal, q_offset,
                             kv_chunk, sk_valid)
    return out


def _flash_fwd_impl(q5, kc, vc, window_f, scale, causal, q_offset, kv_chunk,
                    sk_valid):
    """q5: (B, Hkv, G, Sq, D) fp32; kc/vc: (B, Hkv, Sk, D) fp32, Sk a
    multiple of kv_chunk whose keys from ``sk_valid`` on are padding.
    Returns (out, lse) with lse: (B, Hkv, G, Sq, 1)."""
    b, hkv, g, sq, d = q5.shape
    sk = kc.shape[2]
    nk = sk // kv_chunk
    qpos = q_offset + jnp.arange(sq)
    qf = q5 * scale

    def kv_step(carry, ik):
        m_prev, l_prev, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(kc, ik * kv_chunk, kv_chunk, 2)
        vb = jax.lax.dynamic_slice_in_dim(vc, ik * kv_chunk, kv_chunk, 2)
        kpos = ik * kv_chunk + jnp.arange(kv_chunk)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kb)
        mask = _mask_block_f(qpos, kpos, causal, window_f,
                             sk_valid)[None, None, None]
        logits = jnp.where(mask, logits, NEG_INF)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p, vb)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, g, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out, lse


def _flash_fwd(q5, kc, vc, window_f, scale, causal, q_offset, kv_chunk,
               sk_valid):
    out, lse = _flash_fwd_impl(q5, kc, vc, window_f, scale, causal, q_offset,
                               kv_chunk, sk_valid)
    return out, (q5, kc, vc, window_f, out, lse)


def _flash_bwd(scale, causal, q_offset, kv_chunk, sk_valid, res, dout):
    q5, kc, vc, window_f, out, lse = res
    b, hkv, g, sq, d = q5.shape
    sk = kc.shape[2]
    nk = sk // kv_chunk
    qpos = q_offset + jnp.arange(sq)
    qf = q5 * scale
    delta = jnp.sum(dout * out, axis=-1, keepdims=True)   # (B,Hkv,G,Sq,1)

    def kv_step(dq_acc, ik):
        kb = jax.lax.dynamic_slice_in_dim(kc, ik * kv_chunk, kv_chunk, 2)
        vb = jax.lax.dynamic_slice_in_dim(vc, ik * kv_chunk, kv_chunk, 2)
        kpos = ik * kv_chunk + jnp.arange(kv_chunk)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kb)
        mask = _mask_block_f(qpos, kpos, causal, window_f,
                             sk_valid)[None, None, None]
        logits = jnp.where(mask, logits, NEG_INF)
        p = jnp.exp(logits - lse)                          # (B,Hkv,G,Sq,K)
        dv = jnp.einsum("bhgqk,bhgqd->bhkd", p, dout)
        dp = jnp.einsum("bhgqd,bhkd->bhgqk", dout, vb)
        ds = p * (dp - delta)
        dq_acc = dq_acc + jnp.einsum("bhgqk,bhkd->bhgqd", ds, kb) * scale
        dk = jnp.einsum("bhgqk,bhgqd->bhkd", ds, qf)
        return dq_acc, (dk, dv)

    dq0 = jnp.zeros_like(q5)
    dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, hkv, sk, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, hkv, sk, d)
    return dq, dk, dv, jnp.zeros((), jnp.float32)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention_xla(q, k, v, *, causal=True, window=None, scale=None,
                  kv_len=None, q_chunk=1024, kv_chunk=1024):
    """Flash attention in jnp: q-chunked outer map, custom-VJP kv-chunked
    inner scan.  O(S) residuals; peak temp = B*Hq*q_chunk*kv_chunk logits.
    Any sq/sk: the last q chunk may be short, and K/V are zero-padded to a
    multiple of ``kv_chunk`` with the padded keys masked."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    scale = float(scale if scale is not None else 1.0 / np.sqrt(d))
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    k, v = _pad_to(k, kv_chunk), _pad_to(v, kv_chunk)
    sk_pad = k.shape[2]

    if kv_len is not None:
        # serving path (no gradients): per-batch kv_len masking, plain scan
        # (kv_len <= sk, so the padded keys are masked too)
        return _attention_kvlen(q, k, v, causal=causal, window=window,
                                scale=scale, kv_len=kv_len,
                                kv_chunk=kv_chunk, q_offset=sk - sq)

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # Python-unrolled q-chunk loop: q_offset stays static, which (a) keeps
    # the custom-VJP nondiff args hashable and (b) lets causal chunks skip
    # KV blocks beyond their triangle entirely (no masked-out compute).
    outs = []
    for lo in range(0, sq, q_chunk):
        qlen = min(q_chunk, sq - lo)
        q_off = lo + (sk - sq)
        if causal:
            kv_hi = min(sk_pad, -(-(q_off + qlen) // kv_chunk) * kv_chunk)
        else:
            kv_hi = sk_pad
        qb = q[:, :, lo:lo + qlen]
        q5 = qb.astype(jnp.float32).reshape(b, hkv, g, qlen, d)
        wf = (jnp.float32(1e30) if window is None
              else jnp.asarray(window, jnp.float32))
        out = _flash(q5, kf[:, :, :kv_hi], vf[:, :, :kv_hi], wf, scale,
                     causal, q_off, kv_chunk, sk)
        outs.append(out.reshape(b, hq, qlen, d).astype(q.dtype))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)


def _attention_kvlen(q, k, v, *, causal, window, scale, kv_len, kv_chunk,
                     q_offset):
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    nk = sk // kv_chunk
    qpos = jnp.arange(sq) + q_offset
    qf = q.astype(jnp.float32).reshape(b, hkv, g, sq, d) * scale

    def kv_step(carry, ik):
        m_prev, l_prev, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, ik * kv_chunk, kv_chunk, 2)
        vb = jax.lax.dynamic_slice_in_dim(v, ik * kv_chunk, kv_chunk, 2)
        kpos = ik * kv_chunk + jnp.arange(kv_chunk)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kb.astype(jnp.float32))
        mask = _mask_block(qpos, kpos, causal, window)[None, None, None]
        mask = mask & (kpos[None, None, None, None, :]
                       < kv_len[:, None, None, None, None])
        logits = jnp.where(mask, logits, NEG_INF)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p,
                                       vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, g, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def decode_attention_xla(q, k_cache, v_cache, kv_len, *, scale=None,
                         window=None):
    """Single-token GQA attention against a (B, Hkv, Smax, D) cache.
    ``kv_len``: (B,) valid lengths (the new token is at kv_len-1)."""
    b, hq, _, d = q.shape
    _, hkv, smax, _ = k_cache.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d) * scale
    logits = jnp.einsum("bhgd,bhsd->bhgs", qf, k_cache.astype(jnp.float32))
    kpos = jnp.arange(smax)[None, :]
    mask = kpos < kv_len[:, None]
    if window is not None:
        mask &= kpos > (kv_len[:, None] - 1 - window)
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)
