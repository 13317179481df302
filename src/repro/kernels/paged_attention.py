"""Paged-attention kernels (GQA over a blocked KV pool): decode + prefill.

KV lives in a global pool of fixed-size blocks — k_pool/v_pool:
``(n_blocks, n_kv_heads, block_size, head_dim)`` — and each request owns an
ordered *block table* row ``(max_blocks,)`` mapping its logical KV positions
``[i * block_size, (i+1) * block_size)`` to pool block ids (vLLM's
PagedAttention, Kwon et al. SOSP 2023).  Valid positions are a prefix:
``kv_len[b]`` masks everything at or beyond the current length, so trailing
table entries may point anywhere (the serving engine points them at the
null block).

Two implementations:

* ``pallas`` - scalar-prefetched block-table gather: the grid walks
  (batch, kv-head, block) and the k/v BlockSpec index_maps read the
  prefetched block table, so each grid step DMAs exactly the one pool block
  it needs; a flash-style online softmax accumulates across a request's
  blocks.  No (B, S, D) contiguous KV is ever materialized.
* ``xla`` - pure-jnp gather (``jnp.take`` of pool rows by block table)
  followed by the dense masked decode attention.  Runs anywhere (CPU /
  interpret) and serves as the correctness oracle in tests.

The **prefill** kernel (``paged_prefill_attention_*``) runs one
``block_size`` chunk of a prompt: causal self-attention of the chunk's
queries over every block the request has written so far — earlier chunks'
blocks plus the chunk's own, all reached through the block table.  The
serving engine writes each chunk's K/V straight into its pool block and
then calls this, so a prompt is prefilled without ever materializing a
dense ``(Hkv, prompt_len, D)`` cache:

* ``pallas`` - same scalar-prefetched gather as decode, walking
  (batch, kv-head, block) with a flash-style online softmax; blocks past
  the chunk (``j * bs > q_start + Sq - 1``) are skipped entirely, so a
  chunk at position p only pays for the ceil((p + Sq) / bs) blocks below
  its causal frontier.
* ``xla`` - a scan over table entries gathering *one* pool block per step
  (``jnp.take`` of a (B,) id vector) folded into an online softmax — the
  CPU production path, O(block) memory, never a whole-table gather.  The
  full-gather oracle lives in ``repro.kernels.ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, decode_attention_xla


# ---------------------------------------------------------------------------
# Pallas kernel.
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, kvlen_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, bs: int, g: int,
                  n_steps: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kvlen_ref[b]

    # valid positions are a prefix, so blocks at or past kv_len contribute
    # nothing — skip their compute entirely
    @pl.when(j * bs < kv_len)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)            # (g, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bs, d)
        logits = jnp.dot(q, k.T,
                         preferred_element_type=jnp.float32) * scale
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
        logits = jnp.where(kpos < kv_len, logits, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(logits, axis=-1)[:, None]      # (g, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)[:, None]
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_steps - 1)
    def _flush():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_pallas(q, k_pool, v_pool, block_table, kv_len, *,
                                  scale=None, interpret=False):
    """q: (B, Hq, 1, D); k_pool/v_pool: (N, Hkv, bs, D);
    block_table: (B, M) int32; kv_len: (B,) int32.  Returns (B, Hq, 1, D)."""
    b, hq, _, d = q.shape
    _, hkv, bs, _ = k_pool.shape
    g = hq // hkv
    m = block_table.shape[1]
    scale = float(scale if scale is not None else 1.0 / np.sqrt(d))
    # q-heads are grouped by kv head (consecutive g q-heads share a kv head)
    q4 = q[:, :, 0, :].reshape(b, hkv, g, d)
    kern = functools.partial(_paged_kernel, scale=scale, bs=bs, g=g,
                             n_steps=m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, m),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda b_, h, j, bt, kl: (b_, h, 0, 0)),
            # the block-table gather: grid step (b, h, j) pulls pool block
            # bt[b, j] for kv head h
            pl.BlockSpec((1, 1, bs, d),
                         lambda b_, h, j, bt, kl: (bt[b_, j], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda b_, h, j, bt, kl: (bt[b_, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b_, h, j, bt, kl: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      q4, k_pool, v_pool)
    return out.reshape(b, hq, 1, d)


# ---------------------------------------------------------------------------
# Pure-JAX reference (CPU production path + correctness oracle).
# ---------------------------------------------------------------------------

def paged_decode_attention_xla(q, k_pool, v_pool, block_table, kv_len, *,
                               scale=None, window=None):
    """Gather each request's blocks into contiguous (B, Hkv, M*bs, D) KV
    and run the dense masked decode attention.  Bitwise-identical math to
    the dense layout when M*bs equals the dense cache length (positions at
    or past kv_len are exact zeros in the softmax either way)."""
    b = q.shape[0]
    _, hkv, bs, d = k_pool.shape
    m = block_table.shape[1]
    k = jnp.take(k_pool, block_table, axis=0)      # (B, M, Hkv, bs, D)
    v = jnp.take(v_pool, block_table, axis=0)
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, hkv, m * bs, d)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, hkv, m * bs, d)
    return decode_attention_xla(q, k, v, kv_len, scale=scale, window=window)


# ---------------------------------------------------------------------------
# Prefill: one prompt chunk's causal attention over previously-written
# blocks (chunked prefill — the engine scatters the chunk's K/V into its
# pool block first, then every block <= the causal frontier is read back
# through the table).
# ---------------------------------------------------------------------------

def _paged_prefill_kernel(bt_ref, qstart_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, scale: float, bs: int,
                          g: int, sq: int, n_steps: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qstart_ref[b]

    # block j holds positions [j*bs, (j+1)*bs); the chunk's last query sits
    # at q_start + sq - 1, so later blocks are all-masked — skip them
    @pl.when(j * bs <= q_start + sq - 1)
    def _block():
        d = q_ref.shape[-1]
        q = q_ref[0, 0].astype(jnp.float32).reshape(g * sq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bs, d)
        logits = jnp.dot(q, k.T,
                         preferred_element_type=jnp.float32) * scale
        # row r is query position q_start + (r % sq) of head r // sq
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (g * sq, bs), 0) % sq
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (g * sq, bs), 1)
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(logits, axis=-1)[:, None]      # (g*sq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)[:, None]
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_steps - 1)
    def _flush():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                       ).reshape(g, sq, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention_pallas(q, k_pool, v_pool, block_table, q_start,
                                   *, scale=None, interpret=False):
    """q: (B, Hq, Sq, D) chunk queries starting at absolute position
    q_start[b]; k_pool/v_pool: (N, Hkv, bs, D); block_table: (B, M) int32;
    q_start: (B,) int32.  Returns (B, Hq, Sq, D).  Position 0 must be
    attendable (q_start >= 0 and causal), so block 0 always contributes —
    the online-softmax init never sees an all-masked first block."""
    b, hq, sq, d = q.shape
    _, hkv, bs, _ = k_pool.shape
    g = hq // hkv
    m = block_table.shape[1]
    scale = float(scale if scale is not None else 1.0 / np.sqrt(d))
    # q-heads are grouped by kv head (consecutive g q-heads share a kv head)
    q5 = q.reshape(b, hkv, g, sq, d)
    kern = functools.partial(_paged_prefill_kernel, scale=scale, bs=bs, g=g,
                             sq=sq, n_steps=m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, m),
        in_specs=[
            pl.BlockSpec((1, 1, g, sq, d),
                         lambda b_, h, j, bt, qs: (b_, h, 0, 0, 0)),
            # the block-table gather: grid step (b, h, j) pulls pool block
            # bt[b, j] for kv head h
            pl.BlockSpec((1, 1, bs, d),
                         lambda b_, h, j, bt, qs: (bt[b_, j], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda b_, h, j, bt, qs: (bt[b_, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, sq, d),
                               lambda b_, h, j, bt, qs: (b_, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g * sq, 1), jnp.float32),
            pltpu.VMEM((g * sq, 1), jnp.float32),
            pltpu.VMEM((g * sq, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_table.astype(jnp.int32), q_start.astype(jnp.int32),
      q5, k_pool, v_pool)
    return out.reshape(b, hq, sq, d)


def paged_prefill_attention_xla(q, k_pool, v_pool, block_table, q_start, *,
                                scale=None, window=None):
    """CPU production path: walk the block table gathering one pool block
    per step ((B, Hkv, bs, D) via ``jnp.take``) and fold it into a
    flash-style online softmax.  Peak KV-side temp is a single block — the
    whole-table dense gather only exists in the ``ref`` oracle — and the
    walk stops at the batch's furthest causal frontier instead of paying
    for every (fully-masked) trailing table entry."""
    b, hq, sq, d = q.shape
    _, hkv, bs, _ = k_pool.shape
    m = block_table.shape[1]
    g = hq // hkv
    scale = float(scale if scale is not None else 1.0 / np.sqrt(d))
    qpos = q_start[:, None] + jnp.arange(sq)[None, :]            # (B, Sq)
    qf = q.astype(jnp.float32).reshape(b, hkv, g, sq, d) * scale

    def kv_step(j, carry):
        m_prev, l_prev, acc = carry
        ids = jax.lax.dynamic_index_in_dim(block_table, j, 1,
                                           keepdims=False)       # (B,)
        kb = jnp.take(k_pool, ids, axis=0).astype(jnp.float32)
        vb = jnp.take(v_pool, ids, axis=0).astype(jnp.float32)
        kpos = j * bs + jnp.arange(bs)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kb)
        mask = kpos[None, None, :] <= qpos[:, :, None]           # (B, Sq, bs)
        if window is not None:
            mask &= kpos[None, None, :] > qpos[:, :, None] - window
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p, vb)
        return (m_new, l_new, acc)

    m0 = jnp.full((b, hkv, g, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    # blocks past the last query position contribute exact zeros — stop
    # there (traced bound: fori_loop lowers to while_loop; inference-only)
    n_live = jnp.minimum((jnp.max(q_start) + sq - 1) // bs + 1, m)
    (_, l, acc) = jax.lax.fori_loop(0, n_live, kv_step, (m0, l0, a0))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(b, hq, sq, d).astype(q.dtype)
