"""GQA attention layer (qk-norm, qkv-bias, sliding-window variants) with
train / prefill / decode modes over the kernels in ``repro.kernels``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.act_sharding import constrain
from ..kernels import ops
from .layers import PT, apply_rope, rmsnorm


def attn_templates(cfg, *, bias: bool | None = None, d_model: int | None = None):
    d = d_model or cfg.d_model
    hd = cfg.head_dim_resolved
    bias = cfg.qkv_bias if bias is None else bias
    t = {
        "wq": PT((d, cfg.n_heads * hd), "scaled", ("embed", "qheads")),
        "wk": PT((d, cfg.n_kv_heads * hd), "scaled", ("embed", "kvheads")),
        "wv": PT((d, cfg.n_kv_heads * hd), "scaled", ("embed", "kvheads")),
        "wo": PT((cfg.n_heads * hd, d), "scaled", ("qheads", "embed")),
    }
    if bias:
        t["bq"] = PT((cfg.n_heads * hd,), "zeros", ("qheads",))
        t["bk"] = PT((cfg.n_kv_heads * hd,), "zeros", ("kvheads",))
        t["bv"] = PT((cfg.n_kv_heads * hd,), "zeros", ("kvheads",))
    if cfg.qk_norm:
        t["q_norm"] = PT((hd,), "zeros", (None,))
        t["k_norm"] = PT((hd,), "zeros", (None,))
    return t


def _project_qkv(p, x, cfg):
    b, s, _ = x.shape
    hd = cfg.head_dim_resolved
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = constrain(q, "heads")
    k = constrain(k, "heads")   # auto-replicates when Hkv < TP
    v = constrain(v, "heads")
    return q, k, v


def attn_forward(p, x, cfg, *, positions=None, window=None, causal=True,
                 cross_kv=None):
    """Full-sequence attention (training / encoder).  ``cross_kv``: optional
    (k, v) from an encoder (cross-attention skips RoPE and causality)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg) if cross_kv is None else (
        _project_q_only(p, x, cfg), *cross_kv)
    if cross_kv is None and cfg.rope_theta:
        pos = positions if positions is not None else jnp.arange(s)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    out = ops.attention(q, k, v, causal=causal and cross_kv is None,
                        window=window)
    out = constrain(out, "heads")
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


def _project_q_only(p, x, cfg):
    b, s, _ = x.shape
    hd = cfg.head_dim_resolved
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
    return q


def project_kv(p, x, cfg, *, positions=None, rope=True):
    """K/V projection only (cross-attention caches, prefill caches)."""
    b, s, _ = x.shape
    hd = cfg.head_dim_resolved
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if rope and cfg.rope_theta:
        pos = positions if positions is not None else jnp.arange(s)
        k = apply_rope(k, pos, cfg.rope_theta)
    return k, v


def attn_prefill(p, x, cfg, *, cache_len: int, window=None):
    """Prefill: run causal attention AND return the (possibly longer) KV
    cache padded to ``cache_len``.  Returns (out, (k_cache, v_cache))."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    if cfg.rope_theta:
        pos = jnp.arange(s)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    out = ops.attention(q, k, v, causal=True, window=window)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    pad = cache_len - s
    if pad > 0:
        zeros = jnp.zeros((b, cfg.n_kv_heads, pad, k.shape[-1]), k.dtype)
        k = jnp.concatenate([k, zeros], axis=2)
        v = jnp.concatenate([v, zeros], axis=2)
    elif pad < 0:
        # ring-buffer cache shorter than the prompt: keep the last
        # ``cache_len`` keys at their ring slots (token t -> slot t % W)
        shift = s % cache_len
        k = jnp.roll(k[:, :, -cache_len:], shift, axis=2)
        v = jnp.roll(v[:, :, -cache_len:], shift, axis=2)
    return out, (k, v)


def _project_decode_qkv(p, x, kv_len, cfg):
    """Single-token q/k/v projection with RoPE at position ``kv_len``
    ((B,) vector or scalar).  Shared by the dense and paged decode paths so
    both layouts see bitwise-identical projections."""
    b = x.shape[0]
    hd = cfg.head_dim_resolved
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, 1, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.rope_theta:
        pos = kv_len.reshape(b, 1) if kv_len.ndim else jnp.full((b, 1), kv_len)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    return q, k, v


def attn_decode(p, x, k_cache, v_cache, kv_len, cfg, *, window=None,
                ring: bool = False):
    """One-token decode.  x: (B, 1, D); the new token's position is
    kv_len (0-based) and the caches are updated in place at that slot.
    ``ring=True``: the cache is a ring buffer of its full length W; the new
    kv goes to slot pos % W and attention covers min(pos+1, W) entries
    (slot order is irrelevant to softmax; keys carry absolute RoPE).
    Returns (out, k_cache, v_cache)."""
    b = x.shape[0]
    q, k, v = _project_decode_qkv(p, x, kv_len, cfg)
    # scatter the new kv at slot kv_len: in-place dynamic slice for a shared
    # scalar position (the serving engine's layout), one-hot blend otherwise
    w_cache = k_cache.shape[2]
    if kv_len.ndim == 0:
        slot = kv_len % w_cache if ring else kv_len
        attend = (jnp.minimum(kv_len + 1, w_cache) if ring else kv_len + 1)
        pos_b = jnp.full((b,), attend)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot, 2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot, 2)
    else:
        slot = kv_len % w_cache if ring else kv_len
        pos_b = jnp.minimum(kv_len + 1, w_cache) if ring else kv_len + 1
        hot = jax.nn.one_hot(slot, w_cache, dtype=k_cache.dtype)
        k_cache = (k_cache * (1 - hot)[:, None, :, None]
                   + hot[:, None, :, None] * k)
        v_cache = (v_cache * (1 - hot)[:, None, :, None]
                   + hot[:, None, :, None] * v)
    out = ops.decode_attention(q, k_cache, v_cache, pos_b,
                               window=None if ring else window)
    out = out.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), k_cache, v_cache


def attn_decode_paged(p, x, k_pool, v_pool, layer, block_table, kv_len,
                      cfg, *, window=None):
    """One-token decode against layer ``layer`` of the stacked paged KV
    pool.  x: (B, 1, D); k_pool/v_pool: (L, n_blocks, Hkv, block_size, D),
    the whole stack; layer: int32 scalar; block_table: (B, max_blocks)
    int32; kv_len: (B,) current lengths.

    The new token's KV lands in pool block ``block_table[b, kv_len // bs]``
    of the layer at offset ``kv_len % bs`` (the engine guarantees that
    entry is allocated before the step — idle slots' tables point at the
    null block, so their stale writes stay in scratch).  The stack is
    never sliced by layer: the kernel reads the layer's blocks straight
    from it, and the write moves whole blocks (gather the B target blocks,
    select the token in at its offset, scatter the blocks back), which
    XLA does in place on the carried stack.  A one-row scatter
    ``[layer, blk, :, off, :]`` instead makes layout assignment re-lay the
    whole stack for it and copy it every layer.
    Returns (out, k_pool, v_pool)."""
    b = x.shape[0]
    bs = k_pool.shape[3]
    q, k, v = _project_decode_qkv(p, x, kv_len, cfg)
    blk = jnp.take_along_axis(block_table, (kv_len // bs)[:, None],
                              axis=1)[:, 0]
    at_off = (jnp.arange(bs)[None, :]
              == (kv_len % bs)[:, None])[:, None, :, None]    # (B, 1, bs, 1)
    # rows own distinct blocks, so the block writes never collide (idle
    # rows all hit the null block — one of them wins, and nothing reads it)
    k_blk = jnp.where(at_off, k.astype(k_pool.dtype), k_pool[layer, blk])
    v_blk = jnp.where(at_off, v.astype(v_pool.dtype), v_pool[layer, blk])
    k_pool = k_pool.at[layer, blk].set(k_blk)
    v_pool = v_pool.at[layer, blk].set(v_blk)
    out = ops.paged_decode_attention(q, k_pool, v_pool, layer, block_table,
                                     kv_len + 1, window=window)
    out = out.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), k_pool, v_pool


def attn_prefill_paged(p, x, cfg, k_pool, v_pool, layer, bt_row, chunk, *,
                       window=None):
    """One ``block_size`` chunk of a paged prefill.  x: (1, bs, d_model) —
    the chunk's hidden states, covering absolute positions
    ``[chunk * bs, (chunk + 1) * bs)``; k_pool/v_pool:
    (L, n_blocks, Hkv, bs, hd), the whole stack, of which this call reads
    and writes layer ``layer``; bt_row: (max_blocks,) int32 block table of
    the request being prefilled; ``layer`` and ``chunk`` may be traced
    (one compile serves every chunk of every prompt).

    The chunk's K/V are projected from just these bs rows and written
    straight into pool block ``(layer, bt_row[chunk])`` in place — no
    ``(Hkv, prompt_len, D)`` cache and no per-layer pool is ever
    materialized — then the chunk's queries attend causally over blocks
    ``0..chunk`` through the block table
    (``ops.paged_prefill_attention``).  Returns (out, k_pool, v_pool)."""
    b, s, _ = x.shape
    bs = k_pool.shape[3]
    assert b == 1 and s == bs, (
        f"paged prefill runs one request in block_size chunks: got batch "
        f"{b}, chunk {s} vs block_size {bs}")
    q, k, v = _project_qkv(p, x, cfg)
    q_start = jnp.asarray(chunk, jnp.int32) * bs
    if cfg.rope_theta:
        pos = q_start + jnp.arange(s)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    blk = jnp.asarray(bt_row, jnp.int32)[jnp.asarray(chunk, jnp.int32)]
    at = (jnp.asarray(layer, jnp.int32), blk, 0, 0, 0)
    k_pool = jax.lax.dynamic_update_slice(k_pool, k[0][None, None], at)
    v_pool = jax.lax.dynamic_update_slice(v_pool, v[0][None, None], at)
    out = ops.paged_prefill_attention(q, k_pool, v_pool, layer, bt_row[None],
                                      q_start[None], window=window)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), k_pool, v_pool


def attn_cross_decode(p, x, k_cross, v_cross, cfg):
    """Decode-time cross-attention against fixed encoder KV."""
    b = x.shape[0]
    q = _project_q_only(p, x, cfg)
    kv_len = jnp.full((b,), k_cross.shape[2], jnp.int32)
    out = ops.decode_attention(q, k_cross, v_cross, kv_len)
    out = out.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])
