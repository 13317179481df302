"""xLSTM language model: mLSTM blocks with an sLSTM block every
``slstm_every``-th layer (grouped scan: (k-1) mLSTM + 1 sLSTM per group).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.act_sharding import constrain
from .layers import (PT, embed_lookup, embed_templates, rmsnorm,
                     softmax_xent_chunked, stack_layers)
from .slot_state import make_slot_hooks
from .xlstm import (mlstm_block, mlstm_block_decode, mlstm_block_templates,
                    mlstm_block_with_state, slstm_block, slstm_block_decode,
                    slstm_block_templates, slstm_init_state)


def _groups(cfg):
    k = cfg.slstm_every
    assert cfg.n_layers % k == 0, "xlstm layer count must be a multiple of " \
                                  "slstm_every"
    return cfg.n_layers // k, k - 1  # (n_groups, mlstm per group)


def xlstm_templates(cfg):
    n_groups, m_per = _groups(cfg)
    return {
        "embed": embed_templates(cfg.padded_vocab, cfg.d_model),
        "mlstm": stack_layers(
            lambda: stack_layers(
                lambda: mlstm_block_templates(cfg.d_model, cfg.n_heads),
                m_per), n_groups),
        "slstm": stack_layers(
            lambda: slstm_block_templates(cfg.d_model, cfg.n_heads), n_groups),
        "final_norm": PT((cfg.d_model,), "zeros", ("embed",)),
        "lm_head": PT((cfg.d_model, cfg.padded_vocab), "scaled",
                      ("embed", "vocab")),
    }


def xlstm_backbone(params, x, cfg, *, remat=True):
    n_groups, m_per = _groups(cfg)

    def m_layer(lp, c):
        return mlstm_block(lp, c, cfg.n_heads, norm_eps=cfg.norm_eps)

    def s_layer(lp, c):
        return slstm_block(lp, c, cfg.n_heads, norm_eps=cfg.norm_eps)

    if remat:
        m_layer = jax.checkpoint(m_layer)
        s_layer = jax.checkpoint(s_layer)

    def group_body(carry, inp):
        mparams, sparams = inp

        def inner(c, lp):
            return constrain(m_layer(lp, c), "hidden"), None

        carry, _ = jax.lax.scan(inner, carry, mparams)
        carry = s_layer(sparams, carry)
        return constrain(carry, "hidden"), None

    x, _ = jax.lax.scan(group_body, x, (params["mlstm"], params["slstm"]))
    return x


def xlstm_loss(params, batch, cfg, *, remat=True, xent_chunk=512):
    x = embed_lookup(params["embed"], batch["tokens"])
    x = xlstm_backbone(params, x, cfg, remat=remat)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    loss, acc = softmax_xent_chunked(
        x, params["lm_head"], batch["labels"], chunk=xent_chunk,
        label_mask=batch.get("label_mask"),
        valid_vocab=cfg.vocab_size)
    return loss, {"loss": loss, "accuracy": acc}


# ---------------------------------------------------------------------------
# Serving.
#
# Decode state is O(1)/token and fully recurrent: per sequence it is a
# fixed-size tree of conv tails and (C, n, m) cell states.  Serving keeps
# it in a (…, B, …) per-slot layout — the mLSTM leaves are stacked
# (n_groups, m_per, B, …) by the grouped scan, the sLSTM leaves
# (n_groups, B, …) — so one slot's state is one index of each leaf and the
# continuous-batching slot hooks below admit/evict/reset one request at a
# time (see ``repro.models.slot_state``).
# ---------------------------------------------------------------------------

# batch axis of every cache leaf (the serving slot axis); ``pos`` is the
# implicit per-slot position vector
XLSTM_STATE_AXES = {
    "m_conv": 2, "m_c": 2, "m_n": 2, "m_m": 2,
    "s_conv": 1, "s_c": 1, "s_n": 1, "s_h": 1, "s_m": 1,
}

xlstm_cache_expand, xlstm_cache_slot_write, xlstm_cache_slot_reset = \
    make_slot_hooks(XLSTM_STATE_AXES)


def xlstm_cache_shapes(cfg, batch_size: int, cache_len: int,
                       dtype=jnp.bfloat16):
    del cache_len  # state size is context-independent (that's the point)
    n_groups, m_per = _groups(cfg)
    d = cfg.d_model
    di = 2 * d
    dh_m = di // cfg.n_heads
    dh_s = d // cfg.n_heads
    f32 = jnp.float32
    b = batch_size
    return {
        "m_conv": jax.ShapeDtypeStruct((n_groups, m_per, b, 3, di), dtype),
        "m_c": jax.ShapeDtypeStruct((n_groups, m_per, b, cfg.n_heads, dh_m,
                                     dh_m), f32),
        "m_n": jax.ShapeDtypeStruct((n_groups, m_per, b, cfg.n_heads, dh_m),
                                    f32),
        "m_m": jax.ShapeDtypeStruct((n_groups, m_per, b, cfg.n_heads), f32),
        "s_conv": jax.ShapeDtypeStruct((n_groups, b, 3, d), dtype),
        "s_c": jax.ShapeDtypeStruct((n_groups, b, cfg.n_heads, dh_s), f32),
        "s_n": jax.ShapeDtypeStruct((n_groups, b, cfg.n_heads, dh_s), f32),
        "s_h": jax.ShapeDtypeStruct((n_groups, b, cfg.n_heads, dh_s), f32),
        "s_m": jax.ShapeDtypeStruct((n_groups, b, cfg.n_heads, dh_s), f32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def xlstm_prefill(params, batch, cfg, *, cache_len=None):
    del cache_len
    x = embed_lookup(params["embed"], batch["tokens"])
    b, s, d = x.shape
    n_groups, m_per = _groups(cfg)
    di = 2 * d

    def group_body(carry, inp):
        mparams, sparams = inp

        def inner(c, lp):
            conv0 = jnp.zeros((b, 3, di), x.dtype)
            out, (conv, mstate) = mlstm_block_with_state(
                lp, c, cfg.n_heads, conv0, None, norm_eps=cfg.norm_eps)
            return out, (conv, *mstate)

        carry, mstates = jax.lax.scan(inner, carry, mparams)
        carry, (s_conv, s_state) = slstm_block(
            sparams, carry, cfg.n_heads, conv_state=None, state=None,
            norm_eps=cfg.norm_eps, return_state=True)
        return carry, (mstates, s_conv, s_state)

    x, (mstates, s_convs, s_states) = jax.lax.scan(
        group_body, x, (params["mlstm"], params["slstm"]))
    m_conv, m_c, m_n, m_m = mstates
    s_c, s_n, s_h, s_m = s_states
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, -1].astype(jnp.float32),
                        params["lm_head"].astype(jnp.float32))
    logits = logits[:, :cfg.vocab_size]
    cache = {"m_conv": m_conv, "m_c": m_c, "m_n": m_n, "m_m": m_m,
             "s_conv": s_convs, "s_c": s_c, "s_n": s_n, "s_h": s_h,
             "s_m": s_m, "pos": jnp.int32(s)}
    return logits, cache


def xlstm_decode_step(params, cache, tokens, cfg):
    """tokens: (B, 1).  Returns (logits (B, V), new cache).

    The stacked recurrent state rides in the scans' *carry*: each mLSTM
    layer reads its slice ``[g, j]`` and writes it back in place
    (dynamic_update_slice), each sLSTM block its slice ``[g]``, and no
    state leaf is a scan xs or ys.  With the cache donated, XLA aliases
    the whole while-loop state, so a step reads and writes the matrix
    memory only where the update needs it.  Carrying the state as xs/ys
    instead costs four more full passes over ``m_c`` a step (slice the
    group's state, write each layer into the inner ys, copy those into the
    outer ys, copy the result into the donated output) and about 10 GB of
    temporaries at 48 slots of xlstm-350m on a v5e
    (tests/test_tpu_compile.py pins the carried form)."""
    n_groups, m_per = _groups(cfg)
    x = embed_lookup(params["embed"], tokens)
    state = {k: cache[k] for k in XLSTM_STATE_AXES}
    m_keys = ("m_conv", "m_c", "m_n", "m_m")
    s_keys = ("s_conv", "s_c", "s_n", "s_h", "s_m")

    def at(a, i):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

    def group_body(carry, inp):
        x, st = carry
        mparams, sparams, g = inp

        def inner(c, lp_j):
            x, st = c
            lp, j = lp_j
            conv, cc, nn, m_ = (at(at(st[k], g), j) for k in m_keys)
            x, conv, (cc, nn, m_) = mlstm_block_decode(
                lp, x, cfg.n_heads, conv, (cc, nn, m_),
                norm_eps=cfg.norm_eps)
            st = dict(st)
            for k, v in zip(m_keys, (conv, cc, nn, m_)):
                st[k] = jax.lax.dynamic_update_slice(
                    st[k], v[None, None], (g, j) + (0,) * v.ndim)
            return (x, st), None

        (x, st), _ = jax.lax.scan(inner, (x, st),
                                  (mparams, jnp.arange(m_per)))
        s_conv, s_c, s_n, s_h, s_m = (at(st[k], g) for k in s_keys)
        x, s_conv, s_state = slstm_block_decode(
            sparams, x, cfg.n_heads, s_conv, (s_c, s_n, s_h, s_m),
            norm_eps=cfg.norm_eps)
        st = dict(st)
        for k, v in zip(s_keys, (s_conv, *s_state)):
            st[k] = jax.lax.dynamic_update_index_in_dim(st[k], v, g, 0)
        return (x, st), None

    (x, state), _ = jax.lax.scan(
        group_body, (x, state),
        (params["mlstm"], params["slstm"], jnp.arange(n_groups)))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, -1].astype(jnp.float32),
                        params["lm_head"].astype(jnp.float32))
    logits = logits[:, :cfg.vocab_size]
    return logits, {**state, "pos": cache["pos"] + 1}
