"""Decoder-only transformer families: dense, moe, vlm.

One scanned layer body per family (homogeneous stacks compile to small HLO
even at 94 layers); gemma3's 5:1 local:global pattern rides through the scan
as a per-layer traced window scalar.  Train / prefill / decode share the
same parameter tree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.act_sharding import constrain
from .attention import (attn_decode, attn_decode_paged, attn_forward,
                        attn_prefill, attn_prefill_paged, attn_templates)
from .layers import (PT, embed_lookup, embed_templates, init_params,
                     param_pspecs, rmsnorm, softmax_xent_chunked,
                     stack_layers, swiglu_apply, swiglu_templates)
from .moe import moe_apply, moe_templates

_BIG_WINDOW = 1 << 30  # "global" layers: window larger than any context


# ---------------------------------------------------------------------------
# Templates.
# ---------------------------------------------------------------------------

def layer_templates(cfg):
    t = {
        "ln1": PT((cfg.d_model,), "zeros", ("embed",)),
        "attn": attn_templates(cfg),
        "ln2": PT((cfg.d_model,), "zeros", ("embed",)),
    }
    if cfg.family == "moe":
        t["moe"] = moe_templates(cfg.d_model, cfg.d_ff, cfg.n_experts)
    else:
        t["mlp"] = swiglu_templates(cfg.d_model, cfg.d_ff)
    return t


def decoder_templates(cfg):
    t = {
        "embed": embed_templates(cfg.padded_vocab, cfg.d_model),
        "layers": stack_layers(lambda: layer_templates(cfg), cfg.n_layers),
        "final_norm": PT((cfg.d_model,), "zeros", ("embed",)),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = PT((cfg.d_model, cfg.padded_vocab), "scaled",
                          ("embed", "vocab"))
    if cfg.family == "vlm":
        t["patch_proj"] = PT((cfg.patch_embed_dim, cfg.d_model), "scaled",
                             (None, "embed"))
    return t


def windows_array(cfg) -> jnp.ndarray | None:
    """Per-layer sliding windows as a traced scan input (None if uniform)."""
    if not cfg.local_window:
        return None
    ws = [cfg.layer_window(i) or _BIG_WINDOW for i in range(cfg.n_layers)]
    return jnp.asarray(ws, jnp.int32)


def lm_head_weight(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"]["embedding"].T
    return params["lm_head"]


def _lm_logits(params, x_last, cfg):
    """(B, D) final-norm'd last-token hiddens -> (B, V) serving logits."""
    logits = jnp.einsum("bd,dv->bv", x_last.astype(jnp.float32),
                        lm_head_weight(params, cfg).astype(jnp.float32))
    logits = logits[:, :cfg.vocab_size]
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# Layer body (shared by train/prefill; decode has its own).
# ---------------------------------------------------------------------------

def _ffn(lp, h, cfg, exact=False):
    if cfg.family == "moe":
        return moe_apply(lp["moe"], h, top_k=cfg.top_k,
                         capacity_factor=cfg.capacity_factor, exact=exact)
    return swiglu_apply(lp["mlp"], h)


def _layer(lp, x, cfg, window, positions):
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    x = x + attn_forward(lp["attn"], h, cfg, positions=positions,
                         window=window)
    x = constrain(x, "hidden")
    h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    x = x + _ffn(lp, h, cfg)
    return constrain(x, "hidden")


def _scan_layers(params, x, cfg, positions, *, remat=False):
    windows = windows_array(cfg)
    body = functools.partial(_layer, cfg=cfg, positions=positions)
    if remat:
        body = jax.checkpoint(body, static_argnums=())

    if windows is None:
        def scan_fn(carry, lp):
            return body(lp, carry, window=None), None
        x, _ = jax.lax.scan(scan_fn, x, params["layers"])
    else:
        def scan_fn(carry, inp):
            lp, w = inp
            return body(lp, carry, window=w), None
        x, _ = jax.lax.scan(scan_fn, x, (params["layers"], windows))
    return x


# ---------------------------------------------------------------------------
# Embedding of the (token | patch+token) input.
# ---------------------------------------------------------------------------

def embed_input(params, batch, cfg):
    """Returns (x (B, S_total, D), n_prefix).  For vlm, the stub patch
    embeddings occupy the first n_patches positions."""
    tok = embed_lookup(params["embed"], batch["tokens"])
    if cfg.family == "vlm":
        patches = jnp.einsum("bpe,ed->bpd", batch["patches"],
                             params["patch_proj"]).astype(tok.dtype)
        return jnp.concatenate([patches, tok], axis=1), cfg.n_patches
    return tok, 0


# ---------------------------------------------------------------------------
# Train forward + loss.
# ---------------------------------------------------------------------------

def decoder_loss(params, batch, cfg, *, remat=True, xent_chunk=512):
    x, n_prefix = embed_input(params, batch, cfg)
    x = constrain(x, "hidden")
    s_total = x.shape[1]
    positions = jnp.arange(s_total)
    x = _scan_layers(params, x, cfg, positions, remat=remat)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]
    loss, acc = softmax_xent_chunked(
        x, lm_head_weight(params, cfg), batch["labels"], chunk=xent_chunk,
        label_mask=batch.get("label_mask"), logit_softcap=cfg.logit_softcap,
        valid_vocab=cfg.vocab_size)
    return loss, {"loss": loss, "accuracy": acc}


# ---------------------------------------------------------------------------
# Serving: prefill + decode.
# ---------------------------------------------------------------------------

def decoder_prefill(params, batch, cfg, *, cache_len=None):
    """Returns (last-token logits (B, V), cache dict).

    ``batch["prefill_len"]`` (optional, (B,) int32): per-row true token
    count when ``tokens`` is right-padded to a bucket length (the serving
    engine's prompt-length bucketing).  Causality already hides the pads
    from real tokens, pad KV lands at positions >= the true length (masked
    in decode and overwritten as decode proceeds), so only the last-token
    gather and the cache position depend on it; ``cache["pos"]`` becomes a
    (B,) vector of true lengths."""
    x, n_prefix = embed_input(params, batch, cfg)
    s_total = x.shape[1]
    cache_len = cache_len or s_total
    assert cache_len >= s_total, (
        f"cache_len {cache_len} < prompt length {s_total} "
        "(vlm prompts include n_patches prefix positions)")
    windows = windows_array(cfg)

    b = x.shape[0]
    hd = cfg.head_dim_resolved
    cache_shape = (cfg.n_layers, b, cfg.n_kv_heads, cache_len, hd)
    k0 = jnp.zeros(cache_shape, x.dtype)
    v0 = jnp.zeros(cache_shape, x.dtype)

    def scan_fn(carry, inp):
        x, kc_all, vc_all = carry
        if windows is None:
            (lp, idx), w = inp, None
        else:
            lp, idx, w = inp
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        a, (kc, vc) = attn_prefill(lp["attn"], h, cfg, cache_len=cache_len,
                                   window=w)
        x = x + a
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        # exact=True routes moe dropless: serving prefill must produce the
        # same hiddens as the chunked paged prefill (which is dropless by
        # construction at chunk length <= capacity), so paged==dense token
        # identity holds for the moe family too.  Training keeps
        # capacity-factor routing (decoder_loss does not share this body).
        x = constrain(x + _ffn(lp, h, cfg, exact=True), "hidden")
        # write the layer cache in place (carried, not stacked as scan ys:
        # ys accumulation double-buffers the full multi-GB cache)
        kc_all = jax.lax.dynamic_update_index_in_dim(kc_all, kc, idx, 0)
        vc_all = jax.lax.dynamic_update_index_in_dim(vc_all, vc, idx, 0)
        return (x, kc_all, vc_all), None

    idxs = jnp.arange(cfg.n_layers)
    xs = ((params["layers"], idxs) if windows is None
          else (params["layers"], idxs, windows))
    (x, k_cache, v_cache), _ = jax.lax.scan(scan_fn, (x, k0, v0), xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if "prefill_len" in batch:
        pos = n_prefix + batch["prefill_len"].astype(jnp.int32)   # (B,)
        x_last = jnp.take_along_axis(x, (pos - 1)[:, None, None],
                                     axis=1)[:, 0]
    else:
        pos = jnp.int32(s_total)
        x_last = x[:, -1]
    cache = {"k": k_cache, "v": v_cache, "pos": pos}
    return _lm_logits(params, x_last, cfg), cache


def _decode_scan(params, tokens, k_all, v_all, cfg, attn_fn):
    """Shared one-token decode body for both KV layouts: embed, scan the
    layer stack, final-norm, lm head.  ``attn_fn(lp, h, k_all, v_all,
    layer, window) -> (attn_out, k_all, v_all)`` is the only
    layout-specific piece: it gets the whole stacked caches and the layer
    index, and updates that layer's entries in place.  The paged layout
    reads and writes the layer's blocks inside the stack; the dense
    layout slices its layer's strip out and writes it back.

    The stacked KV caches ride in the scan *carry* and are never sliced
    here: with the cache donated, XLA aliases the whole while-loop state.
    Carrying them as scan xs/ys double-buffers the full cache (~2.6x cache
    bytes of temp measured on phi-3-vision decode_32k; see EXPERIMENTS.md
    §Perf).
    Returns (logits, k_all, v_all)."""
    x = embed_lookup(params["embed"], tokens)
    windows = windows_array(cfg)

    def scan_fn(carry, inp):
        x, kc_all, vc_all = carry
        if windows is None:
            (lp, idx), w = inp, None
        else:
            lp, idx, w = inp
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        a, kc_all, vc_all = attn_fn(lp["attn"], h, kc_all, vc_all, idx, w)
        x = x + a
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + _ffn(lp, h, cfg, exact=True)
        return (x, kc_all, vc_all), None

    idxs = jnp.arange(cfg.n_layers)
    xs = ((params["layers"], idxs) if windows is None
          else (params["layers"], idxs, windows))
    (x, k_all, v_all), _ = jax.lax.scan(scan_fn, (x, k_all, v_all), xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _lm_logits(params, x[:, -1], cfg), k_all, v_all


def decoder_decode_step(params, cache, tokens, cfg):
    """tokens: (B, 1).  Returns (logits (B, V), new cache).

    ``cache["pos"]`` is either a scalar (uniform-position layout: every row
    decodes at the same position) or a (B,) vector (the serving engine's
    slot-pool layout: each slot tracks its own position; the new KV lands
    at each row's own slot via the one-hot path in ``attn_decode``)."""
    pos = cache["pos"]

    def attn_fn(lp, h, k_all, v_all, idx, w):
        kc = jax.lax.dynamic_index_in_dim(k_all, idx, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, idx, 0, keepdims=False)
        a, kc, vc = attn_decode(lp, h, kc, vc, pos, cfg, window=w)
        return (a, jax.lax.dynamic_update_index_in_dim(k_all, kc, idx, 0),
                jax.lax.dynamic_update_index_in_dim(v_all, vc, idx, 0))

    logits, k_new, v_new = _decode_scan(params, tokens, cache["k"],
                                        cache["v"], cfg, attn_fn)
    return logits, {"k": k_new, "v": v_new, "pos": pos + 1}


def make_decode_cache_specs(cfg, batch_size: int, cache_len: int,
                            dtype=jnp.bfloat16):
    """ShapeDtypeStructs for the decode cache (dry-run inputs)."""
    hd = cfg.head_dim_resolved
    shape = (cfg.n_layers, batch_size, cfg.n_kv_heads, cache_len, hd)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype),
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}


# ---------------------------------------------------------------------------
# Slot-pool cache support (continuous-batching serving).
# ---------------------------------------------------------------------------

def decoder_cache_expand(sub, batch: int):
    """Grow a batch-1 prefill cache into an empty ``batch``-slot decode
    cache.  Positions become a per-slot (B,) vector; all slots start empty
    (pos 0), to be filled by :func:`decoder_cache_slot_write` on admission."""
    def grow(x):
        return jnp.zeros(x.shape[:1] + (batch,) + x.shape[2:], x.dtype)
    return {"k": grow(sub["k"]), "v": grow(sub["v"]),
            "pos": jnp.zeros((batch,), jnp.int32)}


def decoder_cache_slot_write(cache, sub, slot):
    """Write a batch-1 prefill cache into batch index ``slot`` of a
    slot-pool decode cache (prefill-on-admit).  ``slot`` may be traced, so
    a jitted caller compiles once for all slots."""
    k = jax.lax.dynamic_update_index_in_dim(cache["k"], sub["k"][:, 0],
                                            slot, 1)
    v = jax.lax.dynamic_update_index_in_dim(cache["v"], sub["v"][:, 0],
                                            slot, 1)
    pos = jax.lax.dynamic_update_index_in_dim(
        cache["pos"],
        jnp.reshape(jnp.asarray(sub["pos"], jnp.int32), ()), slot, 0)
    return {"k": k, "v": v, "pos": pos}


# ---------------------------------------------------------------------------
# Paged KV cache (block-pool serving layout; see repro.serving.kvcache).
# ---------------------------------------------------------------------------

def decoder_paged_cache_init(cfg, *, batch: int, n_blocks: int,
                             block_size: int, max_blocks: int,
                             dtype=jnp.bfloat16):
    """Empty paged decode cache: one global KV block pool shared by all
    ``batch`` slots, per-slot block tables pointing at the null block, and
    per-slot positions at 0."""
    hd = cfg.head_dim_resolved
    pool = (cfg.n_layers, n_blocks, cfg.n_kv_heads, block_size, hd)
    return {"kp": jnp.zeros(pool, dtype), "vp": jnp.zeros(pool, dtype),
            "bt": jnp.zeros((batch, max_blocks), jnp.int32),
            "pos": jnp.zeros((batch,), jnp.int32)}


def decoder_cache_dtype(params):
    """KV dtype a prefill would produce (the embedding activations'
    dtype) — lets the engine build the paged pool before any prefill
    has run."""
    return params["embed"]["embedding"].dtype


def _embed_chunk(params, batch, q_start, bs, cfg):
    """Embed combined positions ``[q_start, q_start + bs)`` of a prompt.

    ``batch["tokens"]``: (1, bs) token ids aligned to those positions (the
    engine feeds 0 where a position is a model-side prefix row or pad).
    For vlm, positions below ``n_patches`` take the projected patch
    embedding instead of the token row."""
    tok = embed_lookup(params["embed"], batch["tokens"])       # (1, bs, D)
    if cfg.family != "vlm":
        return tok
    patches = jnp.einsum("bpe,ed->bpd", batch["patches"],
                         params["patch_proj"]).astype(tok.dtype)
    pos = q_start + jnp.arange(bs)                             # (bs,)
    pat = jnp.take(patches[0], jnp.clip(pos, 0, cfg.n_patches - 1), axis=0)
    return jnp.where((pos < cfg.n_patches)[None, :, None], pat[None], tok)


def decoder_prefill_paged(params, pcache, batch, slot, chunk, prefill_len,
                          cfg):
    """One ``block_size`` chunk of a paged prefill for a single request.

    Chunked prefill: the chunk's hidden states run through the whole layer
    stack; each layer projects the chunk's K/V, writes them in place into
    its layer's pool block ``pcache["bt"][slot, chunk]`` (installed by the
    engine before the call) of the stacked pool, which the scan carries
    and never slices, and attends causally over blocks ``0..chunk`` via
    the block table — the dense batch-1 ``(L, Hkv, prompt_len, hd)``
    prefill cache of the scatter-on-admit path never exists.  ``slot``,
    ``chunk`` and ``prefill_len`` may all be traced, so one compile serves
    every chunk of every prompt at every slot (no length bucketing
    needed).

    MoE chunks route with exact (dropless) dispatch like decode: capacity
    dropping depends on the batch a token shares, which would make a
    chunk's output depend on where the chunk boundaries fall.

    Returns (last-true-token logits (1, V), new pcache) — the logits row
    is the request's next-token distribution only on the final chunk
    (``prefill_len <= (chunk + 1) * bs``); earlier chunks return a
    mid-prompt row the engine discards.  ``pcache["pos"][slot]`` advances
    to ``min((chunk + 1) * bs, prefill_len)``."""
    bs = pcache["kp"].shape[3]
    chunk = jnp.asarray(chunk, jnp.int32)
    prefill_len = jnp.asarray(prefill_len, jnp.int32)
    q_start = chunk * bs
    x = _embed_chunk(params, batch, q_start, bs, cfg)
    x = constrain(x, "hidden")
    bt_row = jax.lax.dynamic_index_in_dim(pcache["bt"], slot, 0,
                                          keepdims=False)      # (M,)
    windows = windows_array(cfg)

    def scan_fn(carry, inp):
        x, kp_all, vp_all = carry
        if windows is None:
            (lp, idx), w = inp, None
        else:
            lp, idx, w = inp
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        a, kp_all, vp_all = attn_prefill_paged(lp["attn"], h, cfg, kp_all,
                                               vp_all, idx, bt_row, chunk,
                                               window=w)
        x = x + a
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = constrain(x + _ffn(lp, h, cfg, exact=True), "hidden")
        return (x, kp_all, vp_all), None

    idxs = jnp.arange(cfg.n_layers)
    xs = ((params["layers"], idxs) if windows is None
          else (params["layers"], idxs, windows))
    (x, kp, vp), _ = jax.lax.scan(
        scan_fn, (x, pcache["kp"], pcache["vp"]), xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    # last *true* row of this chunk (meaningful on the final chunk only)
    last = jnp.clip(prefill_len - 1 - q_start, 0, bs - 1)
    x_last = jax.lax.dynamic_index_in_dim(x[0], last, 0, keepdims=True)
    pos = pcache["pos"].at[slot].set(
        jnp.minimum(q_start + bs, prefill_len))
    return _lm_logits(params, x_last, cfg), {
        "kp": kp, "vp": vp, "bt": pcache["bt"], "pos": pos}


def decoder_decode_step_paged(params, pcache, tokens, cfg):
    """tokens: (B, 1) against the paged cache
    {"kp"/"vp": (L, n_blocks, Hkv, bs, hd), "bt": (B, M), "pos": (B,)}.
    Same layer body as :func:`decoder_decode_step`; only the KV read/write
    goes through the block table."""
    pos, bt = pcache["pos"], pcache["bt"]
    logits, kp, vp = _decode_scan(
        params, tokens, pcache["kp"], pcache["vp"], cfg,
        lambda lp, h, kp_all, vp_all, idx, w: attn_decode_paged(
            lp, h, kp_all, vp_all, idx, bt, pos, cfg, window=w))
    return logits, {"kp": kp, "vp": vp, "bt": bt, "pos": pos + 1}
