"""Qwen3 dense decoder in plain float32 (Qwen/Qwen3-0.6B, config.json).

Per layer, pre-norm: RMSNorm, grouped-query attention with per-head
RMSNorm on queries and keys (qk-norm) and rotary embeddings on the
half-split pairs (HF ``rotate_half``), query head ``h`` reading key/value
head ``h // (H / Hkv)``, softmax over causal scores scaled by
``1/sqrt(head_dim)``; then RMSNorm and a SwiGLU feed-forward
``down(silu(gate x) * up x)``.  A final RMSNorm, and logits from the tied
embedding.

Departures of the program's parameterisation, followed here so that the
same arrays mean the same function:

* every RMSNorm gain is stored as an offset ``w`` and applied as
  ``1 + w`` (Qwen3 stores the gain itself);
* the embedding matrix is padded to a multiple of 256 rows; the logits
  cover the first ``vocab_size`` columns only.
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


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate the (first half, second half) pairs."""
    hd = x.shape[-1]
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, hd, 2) / hd)),
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(params, tokens, model: dict, mm):
    """Final-normed hidden states (S, d_model) of ``tokens`` (S,)."""
    s = tokens.shape[0]
    h_q, h_kv = model["n_heads"], model["n_kv_heads"]
    hd = model["head_dim"]
    eps = model["norm_eps"]
    theta = model["rope_theta"]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]
    x = _f32(params["embed"]["embedding"])[tokens]

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["ln1"], eps)
        q = mm(h, _f32(a["wq"]), "sd,dh->sh").reshape(s, h_q, hd)
        k = mm(h, _f32(a["wk"]), "sd,dh->sh").reshape(s, h_kv, hd)
        v = mm(h, _f32(a["wv"]), "sd,dh->sh").reshape(s, h_kv, hd)
        q = _rope(_rms(q, a["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, a["k_norm"], eps), pos, theta)
        k = jnp.repeat(k, h_q // h_kv, axis=1)
        v = jnp.repeat(v, h_q // h_kv, axis=1)
        scores = mm(q, k, "qhd,khd->hqk") / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        o = mm(p, v, "hqk,khd->qhd").reshape(s, h_q * hd)
        x = x + mm(o, _f32(a["wo"]), "sh,hd->sd")
        m = lp["mlp"]
        h = _rms(x, lp["ln2"], eps)
        g = mm(h, _f32(m["gate"]), "sd,df->sf")
        u = mm(h, _f32(m["up"]), "sd,df->sf")
        x = x + mm(jax.nn.silu(g) * u, _f32(m["down"]), "sf,fd->sd")
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_norm"], eps)


def head(params, model: dict):
    """(d_model, vocab_size) output projection."""
    if model.get("tie_embeddings"):
        w = params["embed"]["embedding"].T
    else:
        w = params["lm_head"]
    return _f32(w[:, :model["vocab_size"]])
