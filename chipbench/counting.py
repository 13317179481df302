"""Operations and bytes the work needs, from the configuration's shapes.

These count what the algorithm must do, not what the compiler emitted:
a multiply-add is 2 operations, weights are read once per launch at
their served width (bfloat16, float32 for the few leaves served in
float32), the key/value cache is read for live positions only (whole
blocks for a paged kernel), and recurrent state is read and written for
live rows only.  Padding of the vocabulary, idle batch rows and masked
blocks are not needed work and are not counted, so a share of a roofline
or a peak built on these counts cannot pass 100% unless the device did
the work in less time than the chip's peaks allow.

``model`` is the ``"model"`` object of a configuration file (the
program's ``ModelConfig`` fields).
"""
from __future__ import annotations

import json
import os

BF16, F32 = 2, 4


def peaks(device_kind: str) -> dict:
    """The chip's peaks by ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def _hd(m):
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def weight_groups(m: dict) -> dict:
    """{"matmul": (params, bytes), "cell": (params, bytes)}: the weights
    every token multiplies (layers and the output head over the published
    vocabulary) and, for the scan family, the per-head recurrent ones."""
    d, L, v = m["d_model"], m["n_layers"], m["vocab_size"]
    if m["family"] == "dense":
        hd, h, hkv = _hd(m), m["n_heads"], m["n_kv_heads"]
        attn = d * h * hd * 2 + d * hkv * hd * 2
        layer = attn + 3 * d * m["d_ff"] + 2 * hd + 2 * d
        n = L * layer + d * v + d
        return {"matmul": (n, n * BF16), "cell": (0, 0)}
    if m["family"] == "ssm":
        h, k = m["n_heads"], m["slstm_every"]
        di = 2 * d
        n_s = L // k
        n_m = L - n_s
        m_bf16 = d * 2 * di + 4 * di + di + 3 * di * di // h + di \
            + di * d + d
        m_f32 = 2 * di * h + 2 * h
        s_bf16 = d + 4 * d + d + d * 4 * d + d + d * d
        s_f32 = 4 * h * (d // h) ** 2
        bf16 = n_m * m_bf16 + n_s * s_bf16 + d * v + d
        return {"matmul": (bf16 + n_m * m_f32,
                           bf16 * BF16 + n_m * m_f32 * F32),
                "cell": (n_s * s_f32, n_s * s_f32 * F32)}
    raise ValueError(f"no counts for family {m['family']!r}")


def state_bytes(m: dict) -> int:
    """Recurrent state of one row (scan family), read or written once."""
    d, L, h, k = m["d_model"], m["n_layers"], m["n_heads"], \
        m["slstm_every"]
    di, n_s = 2 * d, L // k
    dm, ds = di // h, d // h
    mlstm = (h * dm * dm + h * dm + h) * F32 + 3 * di * BF16
    slstm = 4 * h * ds * F32 + 3 * d * BF16
    return (L - n_s) * mlstm + n_s * slstm


def kv_bytes_per_token(m: dict) -> int:
    return m["n_layers"] * m["n_kv_heads"] * _hd(m) * 2 * BF16


def token_flops(m: dict, context: int) -> float:
    """Operations of one token at a position that sees ``context``
    positions (itself included)."""
    n, _ = weight_groups(m)["matmul"]
    nc, _ = weight_groups(m)["cell"]
    f = 2.0 * (n + nc)
    if m["family"] == "dense":
        f += 4.0 * m["n_layers"] * m["n_heads"] * _hd(m) * context
    else:
        # mLSTM cell per head: decay, input-scaled outer product, add
        # (4 dk dv) and the query read-out (2 dk dv)
        h, k = m["n_heads"], m["slstm_every"]
        dm = 2 * m["d_model"] // h
        f += (m["n_layers"] - m["n_layers"] // k) * h * 6.0 * dm * dm
    return f


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Operations of a whole prompt (position p sees p + 1 positions)."""
    return sum(token_flops(m, p + 1) for p in range(prompt_len))


def decode_step_bytes(m: dict, kv_lens: list[int]) -> float:
    """Bytes one decode launch must move for its live rows: the weights
    once, one embedding row per row, and the live KV cache (read, plus
    the new position written) or the recurrent state (read and
    written)."""
    g = weight_groups(m)
    w = g["matmul"][1] + g["cell"][1]
    b = len(kv_lens)
    row = b * m["d_model"] * BF16
    if m["family"] == "dense":
        per = kv_bytes_per_token(m)
        return w + row + sum(kv_lens) * per + b * per
    return w + row + b * 2 * state_bytes(m)


def paged_attn_call(m: dict, kv_lens: list[int], block_size: int,
                    peak: dict) -> float:
    """Least time (s) one layer's paged decode attention can take over
    the live rows: the larger of its operations over peak FLOP/s and its
    bytes (live blocks of K and V, whole blocks; the query and output
    rows) over peak bandwidth."""
    hd, h, hkv = _hd(m), m["n_heads"], m["n_kv_heads"]
    flops = sum(4.0 * h * hd * n for n in kv_lens)
    blocks = sum(-(-n // block_size) for n in kv_lens)
    nbytes = (blocks * block_size * hkv * hd * 2 * BF16
              + len(kv_lens) * h * hd * 2 * BF16)
    return max(flops / peak["flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
