"""Kernel dispatch layer.

Every kernel has up to three implementations:
  * ``pallas``    - the TPU target (pl.pallas_call + BlockSpec VMEM tiling);
  * ``interpret`` - the same kernel body executed in interpret mode
    (CPU-validated against ref.py in tests);
  * ``xla``       - pure-jnp production path, used on CPU and for the
    dry-run lowering so cost_analysis() reflects clean HLO.

Default: ``xla`` on CPU hosts, ``pallas`` when a TPU backend is present.
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax

from . import ref
from .attention import (attention_xla, decode_attention_xla,
                        flash_attention_pallas)
from .conv2d import conv2d_pallas, conv2d_xla
from .dotproduct import dotproduct_pallas, dotproduct_xla
from .dropout import dropout_pallas, dropout_xla
from .dwt import dwt_haar_pallas, dwt_haar_xla
from .expk import exp_pallas, exp_xla
from .fft import fft_pallas, fft_xla
from .jacobi2d import jacobi2d_pallas, jacobi2d_xla
from .matmul import matmul_pallas, matmul_xla
from .paged_attention import (paged_decode_attention_pallas,
                              paged_decode_attention_xla,
                              paged_prefill_attention_pallas,
                              paged_prefill_attention_xla)
from .pathfinder import pathfinder_pallas, pathfinder_xla
from .roi_align import roi_align_xla
from .softmax import softmax_pallas, softmax_xla
from .ssd_scan import ssd_pallas, ssd_step_xla, ssd_xla

_IMPL: str | None = None  # resolved lazily


def default_impl() -> str:
    global _IMPL
    if _IMPL is None:
        # REPRO_KERNEL_IMPL overrides the backend default (CI runs the
        # serving/kernel suites a second time with =interpret so the
        # Pallas paged prefill/decode bodies execute on the CPU runner)
        env = os.environ.get("REPRO_KERNEL_IMPL")
        if env:
            if env not in ("pallas", "interpret", "xla"):
                raise ValueError(
                    f"REPRO_KERNEL_IMPL={env!r}: expected pallas, "
                    "interpret, or xla")
            _IMPL = env
        else:
            _IMPL = "pallas" if jax.default_backend() == "tpu" else "xla"
    return _IMPL


def set_impl(impl: str) -> None:
    global _IMPL
    assert impl in ("pallas", "interpret", "xla")
    _IMPL = impl


@contextlib.contextmanager
def impl_scope(impl: str):
    global _IMPL
    prev = _IMPL
    set_impl(impl)
    try:
        yield
    finally:
        _IMPL = prev


def _dispatch(impl, pallas_fn, xla_fn):
    impl = impl or default_impl()
    if impl == "xla" or pallas_fn is None:
        return xla_fn, {}
    return pallas_fn, {"interpret": impl == "interpret"}


# ---------------------------------------------------------------------------
# Public ops.
# ---------------------------------------------------------------------------

def matmul(x, w, *, impl=None, out_dtype=None, **kw):
    fn, extra = _dispatch(impl, matmul_pallas, matmul_xla)
    return fn(x, w, out_dtype=out_dtype, **extra, **kw)


def attention(q, k, v, *, impl=None, causal=True, window=None, scale=None,
              kv_len=None, **kw):
    impl = impl or default_impl()
    if impl == "xla" or kv_len is not None:
        # kv_len masking (serving) goes through the scan path.
        return attention_xla(q, k, v, causal=causal, window=window,
                             scale=scale, kv_len=kv_len, **kw)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  scale=scale, interpret=impl == "interpret",
                                  **kw)


def decode_attention(q, k_cache, v_cache, kv_len, *, scale=None, window=None):
    return decode_attention_xla(q, k_cache, v_cache, kv_len, scale=scale,
                                window=window)


def paged_decode_attention(q, k_pool, v_pool, layer, block_table, kv_len, *,
                           impl=None, scale=None, window=None):
    """Single-token attention against layer ``layer`` of a stacked paged KV
    pool (L, N, Hkv, bs, D) via a block table.

    Sliding windows ride the jnp gather path (the Pallas kernel keeps the
    prefix-mask fast path; traced per-layer windows would defeat its
    block-skip predicate anyway)."""
    impl = impl or default_impl()
    if impl == "xla" or window is not None:
        return paged_decode_attention_xla(q, k_pool, v_pool, layer,
                                          block_table, kv_len, scale=scale,
                                          window=window)
    return paged_decode_attention_pallas(q, k_pool, v_pool, layer,
                                         block_table, kv_len, scale=scale,
                                         interpret=impl == "interpret")


def paged_prefill_attention(q, k_pool, v_pool, layer, block_table, q_start,
                            *, impl=None, scale=None, window=None):
    """One prompt chunk's causal attention against layer ``layer`` of a
    stacked paged KV pool (the chunk's K/V must already sit in its block).
    Sliding windows ride the per-block gather path, same as decode (traced
    per-layer windows would defeat the Pallas block-skip predicate)."""
    impl = impl or default_impl()
    if impl == "xla" or window is not None:
        return paged_prefill_attention_xla(q, k_pool, v_pool, layer,
                                           block_table, q_start, scale=scale,
                                           window=window)
    return paged_prefill_attention_pallas(q, k_pool, v_pool, layer,
                                          block_table, q_start, scale=scale,
                                          interpret=impl == "interpret")


def ssd_scan(x, dt, a_log, b_mat, c_mat, *, impl=None, d_skip=None, h0=None,
             chunk=64):
    impl = impl or default_impl()
    if impl == "xla" or h0 is not None:
        return ssd_xla(x, dt, a_log, b_mat, c_mat, d_skip=d_skip, h0=h0,
                       chunk=chunk)
    y, h = ssd_pallas(x, dt, a_log, b_mat, c_mat, chunk=chunk,
                      interpret=impl == "interpret")
    if d_skip is not None:
        y = y + (d_skip[None, None, :, None] * x).astype(y.dtype)
    return y, h


def ssd_step(h_state, xt, dtt, a_log, bt, ct, *, d_skip=None):
    return ssd_step_xla(h_state, xt, dtt, a_log, bt, ct, d_skip=d_skip)


def dotproduct(x, y, *, impl=None):
    fn, extra = _dispatch(impl, dotproduct_pallas, dotproduct_xla)
    return fn(x, y, **extra)


def softmax(x, *, impl=None, **kw):
    fn, extra = _dispatch(impl, softmax_pallas, softmax_xla)
    return fn(x, **extra, **kw)


def exp(x, *, impl=None, **kw):
    fn, extra = _dispatch(impl, exp_pallas, exp_xla)
    return fn(x, **extra, **kw)


def dropout(x, bits, *, rate, impl=None, **kw):
    fn, extra = _dispatch(impl, dropout_pallas, dropout_xla)
    return fn(x, bits, rate=rate, **extra, **kw)


def conv2d(x, w, *, impl=None, **kw):
    fn, extra = _dispatch(impl, conv2d_pallas, conv2d_xla)
    return fn(x, w, **extra, **kw)


def jacobi2d(x, *, impl=None, **kw):
    impl = impl or default_impl()
    if impl == "xla":
        return jacobi2d_xla(x, **kw)
    return jacobi2d_pallas(x, interpret=impl == "interpret", **kw)


def dwt_haar(x, *, levels=1, impl=None, **kw):
    fn, extra = _dispatch(impl, dwt_haar_pallas, dwt_haar_xla)
    return fn(x, levels=levels, **extra, **kw)


def pathfinder(w, *, impl=None, **kw):
    fn, extra = _dispatch(impl, pathfinder_pallas, pathfinder_xla)
    return fn(w, **extra, **kw)


def fft(x_re, x_im, *, impl=None, **kw):
    fn, extra = _dispatch(impl, fft_pallas, fft_xla)
    return fn(x_re, x_im, **extra, **kw)


def roi_align(feat, rois, *, impl=None, **kw):
    # Pallas variant intentionally absent (gather-bound; see module doc).
    return roi_align_xla(feat, rois, **kw)
