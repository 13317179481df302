"""Lane-tiled GEMM Pallas kernel (TPU target; paper kernel `matmul`).

Ara2 stripes the output row vector across lanes (C1); here the N dimension is
the lane axis: each grid column ``j`` is a lane-block of 128 output columns
(one MXU tile), and the VMEM accumulator plays the VRF's data-reuse role
("L0 storage ... to buffer data elements re-used multiple times close to the
PEs", §2).  K is the sequential grid axis; the fp32 accumulator lives in VMEM
scratch across K steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# MXU-aligned default tiles (multiples of 128 on both matmul dims).
DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 128


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def matmul_pallas(x, w, *, bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                  out_dtype=None, interpret=False):
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"unpadded shapes {(m, n, k)} vs blocks {(bm, bn, bk)}"
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)


def matmul_xla(x, w, out_dtype=None):
    """Production XLA path (used on CPU and for dry-run lowering)."""
    out_dtype = out_dtype or x.dtype
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(out_dtype)
