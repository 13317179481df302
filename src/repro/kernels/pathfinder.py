"""Pathfinder DP kernel (paper pool, RiVec suite).

dst[j] = w[i][j] + min(src[j-1], src[j], src[j+1]) row by row.  The row
recurrence runs on the sequential grid axis with the running costs in VMEM
scratch; the j+-1 neighbor access is a slide-by-1 (C2's cheapest config).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_BIG = 3.0e38  # python float: jnp scalars would be captured as kernel consts


def _shift_with(row, fill, direction):
    if direction > 0:
        return jnp.concatenate([jnp.full((1, 1), fill, row.dtype), row[:, :-1]],
                               axis=1)
    return jnp.concatenate([row[:, 1:], jnp.full((1, 1), fill, row.dtype)],
                           axis=1)


def _pathfinder_kernel(w_ref, o_ref, src_ref, *, rows: int):
    i = pl.program_id(0)
    w = w_ref[...].astype(jnp.float32)        # (1, cols)

    @pl.when(i == 0)
    def _init():
        src_ref[...] = w

    @pl.when(i > 0)
    def _step():
        src = src_ref[...]
        left = _shift_with(src, _BIG, +1)
        right = _shift_with(src, _BIG, -1)
        src_ref[...] = w + jnp.minimum(src, jnp.minimum(left, right))

    @pl.when(i == rows - 1)
    def _flush():
        o_ref[...] = src_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pathfinder_pallas(w, *, interpret=False):
    rows, cols = w.shape
    return pl.pallas_call(
        functools.partial(_pathfinder_kernel, rows=rows),
        grid=(rows,),
        in_specs=[pl.BlockSpec((1, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, cols), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, cols), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, cols), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(w)[0]


def pathfinder_xla(w):
    from .ref import pathfinder_ref
    return pathfinder_ref(w)
