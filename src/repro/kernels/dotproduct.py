"""Dot-product kernel with the paper's 3-step hierarchical reduction (C3).

The VMEM accumulator tile (8, 128) plays the role of the per-lane FPU
pipeline-register accumulators (§3: "the internal pipeline registers of the
FPU are used as accumulators"): the streaming phase accumulates block
partials into it at full throughput, and only the final grid step pays the
log-tree drain - exactly the paper's intra-lane -> inter-lane -> SIMD split.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = (8, 128)  # VPU-shaped accumulator tile
BLOCK = LANES[0] * LANES[1]


def _dot_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_steps: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32).reshape(LANES)
    y = y_ref[...].astype(jnp.float32).reshape(LANES)
    acc_ref[...] += x * y   # phase 1: streaming accumulate (intra-lane)

    @pl.when(i == n_steps - 1)
    def _drain():
        acc = acc_ref[...]
        # phase 2: inter-lane log tree (across sublanes)
        while acc.shape[0] > 1:
            h = acc.shape[0] // 2
            acc = acc[:h] + acc[h:]
        # phase 3: SIMD log tree (within the 128-wide word)
        row = acc[0]
        while row.shape[0] > 1:
            h = row.shape[0] // 2
            row = row[:h] + row[h:]
        o_ref[0, 0] = row[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dotproduct_pallas(x, y, *, interpret=False):
    (n,) = x.shape
    assert n % BLOCK == 0, f"n={n} must be a multiple of {BLOCK}"
    n_steps = n // BLOCK
    return pl.pallas_call(
        functools.partial(_dot_kernel, n_steps=n_steps),
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,)),
                  pl.BlockSpec((BLOCK,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM(LANES, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, y)[0, 0]


def dotproduct_xla(x, y):
    return jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32))
