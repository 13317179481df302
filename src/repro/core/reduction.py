"""3-step hierarchical reductions (paper contribution C3, §3 "Reductions").

Ara2 reduces a vector in three phases:
  1. intra-lane  - each lane reduces its resident elements at full FPU
     utilization, using the FPU pipeline registers as accumulators;
  2. inter-lane  - a log2(L)+1-step tree over the slide interconnect;
  3. SIMD        - a log-tree within the final 64-bit word.

TPU transplant: intra-shard ``jnp`` reduce (VPU/MXU-local), then an
inter-shard tree built from log2(L) XOR-partner ``ppermute`` steps
(halving/doubling), then the in-register tree inside the Pallas dot-product
kernel.  ``allreduce_*`` are drop-in gradient-sync schedules compared against
native ``psum`` in the dry-run (§Perf).

Latency model: ``reduction_drain_cycles`` implements the paper's closed-form
``R*(1+log2(ceil(R))) - (ceil(R)-R) - 1`` for the intra-lane pipeline drain.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .vector_engine import log2i


# ---------------------------------------------------------------------------
# Single-array 3-step reduction (structural mirror of the hardware).
# ---------------------------------------------------------------------------

def simd_tree_reduce(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Explicit log-step halving tree (phase 3).  Pads with zeros."""
    n = x.shape[axis]
    x = jnp.moveaxis(x, axis, -1)
    p = 1 << (n - 1).bit_length() if n > 1 else 1
    if p != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, p - n)]
        x = jnp.pad(x, pad)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


def hierarchical_reduce(x: jnp.ndarray, n_lanes: int) -> jnp.ndarray:
    """Full 3-step sum of a 1-D vector: stripe across lanes, intra-lane
    accumulate, inter-lane tree.  Equals ``jnp.sum`` (property-tested)."""
    from .lanes import stripe
    lanes = stripe(x, n_lanes)           # (L, elems/lane)
    acc = jnp.sum(lanes, axis=1)         # phase 1: intra-lane
    return simd_tree_reduce(acc, axis=0)  # phases 2+3: log tree


# ---------------------------------------------------------------------------
# Mesh-level trees (inside shard_map).
# ---------------------------------------------------------------------------

def _xor_perm(size: int, d: int):
    return [(i, i ^ d) for i in range(size)]


def allreduce_hd(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Halving-doubling (latency-optimal) all-reduce: log2(L) full-size
    XOR-partner exchanges - the paper's inter-lane tree verbatim."""
    size = jax.lax.axis_size(axis_name)
    d = 1
    while d < size:
        x = x + jax.lax.ppermute(x, axis_name, _xor_perm(size, d))
        d <<= 1
    return x


def reduce_scatter_hd(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Recursive-halving reduce-scatter along leading dim (bandwidth-optimal:
    (L-1)/L of |x| per link).  Shard i of the result is chunk i."""
    size = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    assert x.shape[0] % size == 0, f"leading dim {x.shape[0]} % {size} != 0"
    d = size >> 1
    while d >= 1:
        half = x.shape[0] // 2
        bit = (idx & d) > 0
        keep_start = jnp.where(bit, half, 0)
        send_start = jnp.where(bit, 0, half)
        keep = jax.lax.dynamic_slice_in_dim(x, keep_start, half)
        send = jax.lax.dynamic_slice_in_dim(x, send_start, half)
        x = keep + jax.lax.ppermute(send, axis_name, _xor_perm(size, d))
        d >>= 1
    return x


def allgather_hd(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Recursive-doubling all-gather along leading dim (inverse of
    :func:`reduce_scatter_hd`'s placement)."""
    size = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    d = 1
    while d < size:
        other = jax.lax.ppermute(x, axis_name, _xor_perm(size, d))
        bit = (idx & d) > 0
        lower = jnp.where(bit, other, x)
        upper = jnp.where(bit, x, other)
        x = jnp.concatenate([lower, upper], axis=0)
        d <<= 1
    return x


def allreduce_rs_ag(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Bandwidth-optimal all-reduce = recursive-halving reduce-scatter +
    recursive-doubling all-gather (2*(L-1)/L of |x| per link)."""
    shape = x.shape
    size = jax.lax.axis_size(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    out = allgather_hd(reduce_scatter_hd(flat, axis_name), axis_name)
    if pad:
        out = out[:-pad]
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# Latency model (paper §3).
# ---------------------------------------------------------------------------

def reduction_drain_cycles(r: float) -> float:
    """Cycles to drain R pipeline-register partial sums into one:
    ``R*(1+log2(ceil(R))) - (ceil(R)-R) - 1``; for power-of-two R this is
    ``R*(1+log2(R)) - 1`` (paper §3)."""
    rc = math.ceil(r)
    if rc <= 1:
        return 0.0
    return r * (1 + math.log2(rc)) - (rc - r) - 1


def interlane_reduction_cycles(n_lanes: int, fpu_latency: int, slide_latency: int = 2) -> float:
    """(log2(L)+1) tree steps; the slide<->FPU dependency feedback pays both
    latencies at every step (paper §3)."""
    if n_lanes == 1:
        return 0.0
    return (log2i(n_lanes) + 1) * (fpu_latency + slide_latency)


def simd_reduction_cycles(ew_bits: int, fpu_latency: int) -> float:
    """Final intra-word tree: log2(64/EW) steps, each paying FPU latency."""
    steps = max(0, log2i(64 // ew_bits)) if ew_bits < 64 else 0
    return steps * fpu_latency


def vector_reduction_cycles(n_elems: int, n_lanes: int, ew_bits: int,
                            fpu_pipe: int) -> float:
    """End-to-end reduction latency: N/L streaming + intra-lane drain +
    inter-lane tree + SIMD tree."""
    n64 = n_elems * ew_bits // 64  # 64-bit packets (paper's N)
    stream = max(n64 / n_lanes, 1.0)
    return (stream
            + reduction_drain_cycles(fpu_pipe)
            + interlane_reduction_cycles(n_lanes, fpu_pipe)
            + simd_reduction_cycles(ew_bits, fpu_pipe))
