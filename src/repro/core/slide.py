"""Power-of-two slide decomposition (paper contribution C2, §3 + Figs 2-3).

Ara2's insight: an interconnect supporting *arbitrary* slide amounts in one
step costs O(L^2) wiring; restricting single-step support to power-of-two
amounts and decomposing arbitrary slides into <= log2(L) micro-ops costs
O(L log L) and is what lets the unit scale.

TPU transplant: on the ICI torus an arbitrary one-shot shard rotation is an
``all_to_all``-class operation (every chip talks to every chip: same O(L^2)
cost shape), while a power-of-two-stride ``collective_permute`` is a cheap
neighbor-class hop.  ``mesh_slide`` therefore decomposes an arbitrary rotation
of a sharded axis into binary-weighted ``jax.lax.ppermute`` steps - the exact
analogue of the paper's micro-op decomposition.  Used for halo exchange
(conv2d / jacobi2d), FFT butterflies, ring schedules, and SSM chunk-boundary
hand-off.

``mux_count`` reproduces the Fig 3 interconnect-cost model (2:1 multiplexer
count as an area/wiring proxy) for the four slide-unit configurations the
paper plots, including the ~70% saving of the chosen design point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .vector_engine import log2i


def decompose_pow2(amount: int) -> list[int]:
    """Binary decomposition of a slide amount into power-of-two micro-ops.
    ``11 -> [8, 2, 1]``; sign is carried on each term."""
    sign = 1 if amount >= 0 else -1
    amount = abs(amount)
    return [sign * (1 << b) for b in range(amount.bit_length() - 1, -1, -1)
            if amount >> b & 1]


# ---------------------------------------------------------------------------
# Intra-array slides (vslideup/vslidedown semantics, zero fill).
# ---------------------------------------------------------------------------

def _shift1(x: jnp.ndarray, amount: int, axis: int, fill) -> jnp.ndarray:
    """One micro-op: shift by ``amount`` (any value) along ``axis``."""
    if amount == 0:
        return x
    n = x.shape[axis]
    pad = [(0, 0)] * x.ndim
    if amount > 0:  # vslideup: element i -> i + amount
        pad[axis] = (amount, 0)
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(0, n)
    else:
        pad[axis] = (0, -amount)
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(-amount, n - amount)
    return jnp.pad(x, pad, constant_values=fill)[tuple(sl)]


def slide(x: jnp.ndarray, amount: int, axis: int = 0, fill=0) -> jnp.ndarray:
    """Arbitrary-amount slide decomposed into power-of-two micro-ops.

    Functionally equal to a single shift (property-tested); structurally it
    mirrors the Ara2 hardware: each micro-op is a power-of-two shift the
    optimized SLDU supports natively."""
    for step in decompose_pow2(amount):
        x = _shift1(x, step, axis, fill)
    return x


def rotate(x: jnp.ndarray, amount: int, axis: int = 0) -> jnp.ndarray:
    """Circular slide via pow2 micro-ops (used by FFT butterflies)."""
    n = x.shape[axis]
    amount %= n
    out = x
    for step in decompose_pow2(amount):
        out = jnp.roll(out, step, axis=axis)
    return out


# ---------------------------------------------------------------------------
# Mesh-level slides (shard rotation across a named mesh axis).
# ---------------------------------------------------------------------------

def mesh_slide(x: jnp.ndarray, amount: int, axis_name: str) -> jnp.ndarray:
    """Rotate shards by ``amount`` positions along ``axis_name`` using
    binary-weighted collective_permutes.  Must run inside ``shard_map``.

    <= log2(L) ppermute steps, each a fixed-stride neighbor-class hop on the
    ICI torus - the paper's O(L log L) argument transplanted to collectives.
    """
    size = jax.lax.axis_size(axis_name)
    amount %= size
    for step in decompose_pow2(amount):
        perm = [(i, (i + step) % size) for i in range(size)]
        x = jax.lax.ppermute(x, axis_name, perm)
    return x


def mesh_halo_exchange(x: jnp.ndarray, halo: int, axis_name: str, axis: int = 0):
    """Exchange ``halo`` boundary rows with both mesh neighbors (slide-by-one,
    the SLDU's cheapest configuration).  Returns (left_halo, right_halo) from
    the neighboring shards; edges wrap (callers mask if non-periodic)."""
    size = jax.lax.axis_size(axis_name)
    sl_lo = [slice(None)] * x.ndim
    sl_lo[axis] = slice(0, halo)
    sl_hi = [slice(None)] * x.ndim
    sl_hi[axis] = slice(x.shape[axis] - halo, x.shape[axis])
    fwd = [(i, (i + 1) % size) for i in range(size)]
    bwd = [(i, (i - 1) % size) for i in range(size)]
    right_halo = jax.lax.ppermute(x[tuple(sl_lo)], axis_name, bwd)  # from right nbr
    left_halo = jax.lax.ppermute(x[tuple(sl_hi)], axis_name, fwd)   # from left nbr
    return left_halo, right_halo


# ---------------------------------------------------------------------------
# Interconnect cost model (Fig 3) - 2:1 mux count as area/wiring proxy.
# ---------------------------------------------------------------------------

# Element widths whose re-encodings ("reshuffles") the SLDU must support, and
# the byte fan-in each re-encoding contributes per output byte.
_RESHUFFLE_EWS = (16, 32, 64)
_RESHUFFLE_FANIN_PER_EW = 8


def mux_count(n_lanes: int, mode: str = "slideP2_tmux") -> int:
    """Number of 2:1 multiplexers for a slide-unit interconnect over the
    ``B = 8 * L`` lane bytes.  An n-to-1 mux costs n-1 2:1 muxes.

    Modes (Fig 3):
      * ``all_to_all``    - arbitrary slides + same-cycle reshuffle: every
        output byte selects among all B input bytes.
      * ``slideP2_tmux``  - the Ara2 design point: power-of-two slides only,
        slide XOR reshuffle time-multiplexed (fan-in: 2*log2(B) slide sources
        + 8 re-encode sources per supported EW).
      * ``slideP2``       - power-of-two slides only, no reshuffle support.
      * ``slide1``        - slide-by-one only (+identity).
    """
    bytes_total = 8 * n_lanes
    lb = log2i(bytes_total)
    fanin = {
        "all_to_all": bytes_total,
        "slideP2_tmux": 2 * lb + _RESHUFFLE_FANIN_PER_EW * len(_RESHUFFLE_EWS),
        "slideP2": 2 * lb + 1,
        "slide1": 3,
    }[mode]
    return bytes_total * (max(fanin, 1) - 1)


def sldu_saving(n_lanes: int) -> float:
    """Predicted area/wiring saving of the optimized SLDU (paper: 'saving up
    to 70% of the estimated area and wires')."""
    return 1.0 - mux_count(n_lanes, "slideP2_tmux") / mux_count(n_lanes, "all_to_all")
