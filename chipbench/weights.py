"""Weights from a seed, made on the device in one jitted call.

The benchmark makes the weights itself, in the program's parameter
layout (its template tree gives each leaf's shape, dtype and init kind)
and in the dtype each leaf is served in.  The plain references read the
same arrays, so neither side's numbers come from the other.

Every leaf is random, the norm gains and biases too, so that a reference
that mistook one of them would disagree with the program: ``scaled``
leaves are N(0, 1/fan_in), ``normal`` leaves N(0, scale or 0.02), and
leaves the program would start at zero or one are N(0, 0.1) and
1 + N(0, 0.1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: standard deviation of the leaves the program initialises to 0 or 1
GAIN_STD = 0.1


def seed_key(seed: int):
    """A PRNG key from any non-negative integer (seeds may pass
    2**31): the low and high 31-bit parts are folded in one by one."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(0)
    while True:
        key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
        seed >>= 31
        if not seed:
            return key


def _leaf(t, key):
    z = jax.random.normal(key, t.shape, jnp.float32)
    if t.init == "scaled":
        fan_in = t.shape[-2] if len(t.shape) >= 2 else t.shape[-1]
        z = z * (t.scale if t.scale is not None else 1.0 / np.sqrt(fan_in))
    elif t.init == "normal":
        z = z * (t.scale if t.scale is not None else 0.02)
    elif t.init == "zeros":
        z = z * GAIN_STD
    elif t.init == "ones":
        z = 1.0 + z * GAIN_STD
    else:
        raise ValueError(f"no benchmark init for template kind {t.init!r}")
    return z.astype(t.dtype)


def make_weights(templates, seed: int, is_template):
    """The whole parameter tree of ``templates`` (leaves recognised by
    ``is_template``) from ``seed``, in one jitted call on the default
    device."""
    leaves, treedef = jax.tree_util.tree_flatten(templates,
                                                 is_leaf=is_template)

    def build(key):
        return treedef.unflatten(
            [_leaf(t, jax.random.fold_in(key, i))
             for i, t in enumerate(leaves)])

    return jax.jit(build)(seed_key(seed))
