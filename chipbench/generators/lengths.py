"""Stratified draws: the same multiset of values for every seed.

A length spec is a dict from a traffic file:

  {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b,
   "grid": g}
  {"dist": "uniform", "min": a, "max": b, "grid": g}

``grid`` (optional) rounds each value up to a multiple of ``g`` after
clipping, so that every shape the mix can draw is known before the run.
``draw`` takes the ``n`` quantiles at (i + 0.5) / n and hands them back
in the order the seed's generator permutes them.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def quantile(spec: dict, u: float) -> float:
    """The spec's distribution at probability ``u`` in (0, 1), unclipped."""
    kind = spec["dist"]
    if kind == "lognormal":
        return spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
    if kind == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    raise ValueError(f"unknown length distribution {kind!r}")


def shape(spec: dict, x: float) -> int:
    """Clip to [min, max], then round up to the grid."""
    v = min(max(math.ceil(x), spec["min"]), spec["max"])
    g = spec.get("grid", 1)
    return -(-v // g) * g


def support(spec: dict) -> list[int]:
    """Every value ``draw`` can return for this spec (the warm-up set)."""
    g = spec.get("grid", 1)
    lo = shape(spec, spec["min"])
    return list(range(lo, shape(spec, spec["max"]) + 1, g))


def draw(spec: dict, n: int, rng: np.random.Generator) -> list[int]:
    vals = [shape(spec, quantile(spec, (i + 0.5) / n)) for i in range(n)]
    return [vals[i] for i in rng.permutation(n)]


def exp_gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified exponential gaps of mean ``1 / rate``, permuted."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u)[rng.permutation(n)] / rate
