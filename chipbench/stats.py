"""Samples and statistics the metric readers share."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile (the sample at rank ceil(q/100 * n))."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def in_window(run, t: float) -> bool:
    return run.t0 <= t < run.t1


def ttft_ms(run) -> list[float]:
    """Scheduled arrival to first token, for requests that arrived in the
    window and got a first token."""
    return [1e3 * (r.token_times[0] - r.arrival) for r in run.reqs
            if in_window(run, r.arrival) and r.token_times]


def itl_ms(run) -> list[float]:
    """Gaps between consecutive tokens of one request whose later token
    landed in the window."""
    out = []
    for r in run.reqs:
        tt = r.token_times
        out.extend(1e3 * (b - a) for a, b in zip(tt, tt[1:])
                   if in_window(run, b))
    return out


def decode_launches(run) -> list[list[int]]:
    """KV lengths of the live rows of each decode launch in the window."""
    return [kv for t, kv in run.decode_log if in_window(run, t)]


def prefilled(run) -> list:
    """Requests whose prefill finished in the window."""
    ids = {rid for t, rid in run.prefill_log if in_window(run, t)}
    return [r for r in run.reqs if r.rid in ids]
