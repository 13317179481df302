"""Open loop: requests are sent on a schedule, whatever the server does.

Traffic keys: ``rate_per_s`` (mean arrival rate), ``prompt_len`` and
``output_len`` (length specs, see :mod:`.lengths`), ``drain_s`` (how
long after the window the loop keeps serving, to give every request that
arrived in the window its first token).

The window holds ``round(rate_per_s * seconds)`` arrivals at Poisson-like
gaps: the stratified exponential gaps of :func:`.lengths.exp_gaps`,
scaled to span the window, in the seed's order.  Prompts are token ids
drawn uniformly from the vocabulary; decoding is greedy and each request
stops at its output length.
"""
from __future__ import annotations

import collections

import numpy as np

from ..client import Req
from . import lengths


def plan(traffic: dict, seed: int, seconds: float, vocab: int) -> list:
    """[(send time from window start, prompt ids, output length)]."""
    rng = np.random.default_rng(seed)
    n = max(1, round(traffic["rate_per_s"] * seconds))
    gaps = lengths.exp_gaps(traffic["rate_per_s"], n, rng)
    at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * seconds / gaps.sum()
    plens = lengths.draw(traffic["prompt_len"], n, rng)
    outs = lengths.draw(traffic["output_len"], n, rng)
    return [(float(t), rng.integers(0, vocab, p).tolist(), o)
            for t, p, o in zip(at, plens, outs)]


def run(traffic: dict, drv, *, seed: int, seconds: float, vocab: int,
        max_batch: int, window) -> None:
    """Serve the schedule through ``drv``; ``window.start()`` returns the
    host time the window opens, ``window.end()`` closes it."""
    del max_batch
    sched = plan(traffic, seed, seconds, vocab)
    queue: collections.deque = collections.deque()
    drv.begin()
    t0 = window.start()
    t_end = t0 + seconds
    deadline = t_end + traffic["drain_s"]
    i, closed = 0, False
    try:
        while True:
            now = drv.clock()
            if not closed and now >= t_end:
                window.end()
                closed = True
            while i < len(sched) and t0 + sched[i][0] <= now:
                t, prompt, out = sched[i]
                r = Req(rid=i, prompt=prompt, max_new=out, arrival=t0 + t,
                        enqueued=now)
                drv.add(r)
                queue.append(r)
                i += 1
            while queue and drv.can_admit(queue[0]):
                drv.admit(queue.popleft())
            if closed and i == len(sched) and not queue and all(
                    r.tokens for r in drv.reqs.values()):
                break
            if now >= deadline:
                break
            if drv.engine.session_active:
                drv.step("drain" if closed else "step")
            elif i < len(sched):
                drv.wait_until(t0 + sched[i][0])
            elif queue:
                raise RuntimeError("requests queued but none admissible on "
                                   "an idle engine")
    finally:
        if not closed:
            window.end()
        drv.abort()
