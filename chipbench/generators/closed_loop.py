"""Closed loop: a fixed set of clients, each sending its next request the
moment its previous one finishes.

Traffic keys: ``clients_per_slot`` (clients per engine slot, so a queue
always waits), ``pool_per_client`` (requests drawn per client; the pool
is reused in order if a run gets through it), ``prompt_len`` and
``output_len`` (length specs, see :mod:`.lengths`), ``ramp_completions``
(share of the slots that must have turned over before the window opens).

The first wave, one request per slot, is sent already under way: its
output lengths are the remainders ``u * L`` of the slot count's
stratified quantiles ``L`` of the output spec, paired with stratified
``u`` (longest with least left), so the slots turn over from the start
and the window opens on requests of spread ages instead of a batch that
all started together.  Every seed sends the same lengths in its own
order.  The ramp (set-up) serves until every slot has been
filled and ``ramp_completions * max_batch`` requests have finished.
"""
from __future__ import annotations

import collections
import math

import numpy as np

from ..client import Req
from . import lengths


class Pool:
    """The run's requests in send order: same lengths for every seed."""

    def __init__(self, traffic: dict, seed: int, vocab: int,
                 max_batch: int):
        self.rng = np.random.default_rng(seed)
        self.vocab = vocab
        n = traffic["pool_per_client"] * traffic["clients_per_slot"] \
            * max_batch
        spec = traffic["output_len"]
        self.plens = lengths.draw(traffic["prompt_len"], n, self.rng)
        full = sorted(lengths.shape(spec, lengths.quantile(
            spec, (i + 0.5) / max_batch)) for i in range(max_batch))
        left = [max(1, math.ceil((i + 0.5) / max_batch * length))
                for i, length in enumerate(reversed(full))]
        self.outs = [left[i] for i in self.rng.permutation(max_batch)] \
            + lengths.draw(spec, n - max_batch, self.rng)
        self.k = 0

    def next(self, now: float) -> Req:
        j = self.k % len(self.plens)
        r = Req(rid=self.k, max_new=self.outs[j], arrival=now, enqueued=now,
                prompt=self.rng.integers(0, self.vocab,
                                         self.plens[j]).tolist())
        self.k += 1
        return r


def run(traffic: dict, drv, *, seed: int, seconds: float, vocab: int,
        max_batch: int, window) -> None:
    pool = Pool(traffic, seed, vocab, max_batch)
    queue: collections.deque = collections.deque()
    drv.begin()

    def send():
        r = pool.next(drv.clock())
        drv.add(r)
        queue.append(r)

    def turn():
        """Admit what fits, step once; each finished client sends again.
        Returns (requests finished, every slot was busy)."""
        while queue and drv.can_admit(queue[0]):
            for _ in drv.admit(queue.popleft()):
                send()
        full = drv.engine.session_active == max_batch
        done = drv.step() if drv.engine.session_active else []
        for _ in done:
            send()
        return len(done), full

    try:
        for _ in range(traffic["clients_per_slot"] * max_batch):
            send()
        need = math.ceil(traffic["ramp_completions"] * max_batch)
        finished, filled = 0, False
        while not (filled and finished >= need):
            n, full = turn()
            finished += n
            filled = filled or full
        t_end = window.start() + seconds
        while drv.clock() < t_end:
            turn()
    finally:
        window.end()
        drv.abort()
