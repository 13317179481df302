"""The client side of a run: requests, their token times, the engine calls.

One thread drives one ``ServeEngine`` through its stepwise session API
(``begin_session`` / ``session_admit`` / ``session_step``), as an outer
scheduler would.  Every token arrives through the session's ``on_token``
callback and is stamped on the host clock the moment the engine hands it
over.  Each call into the engine sits inside a named host span
(``admit``, ``step``, ``drain``, ``wait``) that the profiler records
beside the device's operations, so idle gaps can be named by what the
host was doing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax


@dataclasses.dataclass
class Req:
    """One request and what the client saw of it."""
    rid: int
    prompt: list
    max_new: int
    arrival: float                 # scheduled send time (host clock)
    enqueued: float = 0.0          # when the loop put it in the queue
    admitted: float | None = None  # when the loop called session_admit
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    finished: float | None = None


class Client:
    """Wraps one engine session; keeps every request's record and, per
    decode launch, the KV length each live row attends over."""

    def __init__(self, engine, *, traced: bool, clock=time.perf_counter):
        self.engine = engine
        self.clock = clock
        self.traced = traced
        self.reqs: dict[int, Req] = {}
        self.live: set[int] = set()
        # (host time the launch's tokens landed, [kv_len per live row])
        self.decode_log: list[tuple[float, list[int]]] = []
        # (host time, rid) for every request whose prefill finished
        self.prefill_log: list[tuple[float, int]] = []

    def span(self, name: str):
        if self.traced:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    # -- engine session ------------------------------------------------

    def begin(self) -> None:
        self.engine.begin_session(key=jax.random.key(0),
                                  on_token=self._on_token)

    def abort(self) -> None:
        self.engine.session_abort()

    def _on_token(self, ev) -> None:
        r = self.reqs[ev.rid]
        t = self.clock()
        r.token_times.append(t)
        r.tokens.append(ev.token)
        if ev.index == 0:
            self.prefill_log.append((t, ev.rid))

    def _finish(self, rid: int) -> None:
        r = self.reqs[rid]
        r.finished = self.clock()
        self.live.discard(rid)

    def add(self, r: Req) -> None:
        self.reqs[r.rid] = r

    def can_admit(self, r: Req) -> bool:
        eng = self.engine
        return (eng.session_free_slot() is not None
                and eng.session_can_admit(self._request(r)))

    def _request(self, r: Req):
        from repro.serving import Request
        return Request(r.prompt, r.max_new, 0.0, rid=r.rid)

    def admit(self, r: Req) -> list[int]:
        """Admit ``r``; returns the rids that finished in the call."""
        r.admitted = self.clock()
        self.live.add(r.rid)
        with self.span("admit"):
            res = self.engine.session_admit(self._request(r), tag=r.rid)
        if res is not None:
            self._finish(r.rid)
            return [r.rid]
        return []

    def step(self, span: str = "step") -> list[int]:
        """One engine step; logs the decode launch's KV lengths and
        returns the rids that finished."""
        before = {rid: len(self.reqs[rid].tokens) for rid in self.live}
        with self.span(span):
            done = self.engine.session_step()
        t = self.clock()
        kv = []
        for rid, n0 in before.items():
            r = self.reqs[rid]
            # token i >= 1 comes from the decode launch that attends over
            # the prompt and the i tokens before it written to the cache
            for i in range(max(n0, 1), len(r.tokens)):
                kv.append(len(r.prompt) + i)
        if kv:
            self.decode_log.append((t, kv))
        out = [tag for tag, _ in done]
        for rid in out:
            self._finish(rid)
        return out

    def wait_until(self, t: float) -> None:
        """Sleep until host time ``t`` (nothing to step)."""
        with self.span("wait"):
            dt = t - self.clock()
            if dt > 0:
                time.sleep(dt)
