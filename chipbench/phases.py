"""The program's own phase spans in a traced run, and what the device was
doing under them.

The engine opens a profiler annotation around each phase of its work
(``repro.serving.telemetry.phase``: ``engine.step``, ``engine.admit``,
``engine.prefill``, ``engine.grow``, ``engine.decode_dispatch``,
``engine.sample_dispatch``, ``engine.result_wait``, ``engine.emit``,
``engine.release``) and of Python's larger garbage collections
(``python.gc.gen1``, ``python.gc.gen2``).  They land on the profiler's
host plane, on the clock of the device's ``XLA Ops``, beside the
harness's own spans (:data:`chipbench.tracing.HOST_SPANS`), which stay
the only names :mod:`chipbench.tracing` reads.

:func:`load` reads the program's spans, the harness's spans and the
device's operations from the ``.xplane.pb`` into plain tuples, so
:func:`reduce` runs on hand-made data in the tests.  Inside the
harness's ``window`` span it gives, per phase, the count, total and self
time (duration minus the part its child spans cover); each engine step's
host time (the ``engine.step`` span minus its ``engine.result_wait``
child); and each piece of device idle time put down to the innermost
program span over it, named ``<harness span>/<program span>``.

A program without these spans (an older commit) reduces to no phases,
and the readers built on :func:`for_run` then report nothing.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import os
import sys

from . import tracing

#: name prefixes of the program's spans
PROGRAM = ("engine.", "python.gc.")


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM)


@dataclasses.dataclass
class Spans:
    ops: list      # device XLA Ops as (start_ns, end_ns)
    host: list     # (name, start_ns, end_ns, line): harness and program


def load(path: str) -> Spans:
    """Read the device's operations and the host spans :func:`reduce`
    needs (the line tells host threads apart)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    harness = set(tracing.HOST_SPANS) | {"window"}
    ops, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                             (plane.name, k))
                            for e in line.events
                            if e.name in harness or is_program(e.name))
    return Spans(ops=ops, host=host)


def _owners(spans):
    """Disjoint segments ``(start, end, harness, program)``: over each,
    the harness span and the innermost program span (the latest to
    start, the shortest of those) that cover it, or None."""
    ev = []
    for k, (_, s, e) in enumerate(spans):
        if e > s:
            ev.append((s, 1, k))
            ev.append((e, 0, k))
    ev.sort()                    # at one instant, ends before starts
    active: dict[int, tuple] = {}
    out = []
    prev = None
    for t, start, k in ev:
        if active and t > prev:
            harness = program = None
            best = None
            for n, s, e in active.values():
                if is_program(n):
                    if best is None or (s, -e) > best:
                        best, program = (s, -e), n
                else:
                    harness = n
            out.append((prev, t, harness, program))
        prev = t
        if start:
            active[k] = spans[k]
        else:
            del active[k]
    return out


def _label(harness, program) -> str:
    if program is None:
        return harness or "other"
    return f"{harness}/{program}" if harness else program


def reduce(sp: Spans, *, top: int = 10) -> dict:
    """Phases, step host times and idle attribution inside the window."""
    win = [(s, e) for n, s, e, _ in sp.host if n == "window"]
    if not win:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = win[0]
    busy = tracing._union(tracing._clip(sp.ops, lo, hi))
    gaps, prev = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)

    inside = [(n, max(s, lo), min(e, hi), line) for n, s, e, line in sp.host
              if n != "window" and e > lo and s < hi]
    prog = [x for x in inside if is_program(x[0])]
    count, total = collections.Counter(), collections.Counter()
    by_line = collections.defaultdict(list)
    for n, s, e, line in prog:
        count[n] += 1
        total[n] += e - s
        by_line[line].append((n, s, e))
    self_ns = collections.Counter()
    for spans in by_line.values():
        self_ns.update(tracing._self_times(spans))

    # whole spans only: one the window cuts is not a step's duration
    whole = [x for x in sp.host if is_program(x[0])
             and lo <= x[1] and x[2] <= hi]
    waits = collections.defaultdict(list)
    for n, s, e, line in whole:
        if n == "engine.result_wait":
            waits[line].append((s, e))
    for w in waits.values():
        w.sort()
    step_host = []
    for n, s, e, line in whole:
        if n == "engine.step":
            w = waits[line]
            i = bisect.bisect_left(w, (s,))
            waited = 0
            while i < len(w) and w[i][0] < e:
                waited += min(w[i][1], e) - w[i][0]
                i += 1
            step_host.append((e - s - waited) / 1e9)
    dispatch = [(e - s) / 1e9 for n, s, e, _ in whole
                if n == "engine.decode_dispatch"]

    segs = _owners([(n, s, e) for n, s, e, _ in inside])
    starts = [s for s, _, _, _ in segs]
    idle = collections.Counter()
    named_gaps = []
    in_program = 0
    for g0, g1 in gaps:
        pieces = collections.Counter()
        covered = 0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(segs) and segs[i][0] < g1:
            s, e, harness, program = segs[i]
            o = min(e, g1) - max(s, g0)
            if o > 0:
                pieces[_label(harness, program)] += o
                covered += o
                if program is not None:
                    in_program += o
            i += 1
        if g1 - g0 > covered:
            pieces["other"] += g1 - g0 - covered
        idle.update(pieces)
        named_gaps.append((pieces.most_common(1)[0][0], g1 - g0))
    idle_ns = sum(g1 - g0 for g0, g1 in gaps)
    longest = sorted(named_gaps, key=lambda g: -g[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": idle_ns / 1e9,
        "phases": {n: {"count": count[n], "total_s": total[n] / 1e9,
                       "self_s": self_ns[n] / 1e9} for n in count},
        "step_host_s": step_host,
        "decode_dispatch_s": dispatch,
        "idle_by_phase": {n: d / 1e9 for n, d in idle.most_common()},
        "idle_gaps": [[n, d / 1e9] for n, d in longest],
        "idle_in_program": in_program / idle_ns if idle_ns else None,
    }


def summary(red: dict) -> str:
    """The one ``[phases]`` log line of a reduction."""
    share = red["idle_in_program"]
    phases = {n: [p["count"], p["total_s"], p["self_s"]]
              for n, p in sorted(red["phases"].items())}
    return (f"[phases] idle_s={red['idle_s']} idle_in_program="
            f"{'none' if share is None else f'{100 * share}%'} "
            f"phases(count,total_s,self_s)={phases} "
            f"idle_by_phase={red['idle_by_phase']} "
            f"idle_gaps={red['idle_gaps']}")


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime: float) -> dict:
    red = reduce(load(path))
    print(summary(red), file=sys.stderr, flush=True)
    return red


def _newest_xplane(trace_dir) -> str | None:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def for_run(run) -> dict | None:
    """The reduction of a traced run's profile: the newest trace the
    harness wrote (``harness.TRACE_DIR``; each run clears its cell's
    directory first), reduced once and logged as one ``[phases]`` line
    on standard error.  None for an untraced run."""
    if run.trace is None:
        return None
    from .harness import TRACE_DIR
    path = _newest_xplane(TRACE_DIR)
    if path is None:
        return None
    return _reduce_file(path, os.path.getmtime(path))
