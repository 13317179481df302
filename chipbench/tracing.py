"""Reduction of a JAX profiler trace to device busy time, executables,
kernels and idle gaps.

A trace is read with ``jax.profiler.ProfileData`` (nothing but JAX) into
plain tuples first (:func:`load`), so the reduction itself
(:func:`reduce`) runs on recorded data in the tests.  What it reads:

* the device plane (``/device:TPU:0``): its ``XLA Ops`` line gives every
  operation's interval (they nest: a scanned layer loop contains the ops
  of its body), its ``XLA Modules`` line every executable's launch,
  named ``<root>(<fingerprint>)``;
* the host plane: the harness's own spans (``jax.profiler.TraceAnnotation``
  around its calls into the engine, and ``window`` around the measured
  window).

Busy time is the union of the operations' intervals inside the window.
An executable is classified by its root name and, where two share a root
(the program jits ``functools.partial`` objects, which all come out as
``jit__unknown``), by an operation that only it contains.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

#: host spans the harness writes; an idle gap takes the name of the one
#: it overlaps most
HOST_SPANS = ("admit", "step", "drain", "wait")


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns) tuples."""
    ops: list          # device XLA Ops, nested
    modules: list      # device XLA Modules
    host: list         # the harness's host spans and the window span


def _short(op_name: str) -> str:
    """``%fusion.3 = bf16[...] ...`` -> ``fusion.3``."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def _base(short: str) -> str:
    """``paged_decode_attention_pallas.9`` -> ``paged_decode_attention_pallas``."""
    return re.sub(r"\.\d+$", "", short)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read the device and host events that :func:`reduce` needs."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    wanted = set(HOST_SPANS) | {"window"}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((_short(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name in wanted)
    return Trace(ops=ops, modules=modules, host=host)


def _union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _self_times(ops):
    """Self time of each op: its length minus that of the ops nested in
    it (ops of one line nest strictly or not at all)."""
    out = collections.Counter()
    stack = []          # [name, end, child_time]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, _, _, dur, child = stack.pop()
            out[n] += dur - child
        if stack:
            stack[-1][4] += e - s
        stack.append([name, e, s, e - s, 0])
    for n, _, _, dur, child in stack:
        out[n] += dur - child
    return out


def classify(modules, ops, rules: dict) -> dict:
    """{module name -> executable kind} for the modules that ``rules``
    names.  ``rules`` maps a kind to ``{"module": root, "op": base}``
    (``op`` optional): a module is of that kind when its root name is
    ``root`` and, if ``op`` is given, one of its launches contains an
    operation of that base name."""
    contains = collections.defaultdict(set)
    ops_sorted = sorted(ops, key=lambda o: o[1])
    starts = [o[1] for o in ops_sorted]
    seen = set()
    for name, s, e in modules:
        if name in seen:
            continue
        seen.add(name)
        i = bisect.bisect_left(starts, s)
        while i < len(ops_sorted) and ops_sorted[i][1] < e:
            contains[name].add(_base(ops_sorted[i][0]))
            i += 1
    out = {}
    for name in seen:
        root = name.split("(", 1)[0]
        for kind, rule in rules.items():
            if root == rule["module"] and (
                    "op" not in rule or rule["op"] in contains[name]):
                out[name] = kind
    return out


def reduce(tr: Trace, rules: dict, kernels: list, *, top: int = 10) -> dict:
    """Busy and idle time, device time per executable kind and per
    kernel, and the longest idle gaps named by the host span they fall
    in, all inside the harness's ``window`` span.

    ``rules``: executable kinds (see :func:`classify`); ``kernels``: op
    base names whose device time and launch count to report."""
    win = [(s, e) for n, s, e in tr.host if n == "window"]
    if not win:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = win[0]
    ops = [(n, max(s, lo), min(e, hi)) for n, s, e in tr.ops
           if e > lo and s < hi]
    busy = _union((s, e) for _, s, e in ops)
    busy_ns = sum(e - s for s, e in busy)
    kinds = classify(tr.modules, tr.ops, rules)
    exe_ns = collections.Counter()
    exe_n = collections.Counter()
    for name, s, e in tr.modules:
        part = _clip([(s, e)], lo, hi)
        if part:
            label = kinds.get(name, name.split("(", 1)[0])
            exe_ns[label] += part[0][1] - part[0][0]
            exe_n[label] += 1
    kern_ns = collections.Counter()
    kern_n = collections.Counter()
    for name, s, e in ops:
        b = _base(name)
        if b in kernels:
            kern_ns[b] += e - s
            kern_n[b] += 1
    gaps = []
    prev = lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = sorted((s, e, n) for n, s, e in tr.host if n in HOST_SPANS)
    ends = [e for _, e, _ in spans]

    def name_gap(g0, g1):
        # the harness's spans follow one another, so the candidates are
        # the run of spans from the first that ends after g0
        best, over = "other", 0
        i = bisect.bisect_right(ends, g0)
        while i < len(spans) and spans[i][0] < g1:
            o = min(spans[i][1], g1) - max(spans[i][0], g0)
            if o > over:
                best, over = spans[i][2], o
            i += 1
        return best

    named = [(name_gap(a, b), b - a) for a, b in gaps]
    idle_by_span = collections.Counter()
    for n, d in named:
        idle_by_span[n] += d
    longest = sorted(named, key=lambda g: -g[1])[:top]
    selfs = _self_times(ops)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "exe_s": {k: v / 1e9 for k, v in exe_ns.items()},
        "exe_launches": dict(exe_n),
        "kernel_s": {k: v / 1e9 for k, v in kern_ns.items()},
        "kernel_launches": dict(kern_n),
        "device_ops": [[n, v / 1e9] for n, v in selfs.most_common(top)],
        "idle_gaps": [[n, d / 1e9] for n, d in longest],
        "idle_by_span": {n: d / 1e9 for n, d in idle_by_span.items()},
    }
