"""Utilization attribution: roofline-joined step accounting and
bottleneck classification.

Every headline number in Ara2 is *cycle accounting*: §6 instruments
functional-unit utilization per kernel (95% on compute-bound matmul),
and the short-vector regimes are diagnosed as *issue-rate bound* — the
scalar core cannot feed the lanes fast enough — while other kernels pin
the memory system.  PR 7's telemetry records where wall-clock goes
(dispatch vs device spans, slot occupancy) but not *why*: a 4 ms step
span does not say whether the step was starved by dispatch, by HBM, or
was genuinely compute-saturated.

This module closes that gap by joining the two measurement layers the
repo already has:

* the **telemetry** spans/metrics (``repro.serving.telemetry``): per
  decode launch, the host-side dispatch time ``[t0, t_disp]``, the
  blocking device time ``[t_disp, t1]``, and how many of the launch's
  fixed ``max_batch`` slot lanes held a live request;
* the **roofline cost layer** (``repro.roofline.hlo_cost``): exact
  flops and HBM bytes of each compiled executable — the decode step,
  the paged prefill chunk, the dense prefill — read off the compiled
  HLO text with while-trip scaling (the same parser the dry-run
  roofline uses), lowered once per (phase, shape) and memoized.

Joined, every step gets an **attribution record**: achieved FLOP/s and
bytes/s against a :class:`MachineSpec` roofline, and a **bottleneck
verdict** mirroring the paper's §6 regimes:

  ``issue``   - host dispatch dominates the launch (the serving twin of
                the scalar core's issue-rate bound on short vectors);
  ``memory``  - device-bound with useful arithmetic intensity below the
                machine's ridge point (flops/byte where the roofline
                bends);
  ``compute`` - device-bound above the ridge (the regime where Ara2
                reports 95% FU utilization);
  ``idle``    - the launch carried no live request at all.

The engine-level ``fu_utilization`` figure — useful flops (idle lanes
excluded, exactly like idle vector lanes in the paper) per second of
device time, over the machine's peak — is the serving analog of the
paper's FU-utilization headline, and it aggregates across a cluster by
the same lossless-merge discipline as every other metric: replicas
record raw per-step samples into their registries, the cluster
concatenates them, and the figure is derived from the union.

Like tracing, attribution must be free when off and invisible when on:
the default :data:`NULL_ATTR` is a no-op guarded by ``enabled`` on the
hot path (bounded by the ``serving_attr_overhead`` bench row), and an
enabled :class:`Attributor` is host-side only — it never touches the
compiled functions the engine executes (costs come from a *separate*
AOT lowering of the same jitted callables), so tokens are byte-identical
with attribution on vs off (asserted across the conformance matrix).
"""
from __future__ import annotations

import dataclasses
import threading

from ..roofline.hlo_cost import HloCost

#: Bottleneck verdicts, mapped to the paper's §6 regimes (see module doc).
VERDICTS = ("issue", "memory", "compute", "idle")


@dataclasses.dataclass(frozen=True)
class MachineSpec:
    """The machine roofline attribution measures against: peak FLOP/s,
    peak memory bytes/s, and the derived ridge point (the arithmetic
    intensity where the roofline bends from the bandwidth slope onto the
    flat compute ceiling)."""
    name: str
    peak_flops: float              # FLOP/s
    mem_bw: float                  # bytes/s

    @property
    def ridge(self) -> float:
        """Ridge-point arithmetic intensity (flops per byte)."""
        return self.peak_flops / max(self.mem_bw, 1e-9)

    @classmethod
    def for_kind(cls, device_kind: str) -> "MachineSpec":
        """The :data:`PEAKS` row of a jax ``device_kind``; a kind that is
        not in the table raises instead of borrowing another device's
        peaks."""
        try:
            return PEAKS[device_kind]
        except KeyError:
            raise ValueError(
                f"no peak FLOP/s and bandwidth known for device_kind "
                f"{device_kind!r}; add a sourced row to "
                f"repro.serving.attribution.PEAKS (known: "
                f"{sorted(PEAKS)})") from None

    @classmethod
    def detect(cls) -> "MachineSpec":
        """The row of the first jax device's ``device_kind``."""
        import jax
        return cls.for_kind(jax.devices()[0].device_kind)


#: Peaks per jax ``device_kind`` — the one table attribution reads.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and
    # 819 GB/s HBM bandwidth per chip
    "TPU v5 lite": MachineSpec("tpu-v5e", 197e12, 819e9),
    # nominal, not a published or measured figure: it lets the CPU tests
    # drive the classifier; no utilization from it is a device metric
    "cpu": MachineSpec("cpu-nominal", 50e9, 25e9),
}


@dataclasses.dataclass(frozen=True)
class PhaseCost:
    """Per-launch cost of one compiled executable (per device)."""
    flops: float
    mem_bytes: float

    @property
    def ai(self) -> float:
        """Arithmetic intensity (flops per HBM byte)."""
        return self.flops / max(self.mem_bytes, 1e-9)


class NullAttributor:
    """Zero-overhead default: every method is a no-op.  Hot paths guard
    on ``enabled`` (one attribute check per decode step, same contract
    as :class:`~repro.serving.telemetry.NullTracer`)."""

    enabled = False

    def phase_cost(self, key, jitted, args):
        return None

    def record_step(self, metrics, tracer, track, *, t0, t_disp, t1,
                    active, width, cost):
        pass

    def record_prefill(self, metrics, tracer, track, *, t0, t1, cost,
                       tokens=0):
        pass


NULL_ATTR = NullAttributor()


class Attributor(NullAttributor):
    """Recording attributor: joins span timings with executable costs.

    ``spec`` is the roofline to measure against (default: detected from
    the jax backend).  ``issue_threshold`` is the dispatch fraction of a
    launch above which the step is called issue-bound (default 0.5 —
    the host spent at least as long feeding the launch as the device
    spent computing it, the §6 short-vector signature).

    One Attributor may be shared by every replica of a cluster: the cost
    memo is keyed by (phase, shape) so identical replicas lower each
    executable once, and all recording goes into the *caller's* metrics
    registry, which the cluster merges losslessly.
    """

    enabled = True

    def __init__(self, spec: MachineSpec | None = None,
                 issue_threshold: float = 0.5):
        self.spec = spec if spec is not None else MachineSpec.detect()
        self.issue_threshold = float(issue_threshold)
        self._costs: dict = {}
        self._lock = threading.Lock()

    # -- cost extraction ----------------------------------------------

    def phase_cost(self, key, jitted, args) -> PhaseCost:
        """Flops/bytes of ``jitted`` at the shapes of ``args``, memoized
        by ``key``.  A cache miss lowers and compiles a *separate* AOT
        executable of the same function (host-side; the engine's own
        compiled callables and their device buffers are untouched) and
        reads the cost off its HLO text with the while-trip-scaled
        parser the dry-run roofline uses — ``cost_analysis()`` counts
        ``lax.scan`` layer stacks once, which would undercount every
        model here by ~n_layers."""
        c = self._costs.get(key)
        if c is not None:
            return c
        compiled = jitted.lower(*args).compile()
        cost = HloCost(compiled.as_text()).cost()
        c = PhaseCost(float(cost.flops), float(cost.mem_bytes))
        with self._lock:
            c = self._costs.setdefault(key, c)
        return c

    # -- classification -----------------------------------------------

    def classify(self, *, active: int, width: int, dispatch_s: float,
                 device_s: float, cost: PhaseCost) -> str:
        """Bottleneck verdict for one decode launch (see module doc for
        the paper mapping).  ``active``/``width`` are live vs launched
        slot lanes; the *useful* arithmetic intensity scales the
        executable's flops by the live fraction (idle lanes do useless
        work but still drag their rows through the memory system — the
        fixed-width cost `bench_cluster` measures), so a mostly-idle
        launch correctly reads memory-bound even when the executable's
        nominal intensity clears the ridge."""
        if active <= 0:
            return "idle"
        total = dispatch_s + device_s
        if total > 0.0 and dispatch_s >= self.issue_threshold * total:
            return "issue"
        useful_ai = cost.ai * (active / max(width, 1))
        return "memory" if useful_ai < self.spec.ridge else "compute"

    # -- recording ----------------------------------------------------

    def record_step(self, metrics, tracer, track, *, t0, t_disp, t1,
                    active, width, cost) -> None:
        """Attribute one decode launch: verdict counter, raw per-step
        samples (useful flops, bytes, dispatch/device ms — histograms,
        so cluster aggregation stays lossless), and, when a tracer is
        live, a per-step ``roofline`` counter track (percent-of-peak
        FLOP/s and bytes/s) that Perfetto draws alongside the lifecycle
        spans."""
        dispatch_s = max(t_disp - t0, 0.0)
        device_s = max(t1 - t_disp, 0.0)
        verdict = self.classify(active=active, width=width,
                                dispatch_s=dispatch_s, device_s=device_s,
                                cost=cost)
        useful_flops = cost.flops * (active / max(width, 1))
        m = metrics
        m.counter(f"attr_verdict_{verdict}").inc()
        m.histogram("attr_step_flops").observe(useful_flops)
        m.histogram("attr_step_bytes").observe(cost.mem_bytes)
        m.histogram("attr_dispatch_ms").observe(dispatch_s * 1e3)
        m.histogram("attr_device_ms").observe(device_s * 1e3)
        m.gauge("attr_peak_flops").set(self.spec.peak_flops)
        m.gauge("attr_peak_bytes_s").set(self.spec.mem_bw)
        m.gauge("attr_decode_ai").set(cost.ai)
        if tracer.enabled:
            step_s = max(t1 - t0, 1e-12)
            tracer.counter(
                track, "roofline",
                flops_pct=100.0 * useful_flops / (step_s
                                                  * self.spec.peak_flops),
                bytes_pct=100.0 * cost.mem_bytes / (step_s
                                                    * self.spec.mem_bw))

    def record_prefill(self, metrics, tracer, track, *, t0, t1, cost,
                       tokens=0) -> None:
        """Attribute one prefill launch (a paged chunk or a dense
        prefill call).  Prefill has no dispatch/device split recorded
        (the chunk call returns asynchronously and the engine must not
        add a device sync just to measure it), so the verdict is pure
        roofline: the executable's arithmetic intensity against the
        ridge — prefill batches whole prompts, the paper's long-vector
        regime, where issue rate stops being the bound."""
        dt = max(t1 - t0, 0.0)
        verdict = "memory" if cost.ai < self.spec.ridge else "compute"
        m = metrics
        m.counter(f"attr_prefill_verdict_{verdict}").inc()
        m.histogram("attr_prefill_flops").observe(cost.flops)
        m.histogram("attr_prefill_bytes").observe(cost.mem_bytes)
        m.histogram("attr_prefill_ms").observe(dt * 1e3)
        m.gauge("attr_peak_flops").set(self.spec.peak_flops)
        m.gauge("attr_peak_bytes_s").set(self.spec.mem_bw)
        if tracer.enabled:
            span_s = max(dt, 1e-12)
            tracer.counter(
                track, "roofline",
                flops_pct=100.0 * cost.flops / (span_s
                                                * self.spec.peak_flops),
                bytes_pct=100.0 * cost.mem_bytes / (span_s
                                                    * self.spec.mem_bw))


def dominant_verdict(counts: dict) -> str:
    """The verdict with the most steps ('' when nothing was recorded);
    ties break by the VERDICTS order (issue first — the paper's default
    suspicion for short-vector serving workloads)."""
    best, best_n = "", 0
    for v in VERDICTS:
        n = counts.get(v, 0)
        if n > best_n:
            best, best_n = v, n
    return best
