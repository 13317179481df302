"""Utilization-attribution tests (see docs/observability.md).

Unit level: every bottleneck verdict is reachable and stable under a
synthetic :class:`MachineSpec` (no jax involved — the classifier is
pure arithmetic over span timings and a :class:`PhaseCost`), the
dominant-verdict tie-break follows the paper-ordered taxonomy, the
recorded ``attr_*`` metrics merge losslessly across registries, and the
:class:`EngineStats` rollup derives fu_utilization / achieved rates /
verdict counts from the merged union exactly.

Integration level: an attributed ServeEngine produces byte-identical
tokens (attribution is host-side only — its costs come from a separate
AOT lowering), positive HLO-derived costs with a memoized cost table,
``roofline`` counter events on the trace, and attribution fields on the
stats view; a cluster shares one Attributor across replicas and rolls
the replicas up through the registry merge.
"""
import jax
import pytest

from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import (NULL_ATTR, NULL_TRACER, Attributor, ClusterEngine,
                           EngineStats, FakeClock, MachineSpec,
                           MetricsRegistry, NullAttributor, PhaseCost,
                           Request, ServeEngine, Tracer, VERDICTS,
                           dominant_verdict)

CACHE_LEN = 48
BLOCK = 8
SLOTS = 3

# ridge = 100/10 = 10 flops/byte: verdicts are easy to place on either side
SPEC = MachineSpec("synthetic", peak_flops=100.0, mem_bw=10.0)


# ---------------------------------------------------------------------------
# Classifier: every verdict reachable, stable at the boundaries
# ---------------------------------------------------------------------------

def _classify(at, **kw):
    base = dict(active=4, width=4, dispatch_s=0.1, device_s=0.9,
                cost=PhaseCost(flops=100.0, mem_bytes=1.0))   # ai=100
    base.update(kw)
    return at.classify(**base)


def test_machine_spec_ridge():
    assert SPEC.ridge == pytest.approx(10.0)
    assert PhaseCost(flops=50.0, mem_bytes=2.0).ai == pytest.approx(25.0)
    assert MachineSpec.detect().peak_flops > 0     # never degenerate


def test_machine_spec_table_keyed_by_device_kind():
    v5e = MachineSpec.for_kind("TPU v5 lite")
    assert (v5e.peak_flops, v5e.mem_bw) == (197e12, 819e9)
    assert MachineSpec.detect() is MachineSpec.for_kind(
        jax.devices()[0].device_kind)
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        MachineSpec.for_kind("TPU v9 imaginary")


def test_classify_idle():
    at = Attributor(spec=SPEC)
    assert _classify(at, active=0) == "idle"


def test_classify_issue_bound():
    """Dispatch >= threshold * total launch time: the serving twin of the
    paper's scalar-core issue-rate bound, checked before the roofline."""
    at = Attributor(spec=SPEC, issue_threshold=0.5)
    assert _classify(at, dispatch_s=0.6, device_s=0.4) == "issue"
    assert _classify(at, dispatch_s=0.5, device_s=0.5) == "issue"  # boundary
    assert _classify(at, dispatch_s=0.4, device_s=0.6) != "issue"


def test_classify_memory_vs_compute():
    at = Attributor(spec=SPEC)
    lo = PhaseCost(flops=50.0, mem_bytes=10.0)      # ai=5  < ridge 10
    hi = PhaseCost(flops=500.0, mem_bytes=10.0)     # ai=50 > ridge 10
    assert _classify(at, cost=lo) == "memory"
    assert _classify(at, cost=hi) == "compute"


def test_classify_idle_lanes_drag_intensity_down():
    """Useful AI scales by the live fraction: a launch whose nominal
    intensity clears the ridge reads memory-bound when most lanes are
    idle (idle lanes still drag their rows through HBM)."""
    at = Attributor(spec=SPEC)
    hi = PhaseCost(flops=200.0, mem_bytes=10.0)     # nominal ai=20 > ridge
    assert _classify(at, cost=hi, active=4, width=4) == "compute"
    assert _classify(at, cost=hi, active=1, width=4) == "memory"   # ai -> 5


def test_classify_is_deterministic():
    at = Attributor(spec=SPEC)
    kw = dict(active=2, width=4, dispatch_s=0.2, device_s=0.8,
              cost=PhaseCost(flops=120.0, mem_bytes=10.0))
    assert len({at.classify(**kw) for _ in range(10)}) == 1


def test_dominant_verdict_order_and_ties():
    assert dominant_verdict({}) == ""
    assert dominant_verdict({"memory": 3, "compute": 1}) == "memory"
    # ties break in VERDICTS order (issue first)
    assert dominant_verdict({"memory": 2, "issue": 2}) == "issue"
    assert dominant_verdict({v: 1 for v in VERDICTS}) == "issue"


def test_null_attributor_is_inert():
    at = NULL_ATTR
    assert isinstance(at, NullAttributor) and not at.enabled
    assert at.phase_cost("k", None, ()) is None
    m = MetricsRegistry()
    at.record_step(m, NULL_TRACER, "t", t0=0.0, t_disp=1.0, t1=2.0,
                   active=1, width=1, cost=None)
    at.record_prefill(m, NULL_TRACER, "t", t0=0.0, t1=1.0, cost=None)
    assert m.snapshot() == {}


# ---------------------------------------------------------------------------
# Recording + merge + stats rollup (synthetic registries, no engine)
# ---------------------------------------------------------------------------

def _record_steps(at, m, specs):
    """specs: list of (active, dispatch_s, device_s, cost) tuples."""
    t = 0.0
    for active, disp, dev, cost in specs:
        at.record_step(m, NULL_TRACER, "trk", t0=t, t_disp=t + disp,
                       t1=t + disp + dev, active=active, width=4, cost=cost)
        t += disp + dev


def test_record_step_metrics_and_rollup():
    at = Attributor(spec=SPEC)
    m = MetricsRegistry()
    lo = PhaseCost(flops=50.0, mem_bytes=10.0)     # memory side
    hi = PhaseCost(flops=500.0, mem_bytes=10.0)    # compute side
    _record_steps(at, m, [
        (4, 0.0, 1.0, lo),     # memory
        (4, 0.0, 1.0, hi),     # compute
        (4, 0.9, 0.1, hi),     # issue
        (0, 0.0, 1.0, hi),     # idle
    ])
    assert m.counter("attr_verdict_memory").n == 1
    assert m.counter("attr_verdict_compute").n == 1
    assert m.counter("attr_verdict_issue").n == 1
    assert m.counter("attr_verdict_idle").n == 1
    assert m.histogram("attr_step_flops").count == 4
    assert m.gauge("attr_peak_flops").value == SPEC.peak_flops

    s = EngineStats.from_registry(m, mode="continuous", wall_s=4.0)
    # device time = 0.1+1+1+1 s; useful flops = 50+500+500+0
    assert s.achieved_flops_per_s == pytest.approx(1050.0 / 3.1)
    assert s.fu_utilization == pytest.approx(1050.0 / 3.1 / 100.0)
    assert s.ridge_ai == pytest.approx(10.0)
    assert s.verdict_counts == {v: 1 for v in VERDICTS}
    assert s.bottleneck == "issue"                 # tie -> paper order


def test_attr_metrics_merge_losslessly():
    """Two replica registries with attr samples: the merged rollup equals
    attribution over the union — the cluster aggregation contract."""
    at = Attributor(spec=SPEC)
    a, b = MetricsRegistry(), MetricsRegistry()
    lo = PhaseCost(flops=50.0, mem_bytes=10.0)
    _record_steps(at, a, [(4, 0.0, 1.0, lo)] * 2)
    _record_steps(at, b, [(4, 0.0, 1.0, lo)] * 3)
    a.merge(b)
    assert a.counter("attr_verdict_memory").n == 5
    assert a.histogram("attr_step_flops").count == 5
    s = EngineStats.from_registry(a, mode="continuous", wall_s=5.0)
    assert s.achieved_flops_per_s == pytest.approx(50.0)   # 250 flops / 5 s
    assert s.verdict_counts == {"memory": 5}
    assert s.bottleneck == "memory"


def test_record_prefill_pure_roofline_verdict():
    at = Attributor(spec=SPEC)
    m = MetricsRegistry()
    at.record_prefill(m, NULL_TRACER, "trk", t0=0.0, t1=0.5,
                      cost=PhaseCost(flops=50.0, mem_bytes=10.0))
    at.record_prefill(m, NULL_TRACER, "trk", t0=0.5, t1=1.0,
                      cost=PhaseCost(flops=500.0, mem_bytes=10.0))
    assert m.counter("attr_prefill_verdict_memory").n == 1
    assert m.counter("attr_prefill_verdict_compute").n == 1
    assert m.histogram("attr_prefill_ms").count == 2
    s = EngineStats.from_registry(m, mode="continuous", wall_s=1.0)
    assert s.prefill_bottleneck in ("memory", "compute")


def test_roofline_counter_track_on_trace():
    at = Attributor(spec=SPEC)
    m = MetricsRegistry()
    clock = FakeClock(start=0.0, tick=0.0)
    tr = Tracer(clock=clock)
    at.record_step(m, tr, "replica0", t0=0.0, t_disp=0.1, t1=1.0,
                   active=4, width=4, cost=PhaseCost(50.0, 10.0))
    (ev,) = tr.events()
    assert (ev.ph, ev.name, ev.track) == ("C", "roofline", "replica0")
    # 50 useful flops over a 1 s step vs 100 FLOP/s peak -> 50% of peak
    assert ev.args["flops_pct"] == pytest.approx(50.0)
    assert ev.args["bytes_pct"] == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_model():
    cfg = smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _trace(vocab, n=4, max_new=6):
    return [Request([(5 * i + j) % vocab for j in range(4 + i)], max_new,
                    temperature=0.0, rid=i) for i in range(n)]


def test_attribution_leaves_tokens_identical(smoke_model):
    cfg, model, params = smoke_model
    eng = ServeEngine(model, params, max_batch=SLOTS, cache_len=CACHE_LEN,
                      kv_layout="paged", block_size=BLOCK)
    ref = [r.tokens for r in eng.generate(_trace(cfg.vocab_size))]

    at = Attributor()
    eng.set_attributor(at)
    try:
        got = [r.tokens for r in eng.generate(_trace(cfg.vocab_size))]
    finally:
        eng.set_attributor(NULL_ATTR)
    assert got == ref

    # HLO-derived costs are real and memoized (decode + prefill chunks)
    assert at._costs and all(c.flops > 0 and c.mem_bytes > 0
                             for c in at._costs.values())
    s = eng.last_stats
    assert s.achieved_flops_per_s > 0 and s.achieved_bytes_per_s > 0
    assert s.bottleneck in VERDICTS
    assert s.prefill_bottleneck in ("memory", "compute")
    assert 0.0 < s.fu_utilization < 1.0
    assert sum(s.verdict_counts.values()) == s.decode_steps


def test_attributed_trace_carries_roofline_counters(smoke_model):
    cfg, model, params = smoke_model
    eng = ServeEngine(model, params, max_batch=SLOTS, cache_len=CACHE_LEN,
                      kv_layout="paged", block_size=BLOCK)
    tracer, at = Tracer(), Attributor()
    eng.set_tracer(tracer)
    eng.set_attributor(at)
    try:
        eng.generate(_trace(cfg.vocab_size))
    finally:
        eng.set_tracer(NULL_TRACER)
        eng.set_attributor(NULL_ATTR)
    roofs = [e for e in tracer.events() if e.name == "roofline"]
    assert roofs and all(e.ph == "C" for e in roofs)
    assert all(e.args["flops_pct"] >= 0 for e in roofs)


def test_cluster_shares_attributor_and_rolls_up(smoke_model):
    cfg, model, params = smoke_model
    cl = ClusterEngine(model, params, replicas=2, total_slots=4,
                       cache_len=CACHE_LEN, block_size=BLOCK)
    ref = [r.tokens for r in cl.generate(_trace(cfg.vocab_size))]

    at = Attributor()
    cl.set_attributor(at)
    try:
        got = [r.tokens for r in cl.generate(_trace(cfg.vocab_size))]
    finally:
        cl.set_attributor(NULL_ATTR)
    assert got == ref
    # identical replicas share one memo entry per (phase, shape) — the
    # cost table must not scale with the replica count
    phases = {k[0] for k in at._costs}
    assert "decode" in phases
    s = cl.last_stats
    assert s.achieved_flops_per_s > 0 and s.bottleneck in VERDICTS
    assert sum(s.verdict_counts.values()) == s.decode_steps


def test_dense_engine_attribution(smoke_model):
    cfg, model, params = smoke_model
    eng = ServeEngine(model, params, max_batch=SLOTS, cache_len=CACHE_LEN,
                      kv_layout="dense", attribution=Attributor())
    res = eng.generate(_trace(cfg.vocab_size))
    assert all(r.tokens for r in res)
    s = eng.last_stats
    assert s.achieved_flops_per_s > 0 and s.bottleneck in VERDICTS
    assert s.prefill_bottleneck in ("memory", "compute")
