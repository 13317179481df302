"""Serving scheduler benchmark: lock-step groups vs continuous batching
(dense slot KV) vs continuous batching with the paged KV layout.

The serving analog of the paper's fixed-FPU-budget sweep (Ara2 §7.1:
eight 2-lane cores beat one 16-lane core at equal FPU count because eight
independent issue streams remove the single-dispatcher bottleneck).  Here
the FPU budget is the ``max_batch`` slot pool and the trace mixes short
and long requests (``max_new_tokens`` in {8, 64}): lock-step pins every
slot to its group's slowest member, continuous batching refills freed
slots immediately.

The paged run demonstrates the memory-side claim (Ara2's bottleneck
analysis: memory organization, not raw FPU count, gates utilization): its
block pool holds exactly the dense layout's KV footprint
(``max_batch * cache_len`` positions), yet it admits a trace whose
*summed* KV footprint exceeds that capacity, because finished requests
return their blocks immediately instead of holding a worst-case
``cache_len`` reservation.  The bench checks paged greedy tokens match
the dense run token-for-token and exits non-zero with a per-request
diff summary on divergence, so CI catches layout drift diagnosably.

The prefill-memory report makes the chunked-prefill claim a measured
number: the dense path hands a batch-1 ``(L, Hkv, prompt_len, hd)`` K/V
intermediate from prefill to the block scatter, the paged path's chunk
step only ever holds one ``block_size`` chunk — both sizes come from the
abstract shapes, and the compiled temp footprints from XLA's
``memory_analysis`` when the backend reports them.

A second trace runs a **scan family** (ssm: xlstm) through the same
lock-step-vs-continuous comparison: its recurrent state serves from the
slot-addressable layout (``repro.models.slot_state``), so freed slots
refill immediately instead of idling to the group barrier — the same
issue-stream argument, demonstrated on a cache with no KV strips at all.
Tokens must match byte-for-byte and continuous must win occupancy and
decode-step count (both deterministic; tok/s is reported, not asserted,
to keep CI timing-independent).

A third trace hammers one **shared prompt prefix** (the production
shape: system prompts / few-shot templates) through the paged engine
with the prefix cache off vs on: the cached run must emit byte-identical
tokens while admitting most prompt blocks by reference — hit rate is
asserted > 0; TTFT and pool peak are reported (cache on skips prefill
chunks and shares blocks, so both should drop, but wall-clock is not
asserted to keep CI timing-independent).

Emits ``name,us_per_call,derived`` CSV rows like the other benches:
  serving_lockstep,<wall_us>,tok/s=...;occ=...
  serving_continuous,<wall_us>,tok/s=...;occ=...
  serving_paged,<wall_us>,tok/s=...;occ=...;block_util=...;compiles=...
  serving_speedup,,continuous/lockstep=...
  serving_paged_admission,,footprint=...;capacity=...;admitted=...
  serving_prefill_mem,,dense_kv_intermediate=...;paged_chunk_kv=...;...
  serving_prefix_off,<wall_us>,ttft_ms=...;pool_peak=...;hits=0
  serving_prefix_on,<wall_us>,ttft_ms=...;pool_peak=...;hits=...
  serving_prefix_summary,,ttft=...;hit_rate=...;pool_peak=...
  serving_scan_ssm_lockstep,<wall_us>,tok/s=...;occ=...
  serving_scan_ssm_continuous,<wall_us>,tok/s=...;occ=...
  serving_scan_speedup,,continuous/lockstep=...
  serving_latency_{continuous,paged},,ttft_ms_p50=...;...;tpot_ms_p50=...
  serving_trace,<wall_us>,events=...;spans=...;lifecycle=ok;tokens=...
  serving_attr_decode,,fu_utilization=...;achieved_gflops_s=...;bottleneck=...
  serving_attr_prefill,,bottleneck=...;chunks=...;gflops_s=...
  serving_nulltracer_overhead,,ns_per_guarded_call=...;bound=...
  serving_attr_overhead,,ns_per_guarded_call=...;bound=...
  serving_phase_overhead,,us_per_step=...;bound=...

The trailing rows are the observability gates (docs/observability.md):
percentile latency rows come off the :class:`MetricsRegistry` every run
now feeds; the trace row re-runs the paged trace with a live
:class:`Tracer` *and* :class:`Attributor` attached and asserts tokens
stay byte-identical (tracing/attribution must never perturb scheduling
or sampling) and the event stream is lifecycle-well-formed; the
``serving_attr_*`` rows surface the roofline-joined utilization
accounting (achieved FLOP/s and bytes/s vs peak, ``fu_utilization``,
per-phase bottleneck verdicts) that ``tools/bench_compare.py`` gates
against ``benchmarks/baselines/``; and the overhead rows bound the
disabled-path cost of the default :class:`NullTracer` /
:class:`NullAttributor` and of the engine's profiler phase spans with no
profiler session (``python -c "from benchmarks.bench_serving import
phase_overhead_us; print(phase_overhead_us())"`` times that loop alone,
as on the chip's host).

``--smoke`` shrinks the trace/model work for the CI CPU regression gate;
``--json PATH`` additionally dumps every row for the CI artifact;
``--trace PATH`` exports the traced re-run as Chrome-trace JSON
(validated in CI by ``tools/check_trace.py``).
"""
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.common import check_tokens, emit, trace_bursty, write_json

MAX_BATCH = 4
CACHE_LEN = 128
BLOCK = 16
PROMPT_LEN = 8
SHORT_NEW, LONG_NEW = 8, 64
N_REQS = 16


def _trace(vocab, n_reqs, short_new, long_new):
    # the shared bursty generator at burst=1 is this bench's historic
    # interleaved long/short trace byte-for-byte (baselines unchanged)
    return trace_bursty(vocab, n=n_reqs, prompt_len=PROMPT_LEN,
                        short_new=short_new, long_new=long_new)


def _compiled_temp_bytes(fn, *args):
    """Temp-buffer bytes of the compiled fn, or None when the backend's
    memory analysis is unavailable (args may be ShapeDtypeStructs)."""
    try:
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        return None if ma is None else int(ma.temp_size_in_bytes)
    except Exception:
        return None


def _prefill_mem_report(model, params, cache_len, block_size, smoke):
    """Measure the prefill path's peak transient KV bytes, dense-then-
    scatter vs chunked paged, for a worst-case ``cache_len`` prompt.

    The dense-layout admission runs ``model.prefill`` and materializes a
    batch-1 (L, Hkv, prompt_len, hd) K/V cache; the paged chunk step
    (``model.prefill_paged``) writes block-sized pieces straight into the
    pool, so its largest KV-side value is one (Hkv, block_size, hd) chunk
    per layer scan step.  Both are read off the abstract output/jaxpr
    shapes; compiled temp totals are reported alongside when XLA's
    memory_analysis is available on this backend."""
    from repro.serving import blocks_needed
    batch = {"tokens": jnp.zeros((1, cache_len), jnp.int32)}
    cache = jax.eval_shape(
        lambda p, b: model.prefill(p, b, cache_len=None)[1], params, batch)
    itemsize = cache["k"].dtype.itemsize
    dense_kv = 2 * cache["k"].size * itemsize        # k + v
    l, _, hkv, s, hd = cache["k"].shape
    assert s == cache_len
    paged_chunk_kv = 2 * hkv * block_size * hd * itemsize

    max_blocks = blocks_needed(cache_len, block_size)
    n_blocks = MAX_BATCH * max_blocks + 1
    pcache = jax.eval_shape(lambda: model.paged_cache_init(
        batch=MAX_BATCH, n_blocks=n_blocks, block_size=block_size,
        max_blocks=max_blocks, dtype=cache["k"].dtype))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    chunk_batch = {"tokens": jax.ShapeDtypeStruct((1, block_size),
                                                  jnp.int32)}
    dense_tmp = _compiled_temp_bytes(
        lambda p, b: model.prefill(p, b, cache_len=None), params, batch)
    paged_tmp = _compiled_temp_bytes(
        model.prefill_paged, params, pcache, chunk_batch, i32, i32, i32)

    # the removed materialization, as numbers: the dense path's handed-off
    # KV intermediate stacks all L layers of the full prompt, the chunk
    # transient is one block of one layer (the scan carry updates the
    # pool slice in place)
    assert dense_kv == paged_chunk_kv * l * (cache_len // block_size)
    measured = ""
    if dense_tmp is not None and paged_tmp is not None:
        measured = f";dense_tmp={dense_tmp}B;paged_chunk_tmp={paged_tmp}B"
        if not smoke:
            # compiled-temp check: one chunk step's whole scratch
            # footprint must undercut the intermediate the old path
            # materialized.  Gated off the smoke shapes, where the KV
            # intermediate (8KB) is dwarfed by fixed per-call temps and
            # the margin would be one XLA padding change wide.
            assert paged_tmp < dense_tmp + dense_kv, (paged_tmp, dense_tmp,
                                                      dense_kv)
    emit("serving_prefill_mem", "",
         f"dense_kv_intermediate={dense_kv}B;paged_chunk_kv="
         f"{paged_chunk_kv}B;ratio={dense_kv / paged_chunk_kv:.1f}x"
         f"({l} layers x prompt {cache_len} / block {block_size})"
         f"{measured}")
    return dense_kv, paged_chunk_kv


def _scan_family_report(smoke: bool):
    """Continuous-vs-lockstep on a scan family (ssm: xlstm), slot state
    served from the slot-addressable recurrent layout.

    Uniform prompt lengths (so lockstep's left-padded group prefill is
    position-exact and tokens must match byte-for-byte) with mixed decode
    budgets: lockstep pins every slot to its group's slowest member,
    continuous refills freed slots.  Asserts the deterministic wins
    (occupancy and decode-step count) and token identity; tok/s is
    reported for the JSON artifact but not asserted (CI timing noise)."""
    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.serving import Request, ServeEngine

    cfg = smoke_config("xlstm-350m")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    n_reqs = 8 if smoke else N_REQS
    long_new = 16 if smoke else LONG_NEW
    reqs = _trace(cfg.vocab_size, n_reqs, SHORT_NEW, long_new)

    stats, tokens = {}, {}
    for name in ("lockstep", "continuous"):
        eng = ServeEngine(model, params, max_batch=MAX_BATCH,
                          cache_len=32 if smoke else CACHE_LEN, mode=name)
        eng.generate([Request(list(range(PROMPT_LEN)), 2, rid=-1)
                      for _ in range(MAX_BATCH)])   # warmup compile
        res = eng.generate(reqs)
        tokens[name] = [r.tokens for r in res]
        s = stats[name] = eng.last_stats
        emit(f"serving_scan_ssm_{name}", s.wall_s * 1e6,
             f"tok/s={s.tokens_per_s:.1f};occ={s.occupancy:.2f};"
             f"steps={s.decode_steps};ttft_ms={s.ttft_ms_mean:.1f}")

    check_tokens("bench_serving", "scan_ssm_lockstep", tokens["lockstep"],
                 "scan_ssm_continuous", tokens["continuous"],
                 [r.rid for r in reqs])
    cont, lock = stats["continuous"], stats["lockstep"]
    assert cont.occupancy > lock.occupancy, (cont.occupancy, lock.occupancy)
    assert cont.decode_steps < lock.decode_steps, (cont.decode_steps,
                                                   lock.decode_steps)
    speedup = cont.tokens_per_s / max(lock.tokens_per_s, 1e-9)
    emit("serving_scan_speedup", "",
         f"continuous/lockstep={speedup:.2f}x;occ={cont.occupancy:.2f}"
         f"vs{lock.occupancy:.2f};steps={cont.decode_steps}"
         f"vs{lock.decode_steps} (ssm family, slot-addressable "
         "recurrent state)")


def _prefix_cache_report(smoke: bool):
    """Shared-prefix trace through the paged engine, prefix cache off vs
    on.

    Every request opens with the same system-prompt-shaped prefix
    (whole ``BLOCK``-sized spans, so the chain keys resolve) followed by
    a short per-request tail.  With the cache on, the first admission
    registers the prefix blocks and every later one references them
    (refcount++, prefill fast-forwarded past the hit chunks), so tokens
    must stay byte-identical to the cold run while TTFT and the pool
    peak drop.  Hit rate and token identity are asserted; the timing
    deltas are reported only (CI timing noise)."""
    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.serving import Request, ServeEngine

    cfg = smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache_len = 32 if smoke else CACHE_LEN
    n_reqs = 6 if smoke else 12
    prefix_blocks = 1 if smoke else 3
    prefix = [(3 * j + 1) % cfg.vocab_size
              for j in range(prefix_blocks * BLOCK)]
    reqs = [Request(prefix + [(11 * i + j) % cfg.vocab_size
                              for j in range(4)],
                    SHORT_NEW, temperature=0.0, rid=i)
            for i in range(n_reqs)]

    stats, tokens = {}, {}
    for name, pc in (("off", False), ("on", True)):
        eng = ServeEngine(model, params, max_batch=2, cache_len=cache_len,
                          kv_layout="paged", block_size=BLOCK,
                          prefix_cache=pc)
        # warmup compile with sub-block prompts: registers nothing, so
        # the timed trace still sees one cold admission then pure hits
        eng.generate([Request(list(range(PROMPT_LEN)), 2, rid=-1)
                      for _ in range(2)])
        res = eng.generate(reqs)
        tokens[name] = [r.tokens for r in res]
        s = stats[name] = eng.last_stats
        emit(f"serving_prefix_{name}", s.wall_s * 1e6,
             f"ttft_ms={s.ttft_ms_mean:.2f};"
             f"pool_peak={s.block_util_peak:.2f};hits={s.prefix_hits};"
             f"reused={s.prefix_tokens_reused}")

    check_tokens("bench_serving", "prefix_off", tokens["off"],
                 "prefix_on", tokens["on"], [r.rid for r in reqs])
    on, off = stats["on"], stats["off"]
    assert on.prefix_hits > 0, \
        "prefix cache saw no hits on a shared-prefix trace"
    assert off.prefix_hits == 0, off.prefix_hits
    total_prompt = sum(len(r.prompt) for r in reqs)
    emit("serving_prefix_summary", "",
         f"ttft_on={on.ttft_ms_mean:.2f}ms_vs_off={off.ttft_ms_mean:.2f}"
         f"ms;hit_rate={on.prefix_tokens_reused / total_prompt:.2f};"
         f"pool_peak_on={on.block_util_peak:.2f}"
         f"vs{off.block_util_peak:.2f} "
         f"({n_reqs} reqs x {prefix_blocks * BLOCK}-token shared prefix)")


def _telemetry_report(model, params, vocab, n_reqs, long_new, cache_len,
                      n_blocks, base_tokens, trace_path):
    """Traced + attributed re-run of the paged trace: tracing and
    utilization attribution must not change tokens (the
    zero-observer-effect contract — this is the conformance gate the
    acceptance criteria name), the recorded event stream must be
    lifecycle-well-formed and carry the ``roofline`` achieved-vs-peak
    counter track, and the default :class:`NullTracer` /
    :class:`NullAttributor` guards must be cheap enough to leave step
    timing untouched (docs/observability.md).  ``--trace PATH``
    additionally exports the Chrome-trace JSON for Perfetto /
    tools/check_trace.py."""
    from repro.serving import (NULL_ATTR, NULL_TRACER, Attributor, Request,
                               ServeEngine, Tracer, validate_lifecycle)

    eng = ServeEngine(model, params, max_batch=MAX_BATCH,
                      cache_len=cache_len, mode="continuous",
                      kv_layout="paged", block_size=BLOCK,
                      n_blocks=n_blocks)
    eng.generate([Request(list(range(PROMPT_LEN)), 2, rid=-1)
                  for _ in range(MAX_BATCH)])   # warmup compile
    tracer = Tracer()
    eng.set_tracer(tracer)
    eng.set_attributor(Attributor())
    reqs = _trace(vocab, n_reqs, SHORT_NEW, long_new)
    res = eng.generate(reqs)
    eng.set_tracer(NULL_TRACER)
    eng.set_attributor(NULL_ATTR)
    # observer-effect gate: the traced+attributed run's bytes must match
    # the untraced paged run of the same trace exactly
    check_tokens("bench_serving", "paged", base_tokens, "paged_traced",
                 [r.tokens for r in res], [r.rid for r in reqs])
    events = tracer.events()
    validate_lifecycle(events)
    spans = sum(1 for e in events if e.ph == "X")
    s = eng.last_stats
    emit("serving_trace", s.wall_s * 1e6,
         f"events={len(events)};spans={spans};lifecycle=ok;"
         f"tokens=identical({n_reqs})")
    assert any(e.name == "roofline" for e in events), \
        "attributed traced run emitted no roofline counter track"

    # attribution rows (the serving_attr_* gates): achieved FLOP/s and
    # bytes/s vs the machine roofline, the engine fu_utilization figure,
    # and the per-phase bottleneck verdicts.  On the CI CPU the absolute
    # utilization is tiny and the expected regime is the paper's §6
    # short-vector story (decode issue- or memory-bound, never
    # compute-bound at smoke shapes) — the row just has to be present,
    # self-consistent, and inside the baseline's tolerance band.
    assert s.achieved_flops_per_s > 0 and s.bottleneck, s
    assert 0.0 < s.fu_utilization < 1.0, s.fu_utilization
    m = eng.last_metrics
    verdicts = ";".join(f"{k}={v}" for k, v in s.verdict_counts.items())
    emit("serving_attr_decode", "",
         f"fu_utilization={s.fu_utilization:.3e};"
         f"achieved_gflops_s={s.achieved_flops_per_s / 1e9:.3f};"
         f"achieved_gbytes_s={s.achieved_bytes_per_s / 1e9:.3f};"
         f"ai={s.decode_ai:.2f};ridge={s.ridge_ai:.2f};"
         f"bottleneck={s.bottleneck};{verdicts}")
    pf_ms = sum(m.histogram("attr_prefill_ms").samples)
    pf_fl = sum(m.histogram("attr_prefill_flops").samples)
    n_chunks = m.histogram("attr_prefill_ms").count
    emit("serving_attr_prefill", "",
         f"bottleneck={s.prefill_bottleneck};chunks={n_chunks};"
         f"gflops_s={pf_fl / max(pf_ms, 1e-9) / 1e6:.3f};"
         f"chunk_ms_mean={pf_ms / max(n_chunks, 1):.2f}")
    if trace_path:
        n = tracer.export(trace_path)
        print(f"[bench] wrote {trace_path} ({n} trace events)",
              file=sys.stderr)

    # NullTracer overhead: the hot-path guard (``if tracer.enabled:``) on
    # the default tracer, per call.  A decode step takes O(10) of these;
    # the bound is deliberately loose (CI CPU noise) — the point is
    # catching an accidentally-instantiated recording tracer or an
    # attribute-heavy guard, either of which blows it by orders of
    # magnitude.
    tr = NULL_TRACER
    n_calls = 200_000
    t0 = time.perf_counter()
    for _ in range(n_calls):
        if tr.enabled:
            tr.instant("t", "x")
    ns = (time.perf_counter() - t0) / n_calls * 1e9
    bound = 2000.0
    assert ns < bound, f"NullTracer guard costs {ns:.0f}ns/call"
    emit("serving_nulltracer_overhead", "",
         f"ns_per_guarded_call={ns:.1f};bound={bound:.0f}ns;"
         f"calls={n_calls}")

    # NullAttributor overhead: the same contract for the attribution
    # guard (one ``if attr.enabled:`` per decode launch + one per prefill
    # chunk) — attribution off must cost one attribute check, nothing
    # else.
    at = NULL_ATTR
    t0 = time.perf_counter()
    for _ in range(n_calls):
        if at.enabled:
            at.record_step(None, None, "t", t0=0, t_disp=0, t1=0,
                           active=0, width=1, cost=None)
    ns = (time.perf_counter() - t0) / n_calls * 1e9
    assert ns < bound, f"NullAttributor guard costs {ns:.0f}ns/call"
    emit("serving_attr_overhead", "",
         f"ns_per_guarded_call={ns:.1f};bound={bound:.0f}ns;"
         f"calls={n_calls}")

    # the phase spans open every decode step whether or not a profiler
    # records them, so their off-path cost is paid by every user
    us = phase_overhead_us()
    bound_us = 20.0
    assert us < bound_us, f"phase spans cost {us:.1f}us/step"
    emit("serving_phase_overhead", "",
         f"us_per_step={us:.2f};bound={bound_us:.0f}us")


def phase_overhead_us(n_steps: int = 100_000) -> float:
    """Microseconds a decode step pays for its five phase spans
    (``telemetry.phase``) when no profiler session is active and no
    tracer is attached."""
    from repro.serving.telemetry import phase

    t0 = time.perf_counter()
    for k in range(n_steps):
        with phase("engine.step", step_num=k):
            with phase("engine.decode_dispatch"):
                pass
            with phase("engine.sample_dispatch"):
                pass
            with phase("engine.result_wait"):
                pass
            with phase("engine.emit"):
                pass
    return (time.perf_counter() - t0) / n_steps * 1e6


def run(smoke: bool = False, json_path: str | None = None,
        trace_path: str | None = None):
    from benchmarks.common import reset_rows
    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.serving import Request, ServeEngine

    reset_rows()

    cache_len = 32 if smoke else CACHE_LEN
    n_reqs = 8 if smoke else N_REQS
    long_new = 16 if smoke else LONG_NEW
    cfg = smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    reqs = _trace(cfg.vocab_size, n_reqs, SHORT_NEW, long_new)

    # paged pool sized to the dense layout's exact KV footprint: admission
    # beyond it can only come from block recycling, not extra memory
    pool_positions = MAX_BATCH * cache_len
    engines = {
        "lockstep": dict(mode="lockstep"),
        "continuous": dict(mode="continuous"),
        "paged": dict(mode="continuous", kv_layout="paged",
                      block_size=BLOCK,
                      n_blocks=pool_positions // BLOCK + 1),
    }
    stats, tokens = {}, {}
    for name, kw in engines.items():
        eng = ServeEngine(model, params, max_batch=MAX_BATCH,
                          cache_len=cache_len, **kw)
        # warmup: compile prefill/decode/sample outside the timed run
        eng.generate([Request(list(range(PROMPT_LEN)), 2, rid=-1)
                      for _ in range(MAX_BATCH)])
        res = eng.generate(reqs)
        tokens[name] = [r.tokens for r in res]
        s = eng.last_stats
        stats[name] = s
        extra = ""
        if name == "paged":
            extra = (f";block_util={s.block_util_peak:.2f}"
                     f";compiles={s.prefill_compiles}")
        emit(f"serving_{name}", s.wall_s * 1e6,
             f"tok/s={s.tokens_per_s:.1f};occ={s.occupancy:.2f};"
             f"steps={s.decode_steps};ttft_ms={s.ttft_ms_mean:.1f};"
             f"preempted={s.preempted};requeued={s.requeued}" + extra)

    # exit non-zero with a per-request diff summary on divergence (a bare
    # assert left CI logs undiagnosable)
    check_tokens("bench_serving", "continuous", tokens["continuous"],
                 "paged", tokens["paged"], [r.rid for r in reqs])

    # percentile latency rows straight off the metrics registry each run
    # feeds (EngineStats.from_registry); CI gates on their presence
    for name in ("continuous", "paged"):
        s = stats[name]
        emit(f"serving_latency_{name}", "",
             f"ttft_ms_p50={s.ttft_ms_p50:.1f};p90={s.ttft_ms_p90:.1f};"
             f"p99={s.ttft_ms_p99:.1f};tpot_ms_p50={s.tpot_ms_p50:.2f};"
             f"p99={s.tpot_ms_p99:.2f};n={n_reqs}")

    speedup = (stats["continuous"].tokens_per_s
               / max(stats["lockstep"].tokens_per_s, 1e-9))
    emit("serving_speedup", "",
         f"continuous/lockstep={speedup:.2f}x "
         f"(trace: {n_reqs} reqs, max_new {SHORT_NEW}/{long_new}, "
         f"{MAX_BATCH} slots)")

    # admission headline: summed trace KV footprint vs the pool capacity
    # (== dense max_batch * cache_len) that nonetheless served it
    footprint = sum(len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
    served = all(len(t) == r.max_new_tokens
                 for t, r in zip(tokens["paged"], reqs))
    assert footprint > pool_positions, \
        "trace too small to demonstrate block recycling"
    assert served, "paged engine failed to serve the full trace"
    emit("serving_paged_admission", "",
         f"footprint={footprint}pos;capacity={pool_positions}pos;"
         f"admitted=all({n_reqs});block_util_peak="
         f"{stats['paged'].block_util_peak:.2f}")

    # prefill transient memory: the dense (L, Hkv, prompt, hd) KV
    # intermediate vs the chunked path's single-block transient
    _prefill_mem_report(model, params, cache_len, BLOCK, smoke)

    # shared-prefix trace: refcounted prefix cache off vs on, tokens
    # byte-identical, hit rate asserted
    _prefix_cache_report(smoke)

    # scan family (slot-addressable recurrent state): same scheduler
    # comparison, no KV strips involved
    _scan_family_report(smoke)

    # telemetry gates: traced re-run (byte-identical tokens + well-formed
    # lifecycle) and the NullTracer disabled-path overhead bound
    _telemetry_report(model, params, cfg.vocab_size, n_reqs, long_new,
                      cache_len, pool_positions // BLOCK + 1,
                      tokens["paged"], trace_path)
    if json_path:
        write_json(json_path, bench="bench_serving", smoke=smoke)
    return speedup


if __name__ == "__main__":
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from benchmarks.common import json_path_arg, path_arg
    print("name,us_per_call,derived")
    run(smoke="--smoke" in sys.argv, json_path=json_path_arg(sys.argv),
        trace_path=path_arg(sys.argv, "--trace"))
