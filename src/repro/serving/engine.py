"""Slot-based continuous-batching serving engine.

The paper's multi-core result (Ara2 §7.1: eight 2-lane cores beat one
16-lane core by >3x at equal FPU count, because eight independent issue
streams remove the single-dispatcher bottleneck) maps onto serving as:
many independently scheduled decode *slots* beat one lock-step batch whose
cadence is set by its slowest member.

Two scheduling modes:

* ``continuous`` (the default for every family) - a fixed pool of
  ``max_batch`` decode slots with per-slot cache state and per-slot
  positions.  An admission scheduler prefills a queued request into a
  freed slot *immediately* (prefill-on-admit via
  ``model.cache_slot_write``); the other slots keep decoding on the next
  step.  A short request never holds its neighbors hostage.  The slot
  state is per-slot KV strips for the transformer families and per-slot
  *recurrent* state for the scan families (ssm/hybrid/encdec: conv tails,
  SSD/LSTM cell states, sliding-window ring KV, cross-attention strips —
  see ``repro.models.slot_state``); a freed or preempted scan slot is
  zeroed via ``model.cache_slot_reset`` so no recurrent state survives
  its request.

* ``lockstep`` - the legacy group scheduler, kept behind the ``mode``
  flag as a baseline (and as the uniform-length reference the
  conformance property tests continuous mode against): requests run in
  groups of ``max_batch``; a finished sequence's slot idles until the
  whole group drains, and slot refill re-runs a batched prefill over the
  next waiting group.

Continuous mode supports two KV layouts (``kv_layout``):

* ``dense`` (default) - every slot reserves a full ``(Hkv, cache_len, D)``
  KV strip per layer, so admission enforces ``prefill + decode writes <=
  cache_len`` per request and memory is bounded by worst-case reservation
  (``max_batch * cache_len`` positions live at all times).

* ``paged`` - KV lives in one global pool of fixed-size blocks
  (``repro.serving.kvcache.BlockAllocator``) addressed through per-slot
  block tables; *both* phases run the paged-attention kernels
  (``repro.kernels.paged_attention``): decode single-token gather, and a
  **chunked prefill** that admits a prompt in ``block_size`` chunks, each
  chunk's K/V written straight into a just-allocated pool block and its
  queries attending over the blocks written so far — the dense batch-1
  ``(L, Hkv, prompt_len, hd)`` prefill cache of the old
  prefill-then-scatter path never exists, and one compiled chunk shape
  serves every prompt length.  Admission is bounded by *free blocks*, not
  a per-slot length: blocks are allocated lazily as a request's position
  grows (prefill chunks and decode writes alike), and a finished request
  returns its blocks immediately - so a trace whose summed KV footprint
  exceeds ``max_batch * cache_len`` still serves as long as the
  *concurrently live* footprint fits the pool.  ``cache_len`` remains
  only the per-request context bound (the block table's width).  The
  allocator may be *external and shared* between engines (``allocator=``):
  a multi-replica cluster (``repro.serving.cluster.ClusterEngine``)
  passes one pool to every replica, tagging allocations with ``owner=``.

Paged admission policies (``admission=``):

* ``reserve`` (default) - admit only when the pool covers the request's
  worst case beyond standing reservations; lazy growth can never fail.
* ``overcommit`` - admit when the *first prefill chunk's* block is free;
  lazy growth (a later prefill chunk or a decode write) may then find
  the pool empty, which raises
  :class:`repro.serving.kvcache.PoolPressure` out of ``session_step`` so
  a cluster scheduler can preempt a victim (``session_preempt``: blocks
  freed, request re-queued carrying its generated prefix in
  ``Request.done`` for re-prefill) and retry — a long prompt can be
  preempted *mid-prefill* (its chunks already computed are simply redone
  on re-admission) and a retried step resumes a surviving
  half-prefilled slot at its next chunk.  Overcommit is a cluster
  driver mode - plain ``generate`` on an overcommitted engine propagates
  the pressure error instead of preempting.

The continuous scheduler is exposed as a *stepwise session API*
(``begin_session`` / ``session_admit`` / ``session_step`` /
``session_preempt`` / ``end_session``) so an outer scheduler can
interleave several engines over one pool; ``generate`` drives the same
API for the single-engine case.

Prompt-length bucketing (``bucket=``): dense-layout prompts are prefilled
at their exact length by default - one compile per distinct length.  With
``bucket="pow2"`` (or an integer multiple), continuous-mode prefills are
right-padded up to the bucket boundary and the true length rides in
``batch["prefill_len"]``; causal masking hides the pads, so outputs are
identical while compiles drop to one per bucket
(``EngineStats.prefill_compiles`` counts distinct compiled prefill
shapes).  The paged layout ignores ``bucket``: its chunked prefill
compiles exactly one ``(1, block_size)`` chunk shape for all prompts.
Bucketing requires a prefill that understands ``prefill_len``
(``model.supports_prefill_len``) — a scan-family prefill folds every
position into recurrent state, so right-padding would corrupt it, and
``bucket=`` is rejected there.

Prefix caching (``prefix_cache=True``, paged layout only): full
``block_size`` spans of a finished prefill's prompt are *registered* in
the allocator's prefix index under exact chain keys (nested tuples over
the span's token ids, chained on the parent key — token-exact, no hash
collisions).  A later admission whose prompt starts with the same spans
*references* the resident blocks instead of recomputing them: refcount
incremented, chunked prefill fast-forwarded to the first cold block,
``EngineStats.prefix_hits``/``prefix_tokens_reused`` counting the skip.
A request whose whole prefill is covered re-runs only its final chunk
(the engine needs that chunk's logits to sample the first token), and
because that chunk's pool block is shared, the write barrier in
``_advance_prefill`` gives the slot a private **copy-on-write** block
first (allocate, byte-copy, re-table, drop the shared reference).
Blocks written by decode are never registered — only chunk-prefill
output is, so a cache hit serves bytes that are bit-identical to what
the cold path would recompute.  Registered blocks whose last reference
drops park in the allocator's cached LRU (revivable by a later hit,
evicted LRU-first when the pool needs them), and the engine keeps its
device-side pool alive across sessions so cached bytes stay resident;
``session_abort`` flushes this engine's index entries instead (an
aborted session's pool state is not trustworthy).

Per-request sampling is vectorized and **request-keyed**: row ``i``'s
``t``-th token is sampled with ``fold_in(fold_in(key, rid_i), t)``, so a
request's sampled stream depends only on its ``rid`` and the base key -
never on which slot, step, replica, or scheduler served it (and a
preempted request resumes its stream exactly where it stopped).
Temperature<=0 rows take argmax (deterministic regardless of the key);
temperature>0 rows sample at their own temperature - never at the batch
max.
"""
from __future__ import annotations

import collections
import dataclasses
import queue as queue_mod
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..models.model import Model
from . import kvcache
from .attribution import NULL_ATTR, VERDICTS, dominant_verdict
from .kvcache import BlockAllocator, PoolPressure, blocks_needed
from .slo import make_policy
from .telemetry import (MONOTONIC, NULL_TRACER, MetricsRegistry,
                        install_gc_spans, phase)


@dataclasses.dataclass
class Request:
    """One generation request.

    The scheduler may admit, move, preempt, and re-admit a request
    freely: everything observable about its output is a pure function of
    (``prompt``, ``max_new_tokens``, ``temperature``, ``rid``, base PRNG
    key) — the conformance property in ``tests/test_serving_props.py``
    holds the token stream byte-identical across every scheduler, cache
    layout, and topology.  The remaining fields are scheduler
    bookkeeping that preemption threads through a requeue."""
    prompt: list[int]
    max_new_tokens: int = 32       # total budget, including ``done``
    temperature: float = 0.0
    rid: int = 0
    priority: int = 0              # preemption picks the lowest first
    # tokens already generated before this (re)admission: set by
    # session_preempt when a request is re-queued; prefill covers
    # prompt + done and sampling resumes at stream index len(done)
    done: tuple = ()
    # time-to-first-token of the *first* admission, carried across
    # preemptions so Result.prefill_ms stays the request's real TTFT
    first_ttft_ms: float | None = None
    # perf_counter of the *first* admission, carried across preemptions
    # that fired before any token was sampled (mid-prefill eviction):
    # the eventual first token's TTFT must span the aborted attempt and
    # the hysteresis wait, not restart at re-admission
    first_admit_t: float | None = None
    # times this request has been preempted (a victim evicted mid-prefill
    # carries no ``done`` prefix, so ``done`` alone cannot mark a requeue)
    requeues: int = 0
    # SLO budgets (None = best-effort, the default): ``slo_ttft_ms`` is
    # the enqueue -> first-token target, ``slo_tpot_ms`` the decode
    # ms-per-output-token target.  Budgets never change the token
    # stream — they drive the scheduling policies in ``serving.slo``
    # (admission order, victim protection, starvation pressure) and the
    # ``slo_*`` attainment metrics.
    slo_ttft_ms: float | None = None
    slo_tpot_ms: float | None = None


@dataclasses.dataclass
class Result:
    """One request's output: the full generated stream (a preempted
    request's ``done`` prefix included — resume is invisible) plus its
    latency split."""
    rid: int
    tokens: list[int]
    prefill_ms: float = 0.0        # time-to-first-token for this request
    decode_ms_per_tok: float = 0.0


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One streamed token, emitted the moment it is sampled.

    ``index`` is the token's position in the request's *full* output
    stream (``Result.tokens``), so a client concatenating events in
    per-rid index order reconstructs the byte-identical stream
    ``generate`` would have returned.  Preemption is invisible here too:
    a preempted request's already-emitted tokens ride along in
    ``Request.done`` and are never re-emitted — emission resumes at
    ``len(done)`` after re-admission.  ``final`` marks the request's
    last token.

    Emitted via the ``on_token`` callback (``begin_session`` /
    ``generate``) or consumed through the pull-based ``stream``
    generator.  In the threaded cluster driver the callback fires on
    replica worker threads, so it must be thread-safe."""
    rid: int
    token: int
    index: int
    final: bool


def _stream_events(run):
    """Drive ``run(on_token_callback)`` on a background thread, yielding
    the :class:`TokenEvent` rows it emits in order.  Shared by
    ``ServeEngine.stream`` and ``ClusterEngine.stream``: the callback
    just enqueues events (thread-safe — cluster workers may emit
    concurrently), the consumer thread pulls them as they land.  An
    exception from the run re-raises out of the generator after the
    driver thread is joined."""
    q: queue_mod.SimpleQueue = queue_mod.SimpleQueue()

    def driver():
        try:
            run(q.put)
            q.put(("done", None))
        except BaseException as e:      # re-raised in the consumer
            q.put(("error", e))

    t = threading.Thread(target=driver, name="stream-driver", daemon=True)
    t.start()
    while True:
        item = q.get()
        if isinstance(item, TokenEvent):
            yield item
            continue
        kind, payload = item
        t.join()
        if kind == "error":
            raise payload
        return


@dataclasses.dataclass
class EngineStats:
    """Aggregate metrics for the last ``generate`` call (or session).

    ``occupancy`` is the utilization headline this repo exists to
    measure: the fixed-shape decode launch always computes ``max_batch``
    slot lanes, so occupancy is the fraction of launched lanes that held
    a live request — the serving twin of the paper's vector-lane
    utilization under short workloads.

    Built as a *view over a* :class:`~repro.serving.telemetry.MetricsRegistry`
    (``from_registry``): the registry holds the raw counters and
    histogram samples, this dataclass snapshots the derived numbers.
    The mean fields predate the registry and are kept for compatibility;
    the ``*_p50/p90/p99`` fields are exact nearest-rank percentiles over
    the raw samples, so cluster stats can merge replica histograms
    instead of averaging replica means."""
    mode: str                      # resolved scheduler ("cluster" at top)
    wall_s: float
    generated_tokens: int
    tokens_per_s: float
    decode_steps: int              # decode launches (cluster: summed)
    occupancy: float               # busy slot-steps / (max_batch * steps)
    ttft_ms_mean: float            # mean time-to-first-token
    kv_layout: str = "dense"
    prefill_compiles: int = 0      # distinct prefill shapes compiled so far
    block_util_peak: float = 0.0   # paged: peak live blocks / pool capacity
    preempted: int = 0             # requests evicted under pool pressure
    requeued: int = 0              # re-admissions of preempted requests
    router_policy: str = ""        # cluster-level: routing policy used
    prefix_hits: int = 0           # prompt blocks admitted by reference
    prefix_tokens_reused: int = 0  # prefill positions skipped via hits
    ttft_ms_p50: float = 0.0       # time-to-first-token percentiles
    ttft_ms_p90: float = 0.0
    ttft_ms_p99: float = 0.0
    tpot_ms_mean: float = 0.0      # time-per-output-token (per request)
    tpot_ms_p50: float = 0.0
    tpot_ms_p90: float = 0.0
    tpot_ms_p99: float = 0.0
    queue_age_ms_mean: float = 0.0  # enqueue -> admission wait
    queue_age_ms_p99: float = 0.0
    # -- SLO attainment (repro.serving.slo) --
    # Only requests carrying a budget are scored; with no budgets in the
    # trace the totals stay 0 and ``slo_attainment`` reads 1.0.
    sched_policy: str = ""         # admission/victim policy in effect
    slo_ttft_total: int = 0        # first tokens scored against a budget
    slo_ttft_attained: int = 0     # ... that landed inside it
    slo_tpot_total: int = 0        # finished requests with a TPOT budget
    slo_tpot_attained: int = 0
    slo_attainment: float = 1.0    # attained / total over both phases
    slo_starve_preempts: int = 0   # cluster: starvation-pressure evictions
    # -- utilization attribution (repro.serving.attribution) --
    # All-zero/empty unless an Attributor was attached.  fu_utilization
    # is the paper-§6 analog: useful flops (idle slot lanes excluded,
    # like idle vector lanes) per second of device-busy time, over the
    # machine's peak — the serving twin of Ara2's FU-utilization figure.
    fu_utilization: float = 0.0
    achieved_flops_per_s: float = 0.0  # useful FLOP/s over busy device time
    achieved_bytes_per_s: float = 0.0  # HBM bytes/s over busy device time
    decode_ai: float = 0.0         # decode executable flops/byte
    ridge_ai: float = 0.0          # machine ridge point (flops/byte)
    bottleneck: str = ""           # dominant decode verdict (issue/
    #                                memory/compute/idle)
    prefill_bottleneck: str = ""   # dominant prefill verdict
    verdict_counts: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def from_registry(cls, m: MetricsRegistry, *, mode: str, wall_s: float,
                      kv_layout: str = "dense", prefill_compiles: int = 0,
                      block_util_peak: float = 0.0,
                      router_policy: str = "",
                      sched_policy: str = "") -> "EngineStats":
        """Derive the stats view from a registry (one engine session's,
        or several replicas' registries merged)."""
        ttft = m.histogram("ttft_ms")
        tpot = m.histogram("tpot_ms")
        qage = m.histogram("queue_age_ms")
        gen = m.counter("generated_tokens").n
        steps = m.counter("decode_steps").n
        busy = m.counter("busy_slot_steps").n
        offered = m.counter("offered_slot_steps").n
        # attribution rollup: raw per-launch samples (replica registries
        # merge losslessly, so a cluster's figure is derived from the
        # union exactly as a single engine's is)
        dev_s = sum(m.histogram("attr_device_ms").samples) / 1e3
        pf_s = sum(m.histogram("attr_prefill_ms").samples) / 1e3
        useful = (sum(m.histogram("attr_step_flops").samples)
                  + sum(m.histogram("attr_prefill_flops").samples))
        moved = (sum(m.histogram("attr_step_bytes").samples)
                 + sum(m.histogram("attr_prefill_bytes").samples))
        busy_s = dev_s + pf_s
        peak = m.gauge("attr_peak_flops").value
        mem_bw = m.gauge("attr_peak_bytes_s").value
        verdicts = {v: m.counter(f"attr_verdict_{v}").n for v in VERDICTS}
        verdicts = {k: n for k, n in verdicts.items() if n}
        pf_verdicts = {v: m.counter(f"attr_prefill_verdict_{v}").n
                       for v in VERDICTS}
        ach_f = useful / busy_s if busy_s > 0 else 0.0
        ach_b = moved / busy_s if busy_s > 0 else 0.0
        slo_tt = m.counter("slo_ttft_total").n
        slo_ta = m.counter("slo_ttft_attained").n
        slo_pt = m.counter("slo_tpot_total").n
        slo_pa = m.counter("slo_tpot_attained").n
        return cls(
            mode, wall_s, gen, gen / max(wall_s, 1e-9), steps,
            busy / max(offered, 1), ttft.mean,
            kv_layout=kv_layout, prefill_compiles=prefill_compiles,
            block_util_peak=block_util_peak,
            preempted=m.counter("preempted").n,
            requeued=m.counter("requeued").n,
            router_policy=router_policy,
            prefix_hits=m.counter("prefix_hits").n,
            prefix_tokens_reused=m.counter("prefix_tokens_reused").n,
            ttft_ms_p50=ttft.percentile(50),
            ttft_ms_p90=ttft.percentile(90),
            ttft_ms_p99=ttft.percentile(99),
            tpot_ms_mean=tpot.mean,
            tpot_ms_p50=tpot.percentile(50),
            tpot_ms_p90=tpot.percentile(90),
            tpot_ms_p99=tpot.percentile(99),
            queue_age_ms_mean=qage.mean,
            queue_age_ms_p99=qage.percentile(99),
            sched_policy=sched_policy,
            slo_ttft_total=slo_tt, slo_ttft_attained=slo_ta,
            slo_tpot_total=slo_pt, slo_tpot_attained=slo_pa,
            slo_attainment=((slo_ta + slo_pa) / (slo_tt + slo_pt)
                            if slo_tt + slo_pt else 1.0),
            slo_starve_preempts=m.counter("slo_starve_preempts").n,
            fu_utilization=ach_f / peak if peak > 0 else 0.0,
            achieved_flops_per_s=ach_f,
            achieved_bytes_per_s=ach_b,
            decode_ai=m.gauge("attr_decode_ai").value,
            ridge_ai=(peak / mem_bw if mem_bw > 0 else 0.0),
            bottleneck=dominant_verdict(verdicts),
            prefill_bottleneck=dominant_verdict(pf_verdicts),
            verdict_counts=verdicts)


@dataclasses.dataclass
class _Slot:
    req: Request
    tag: int                       # caller's result index (``tag`` arg)
    tokens: list[int]              # tokens generated *this* admission
    ttft_ms: float
    admit_seq: int = 0             # global admission order (victim pick)
    decode_s: float = 0.0
    steps: int = 0
    # paged layout bookkeeping
    prefill_pos: int = 0           # cache positions the prefill will write
    blocks: list[int] = dataclasses.field(default_factory=list)
    reserve_left: int = 0          # worst-case blocks not yet allocated
    # chunked-prefill progress: chunks completed so far, or None once the
    # prefill has finished and the first token is sampled (dense slots are
    # always None — their prefill runs at admit)
    chunks_done: int | None = None
    # prefix cache: blocks[:shared_until] are referenced from the prefix
    # index (refcounted, read-only for this slot until copy-on-write)
    shared_until: int = 0
    extra_row: int = 0             # extra_inputs row (vlm patches)
    admit_t: float = 0.0           # clock time of the *first* admission
    #                                (TTFT base, carried across preempts)
    enqueue_t: float | None = None  # clock time the request entered the
    #                                caller's queue (SLO deadline base)
    span_t0: float = 0.0           # clock time of *this* admission (the
    #                                request span's start in the trace)
    first_tok_t: float = 0.0       # clock time of this admission's first
    #                                sampled token (decode-stretch start)


@dataclasses.dataclass
class _Session:
    """Mutable state of one stepwise continuous-batching run.

    All scalar accounting (decode/busy steps, generated tokens, preempt
    and prefix counters) and every latency sample (TTFT, TPOT, queue
    age) live in ``metrics`` — ``end_session`` derives
    :class:`EngineStats` from it, and the cluster merges replica
    registries for exact cross-replica percentiles."""
    key: Any                       # base PRNG key (rid/step-keyed streams)
    slots: list
    toks: np.ndarray               # (B, 1) next-token feed
    temps: np.ndarray              # (B,) per-slot temperature
    rids: np.ndarray               # (B,) per-slot request id
    tok_idx: np.ndarray            # (B,) next sample's stream index
    metrics: MetricsRegistry
    t_start: float
    cache: Any = None
    admit_counter: int = 0
    # Results finished during session_step's prefill phase, parked here so
    # they survive a PoolPressure raised later in the same step (the slot
    # is already released — a lost local would drop the Result for good);
    # the next successful session_step returns them
    finished_pending: list = dataclasses.field(default_factory=list)
    # streaming: called with a TokenEvent for every token the moment it
    # is sampled (None = no streaming).  Runs on whatever thread drives
    # the session, so cluster-level callbacks must be thread-safe.
    on_token: Any = None


def _sample_rows(logits, temps, key, rids, tok_idx):
    """Per-row temperature sampling over (B, V) logits.

    Row ``i`` uses the key ``fold_in(fold_in(key, rids[i]), tok_idx[i])``,
    so a request's sampled stream is a pure function of (base key, rid,
    token index) - independent of slot, step order, and batch composition.
    Rows with temperature <= 0 take argmax (greedy, key-independent);
    rows with temperature > 0 sample a categorical at their own
    temperature."""
    keys = jax.vmap(
        lambda r, t: jax.random.fold_in(jax.random.fold_in(key, r), t)
    )(rids, tok_idx)
    greedy = jnp.argmax(logits, axis=-1)
    safe = jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, logits / safe)
    return jnp.where(temps > 0.0, sampled, greedy)


class ServeEngine:
    """Batched generation over the uniform Model API.

    Invariants the property suite (``tests/test_serving_props.py``)
    asserts over this class:

    * **scheduler-invisible tokens** — for one trace and base key, every
      mode/layout/topology combination emits byte-identical token
      streams (greedy rows are argmax; sampled rows are request-keyed,
      see ``_sample_rows``).
    * **block conservation** (paged) — after ``generate`` returns or
      raises, every block and reservation is back in the pool.
    * **preemption-invisible resume** — a preempted request re-admitted
      with its ``done`` prefix reproduces the uninterrupted stream.
    * **no state leak** — a freed/preempted slot's cache state cannot
      reach a later occupant: scan-family slots are zeroed on release
      (``model.cache_slot_reset``), KV-family slots are masked by their
      per-slot ``pos`` and fully rewritten at the next admission.

    mode: "auto" (resolves to continuous - every family is
    slot-addressable), "continuous", or "lockstep" (the group-barrier
    baseline).

    ``extra_inputs`` (vlm patches / encdec frames): leaves carry one row
    per request, indexed by submission order; a leaf with leading dim 1
    broadcasts to every request.  Too few rows is an error, not a clamp.

    kv_layout: "dense" or "paged" (continuous mode only; see module doc).
    The scan families (ssm/hybrid/encdec) serve on the dense slot layout;
    requesting "paged" for them raises (recurrent state is O(1) per slot
    already - there is nothing to page).
    block_size / n_blocks size the paged pool - n_blocks defaults to the
    dense layout's footprint (max_batch * cache_len positions) plus the
    null block.  ``allocator=`` injects an external (shared) pool instead;
    ``owner=`` tags this engine's allocations in it; ``admission=``
    selects "reserve" (default) or "overcommit" (cluster preemption mode).
    bucket: None (exact-length prefills), "pow2", or an integer
    pad-to-multiple; rejected when the family's prefill cannot mask pads
    (``model.supports_prefill_len``).
    policy: scheduling policy name from ``serving.slo.POLICIES`` (or a
    ``SchedPolicy`` instance) — drives admission order inside
    ``generate`` and the ``session_victims`` ranking; "fifo" (default)
    is byte-for-byte the pre-policy scheduler, and every policy is
    token-identical to it (request-keyed sampling; budgets only move
    *when* a request runs).
    prefix_cache: paged layout only — admit shared prompt prefixes by
    referencing resident pool blocks (see the module doc); rejected for
    families whose prefill carries a non-token prefix (vlm patches:
    patch content is not addressable by token ids).
    tracer / clock / track: telemetry (``repro.serving.telemetry``;
    ``docs/observability.md``).  ``tracer`` defaults to the no-op
    ``NULL_TRACER``; a real ``Tracer`` records request-lifecycle spans,
    pool events, and the engine's phase spans (``engine.*``, which the
    profiler records too; ``telemetry.phase``), host-side only (no
    compiled function depends on it — ``set_tracer`` may attach one to
    a warm engine).  ``clock`` injects the timebase every latency
    number is computed from (defaults to the tracer's clock when a
    tracer is given, else the process monotonic clock).  ``track``
    names this engine's trace track (default ``engine{owner}``; the
    cluster passes ``replica{i}``).
    """

    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 cache_len: int = 1024, extra_inputs: dict | None = None,
                 mode: str = "auto", kv_layout: str = "dense",
                 block_size: int | None = None,
                 n_blocks: int | None = None,
                 bucket: str | int | None = None,
                 allocator: BlockAllocator | None = None,
                 admission: str = "reserve", owner: Any = 0,
                 prefix_cache: bool = False, policy="fifo",
                 tracer=None, clock=None, track: str | None = None,
                 attribution=None):
        assert mode in ("auto", "continuous", "lockstep"), mode
        assert kv_layout in ("dense", "paged"), kv_layout
        assert admission in ("reserve", "overcommit"), admission
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.extra = extra_inputs or {}
        self.bucket = bucket
        self.owner = owner
        self.tracer = NULL_TRACER
        self.attr = NULL_ATTR
        self.clock = MONOTONIC
        self.track = track if track is not None else f"engine{owner}"
        # survives end_session so an outer aggregator (the cluster) can
        # merge per-replica registries after sessions close
        self.last_metrics = MetricsRegistry()
        slot_capable = model.cache_slot_write is not None
        if mode == "auto":
            mode = "continuous" if slot_capable else "lockstep"
        if mode == "continuous" and not slot_capable:
            # every built-in family ships slot hooks now; a custom Model
            # without them must ask for lockstep explicitly
            raise ValueError(
                f"mode='continuous': model {model.cfg.name!r} exposes no "
                "cache_slot_write hook (pass mode='lockstep')")
        if bucket and not model.supports_prefill_len:
            raise ValueError(
                f"bucket={bucket!r}: family {model.cfg.family!r} prefill "
                "cannot mask right-pads (recurrent state would absorb "
                "them); drop bucket= for scan families")
        if kv_layout == "paged":
            if model.decode_paged is None:
                raise ValueError(
                    f"kv_layout='paged': family {model.cfg.family!r} has "
                    "no paged cache hooks")
            if mode != "continuous":
                raise ValueError(
                    "kv_layout='paged' requires the continuous scheduler")
        elif allocator is not None:
            raise ValueError("allocator= requires kv_layout='paged'")
        elif admission != "reserve":
            raise ValueError("admission='overcommit' requires "
                             "kv_layout='paged'")
        elif prefix_cache:
            raise ValueError("prefix_cache=True requires kv_layout="
                             "'paged' (there are no blocks to share)")
        if prefix_cache and model.cfg.family == "vlm":
            raise ValueError(
                "prefix_cache=True: vlm prompts start with a patch prefix "
                "whose content is not addressable by token ids, so prefix "
                "blocks cannot be content-hashed")
        self.mode = mode
        self.kv_layout = kv_layout
        self.policy = make_policy(policy)
        self._admission = admission
        self.prefix_cache = prefix_cache
        self.last_stats: EngineStats | None = None
        self._prefill_shapes: set[int] = set()   # compiled prefill lengths
        self._sess: _Session | None = None
        self._sample = jax.jit(_sample_rows)
        self._slot_capable = slot_capable
        # the cache is dead after every call that consumes it - donate so
        # XLA updates the multi-GB KV buffers in place instead of copying
        if kv_layout == "paged":
            if allocator is not None:
                if n_blocks is not None:
                    raise ValueError(
                        "n_blocks conflicts with an external allocator "
                        "(the pool is already sized)")
                if block_size is not None \
                        and block_size != allocator.block_size:
                    raise ValueError(
                        f"block_size={block_size} conflicts with the "
                        f"external allocator's {allocator.block_size}")
                self._owns_pool = False
                block_size = allocator.block_size
            else:
                self._owns_pool = True
                if block_size is None:
                    block_size = 16
            self.block_size = block_size
            self.max_blocks = blocks_needed(cache_len, block_size)
            if allocator is None:
                if n_blocks is None:
                    n_blocks = max_batch * self.max_blocks + 1
                allocator = BlockAllocator(n_blocks, block_size)
            allocator.claim_policy(admission)
            self.allocator = allocator
            # chunked prefill: one block_size chunk per call, slot/chunk/
            # length all traced — a single compile serves every prompt
            # length (``bucket=`` is ignored; there is nothing to bucket)
            self._prefill_chunk = jax.jit(model.prefill_paged,
                                          donate_argnums=(1,))
            self._decode = jax.jit(model.decode_paged, donate_argnums=(1,))
            self._bt_set = jax.jit(kvcache.bt_set_entry, donate_argnums=(0,))
            self._slot_release = jax.jit(kvcache.slot_release,
                                         donate_argnums=(0,))
            self._copy_block = jax.jit(kvcache.pool_copy_block,
                                       donate_argnums=(0,))
            # device pool persisted across sessions (prefix_cache only):
            # cached blocks' bytes must stay resident to be hit again
            self._pcache = None
        else:
            self._decode = jax.jit(model.decode, donate_argnums=(1,))
            self._prefill = jax.jit(
                lambda p, b: model.prefill(p, b, cache_len=cache_len))
            if slot_capable:
                self._cache_expand = jax.jit(model.cache_expand,
                                             static_argnums=(1,))
                self._slot_write = jax.jit(model.cache_slot_write,
                                           donate_argnums=(0,))
            # scan families: zero a slot's recurrent state on free/preempt
            # (KV families have no reset hook - pos masking covers them)
            self._slot_reset = (
                jax.jit(model.cache_slot_reset, donate_argnums=(0,))
                if model.cache_slot_reset is not None else None)
        if tracer is not None:
            self.set_tracer(tracer)
        if clock is not None:
            self.clock = clock
        if attribution is not None:
            self.set_attributor(attribution)

    # ------------------------------------------------------------------
    # Telemetry plumbing.
    # ------------------------------------------------------------------

    def set_tracer(self, tracer, track: str | None = None) -> None:
        """Attach (or detach, with None) a tracer.  Host-side only — no
        compiled function depends on it, so a warm engine keeps its
        caches.  The engine adopts an enabled tracer's clock so spans
        and instants share one timeline (assign ``self.clock`` after to
        override); an owned pool's allocator follows the same tracer."""
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if track is not None:
            self.track = track
        if self.tracer.enabled:
            self.clock = self.tracer.clock
        if self.kv_layout == "paged" and self._owns_pool:
            self.allocator.set_tracer(self.tracer)

    def set_attributor(self, attributor) -> None:
        """Attach (or detach, with None) a utilization attributor
        (``repro.serving.attribution.Attributor``).  Host-side only,
        like the tracer: no compiled function the engine executes
        depends on it (executable costs come from a separate AOT
        lowering of the same jitted callables, memoized per shape), so
        tokens are byte-identical with attribution on vs off, and a
        warm engine keeps its caches.  Attribution covers the
        continuous scheduler's phases — decode launches and prefills
        (dense and chunked paged alike); the legacy lockstep scheduler
        is not attributed.  One attributor may be shared across a
        cluster's replicas (the cost memo is shape-keyed)."""
        self.attr = attributor if attributor is not None else NULL_ATTR

    def _slot_track(self, i: int) -> str:
        """Trace track of slot ``i`` (request spans nest per slot, so
        concurrent slots never interleave spans on one track)."""
        return f"{self.track}/slot{i}"

    # ------------------------------------------------------------------
    # Public API.
    # ------------------------------------------------------------------

    def generate(self, requests: list[Request], key=None,
                 on_token=None) -> list[Result]:
        """Run ``requests`` to completion and return their Results.
        ``on_token`` (continuous mode only) streams every sampled token
        as a :class:`TokenEvent` the moment it exists — see ``stream``
        for the pull-based generator over the same events."""
        key = key if key is not None else jax.random.key(0)
        requests = list(requests)
        todo = [(i, r) for i, r in enumerate(requests)
                if r.max_new_tokens - len(r.done) > 0]
        if not todo:
            self.last_metrics = MetricsRegistry()
            self.last_stats = EngineStats(
                self.mode, 0.0, 0, 0.0, 0, 0.0, 0.0,
                kv_layout=self.kv_layout,
                prefill_compiles=len(self._prefill_shapes))
            return [Result(r.rid, list(r.done)) for r in requests]
        if self.kv_layout == "paged":
            # reject impossible requests before any work is scheduled: a
            # raise mid-schedule would abort the batch with blocks still
            # allocated (and admission would otherwise stall forever on a
            # request that can never fit)
            for _, r in todo:
                self.check_request(r)
        if self.mode == "continuous":
            done = self._generate_continuous(todo, key, on_token)
        else:
            if on_token is not None:
                raise ValueError("streaming (on_token) requires the "
                                 "continuous scheduler")
            done = self._generate_lockstep(todo, key)
        # requests with an exhausted budget produce their prefix verbatim
        # and never occupy a slot; everything else went to the scheduler
        results = [Result(r.rid, list(r.done)) for r in requests]
        for (i, _), res in zip(todo, done):
            results[i] = res
        return results

    def check_request(self, r: Request) -> None:
        """Reject a request that can never be served: context overflow, or
        (paged) a worst case larger than the whole pool."""
        self._check_budget(
            self._n_prefix() + len(r.prompt) + len(r.done),
            r.max_new_tokens - len(r.done), r.rid)
        if self.kv_layout == "paged":
            worst = self._worst_blocks(r)
            if worst > self.allocator.capacity:
                raise ValueError(
                    f"request rid={r.rid} needs {worst} KV blocks "
                    f"(block_size={self.block_size}) but the pool only "
                    f"has {self.allocator.capacity}")

    # ------------------------------------------------------------------
    # Admission accounting helpers.
    # ------------------------------------------------------------------

    def _gather_extra(self, rows: list[int]) -> dict:
        """Select extra-input rows by submission order (dim-1 broadcasts)."""
        out = {}
        for k, v in self.extra.items():
            if v.shape[0] == 1:
                out[k] = jnp.broadcast_to(jnp.asarray(v),
                                          (len(rows),) + tuple(v.shape[1:]))
            elif max(rows) < v.shape[0]:
                out[k] = jnp.asarray(v)[jnp.asarray(rows)]
            else:
                raise ValueError(
                    f"extra_inputs[{k!r}] has {v.shape[0]} rows but request "
                    f"#{max(rows)} needs its own (pass one row per request, "
                    "or a single row to broadcast)")
        return out

    def _check_budget(self, prefill_pos: int, max_new: int, rid) -> None:
        """Every position written past prefill must fit in cache_len: the
        per-slot strip length (dense; writes beyond it are silently dropped
        by the one-hot update) or the block-table width (paged).  Families
        with unbounded state (``model.bounded_cache`` False: ssm's O(1)
        recurrent state, hybrid's state + wrapping attention ring) have no
        write budget to enforce."""
        if not self.model.bounded_cache:
            return
        writes = prefill_pos + max(max_new - 1, 0)
        if writes > self.cache_len:
            raise ValueError(
                f"request rid={rid} needs {writes} cache positions "
                f"(prefill {prefill_pos} + {max_new - 1} decode writes) "
                f"but cache_len={self.cache_len}")

    def _n_prefix(self) -> int:
        """Model-side prefix positions prefill adds ahead of the tokens."""
        cfg = self.model.cfg
        return cfg.n_patches if cfg.family == "vlm" else 0

    def _bucket_len(self, n: int) -> int:
        """Round a prompt length up to its bucket (pow2 or pad-to-multiple),
        capped so the padded sequence still fits the per-request bound."""
        if not self.bucket:
            return n
        if self.bucket == "pow2":
            b = 1
            while b < n:
                b <<= 1
        else:
            b = -(-n // int(self.bucket)) * int(self.bucket)
        return max(min(b, self.cache_len - self._n_prefix()), n)

    def _worst_blocks(self, r: Request) -> int:
        """Worst-case block count for a request (all cache positions it can
        ever write), computable before prefill runs."""
        writes = (self._n_prefix() + len(r.prompt) + len(r.done)
                  + max(r.max_new_tokens - len(r.done) - 1, 0))
        return blocks_needed(writes, self.block_size)

    def _prefix_hits(self, r: Request) -> tuple[list, bool]:
        """Resolve the request's prefill (prompt + done) against the
        prefix index: the longest run of resident full blocks, as
        ``([(chain_key, block_id), ...], full_boundary)``.  Pure — no
        refcounts move until ``session_admit`` applies the hits —
        so ``session_can_admit`` can price an admission exactly.
        ``full_boundary`` is True when the hits cover the *entire*
        prefill: the final chunk must then be recomputed anyway (its
        logits seed the first sampled token), behind a copy-on-write
        of its shared block."""
        if not self.prefix_cache:
            return [], False
        seq = list(r.prompt) + list(r.done)
        hits = []
        for key in kvcache.prefix_chain_keys(seq, self.block_size):
            blk = self.allocator.lookup(key, self.owner)
            if blk is None:
                break
            hits.append((key, blk))
        boundary = bool(hits) and len(hits) * self.block_size == len(seq)
        return hits, boundary

    def _admit_block_need(self, r: Request) -> int:
        """Blocks a reserve admission must find unreserved-free: the worst
        case minus blocks admitted by reference, plus one for the
        full-boundary COW copy, plus one per hit that revives a cached
        (refcount-0) block — a revival spends an allocatable block just
        like a fresh allocation does."""
        hits, boundary = self._prefix_hits(r)
        n_cached = sum(self.allocator.is_cached(b) for _, b in hits)
        return (self._worst_blocks(r) - len(hits) + int(boundary)
                + n_cached)

    # ------------------------------------------------------------------
    # Stepwise session API (one continuous-batching run; ``generate``
    # drives it for the single-engine case, ClusterEngine interleaves
    # several engines' sessions over one shared pool).
    #
    # Thread affinity: an open session's state (_Session, slot arrays,
    # device cache) is NOT internally locked — all session mutators
    # (session_admit / session_step / session_preempt / session_abort /
    # end_session) of one engine must be driven from a single thread at
    # a time.  The threaded cluster driver honors this by pinning each
    # engine to one worker thread and handing admissions/preemptions to
    # that worker over a queue; only the *shared* BlockAllocator (its
    # own lock) and the tracer (locked) are touched cross-thread.
    # ``session_active`` and ``session_can_admit`` are safe advisory
    # reads from other threads (a slot count and a pool-side check).
    # ------------------------------------------------------------------

    def begin_session(self, key=None, on_token=None) -> None:
        """Open a stepwise session.  ``on_token``, when given, streams
        every sampled token as a :class:`TokenEvent` the moment it
        exists (called synchronously from the admitting/stepping
        thread)."""
        if self.mode != "continuous":
            raise ValueError("stepwise sessions require the continuous "
                             "scheduler")
        if self._sess is not None:
            raise RuntimeError("a session is already open on this engine")
        install_gc_spans()
        bsz = self.max_batch
        if self.kv_layout == "paged" and self._owns_pool:
            self.allocator.reset_peak()
        self._sess = _Session(
            key=key if key is not None else jax.random.key(0),
            slots=[None] * bsz,
            toks=np.zeros((bsz, 1), np.int32),
            temps=np.zeros((bsz,), np.float32),
            rids=np.zeros((bsz,), np.int32),
            tok_idx=np.zeros((bsz,), np.int32),
            metrics=MetricsRegistry(), t_start=self.clock.now(),
            on_token=on_token)

    def _require_session(self) -> _Session:
        if self._sess is None:
            raise RuntimeError("no session is open on this engine "
                               "(call begin_session first)")
        return self._sess

    @property
    def session_active(self) -> int:
        """Busy slot count of the open session (0 when none is open)."""
        if self._sess is None:
            return 0
        return sum(s is not None for s in self._sess.slots)

    def session_free_slot(self) -> int | None:
        for i, s in enumerate(self._sess.slots):
            if s is None:
                return i
        return None

    def session_slots(self):
        """Live (slot index, slot) pairs - victim scanning."""
        return [(i, s) for i, s in enumerate(self._sess.slots)
                if s is not None]

    def session_victims(self, now: float):
        """Policy-ranked preemption candidates of the open session:
        ``(victim_key, slot)`` pairs — the minimum key is the preferred
        victim.  The key's leading element is the policy's protection
        flag (``slo_adaptive``: 1 while the request is inside its
        deadline slack), so callers can tell a protected pick apart."""
        return [(self.policy.victim_key(s.req, s.admit_seq, s.admit_t,
                                        now), i)
                for i, s in self.session_slots()]

    def session_backlog(self) -> int:
        """Outstanding decode tokens across live slots (shortest-queue
        routing metric)."""
        return sum(s.req.max_new_tokens - len(s.req.done) - len(s.tokens)
                   for _, s in self.session_slots())

    def session_slot_steps(self) -> tuple[int, int]:
        """(busy, offered) slot-steps of the open session - offered counts
        max_batch lanes per launched decode step (cluster occupancy)."""
        m = self._require_session().metrics
        return (m.counter("busy_slot_steps").n,
                m.counter("offered_slot_steps").n)

    def session_can_admit(self, r: Request) -> bool:
        """Pool-side admission test (always true for the dense layout,
        where ``check_request`` already enforced the per-slot budget).

        reserve: the pool must cover the request's worst case on top of
        standing reservations, so lazy growth can never fail mid-prefill
        or mid-decode.
        overcommit: only the *first prefill chunk's* block must be free —
        prefill itself now grows lazily chunk by chunk, so admission is
        bounded by free blocks for prefill exactly as it is for decode,
        and later growth (either phase) may raise PoolPressure, resolved
        by cluster preemption.  A False here always clears once live
        requests finish and recycle blocks (``check_request`` rejected
        requests that exceed the whole pool)."""
        if self.kv_layout != "paged":
            return True
        if self._admission == "overcommit":
            return self.allocator.n_avail >= 1
        return self.allocator.n_avail >= self._admit_block_need(r)

    def _emit_token(self, sess: _Session, r: Request, tok: int,
                    index: int) -> None:
        """Stream one sampled token through the session's ``on_token``
        callback (no-op without one)."""
        if sess.on_token is not None:
            sess.on_token(TokenEvent(r.rid, tok, index,
                                     index + 1 >= r.max_new_tokens))

    def _observe_slo_ttft(self, r: Request, slot: int, enqueue_t,
                          admit_t: float, t1: float) -> None:
        """Score the first token of a TTFT-budgeted request.  The
        deadline base is the enqueue time (what a client experiences);
        a requeued mid-prefill victim falls back to its first admission
        time (``first_admit_t``), so a chain of evictions cannot reset
        the clock.  Host-side only: budgets never touch tokens."""
        base = r.first_admit_t
        if base is None:
            base = enqueue_t if enqueue_t is not None else admit_t
        att_ms = (t1 - base) * 1e3
        m = self._sess.metrics
        m.counter("slo_ttft_total").inc()
        m.histogram("slo_ttft_slack_ms").observe(r.slo_ttft_ms - att_ms)
        if att_ms <= r.slo_ttft_ms:
            m.counter("slo_ttft_attained").inc()
        elif self.tracer.enabled:
            # deadline-miss span: the overrun stretch, deadline -> first
            # token, on the slot track next to the prefill it indicts
            self.tracer.complete(self._slot_track(slot), "slo_miss",
                                 base + r.slo_ttft_ms / 1e3, t1,
                                 rid=r.rid, phase="ttft",
                                 over_ms=att_ms - r.slo_ttft_ms)

    def _observe_slo_tpot(self, s: _Slot, per_tok_ms: float) -> None:
        """Score a finished TPOT-budgeted request's decode rate."""
        m = self._sess.metrics
        m.counter("slo_tpot_total").inc()
        m.histogram("slo_tpot_slack_ms").observe(
            s.req.slo_tpot_ms - per_tok_ms)
        if per_tok_ms <= s.req.slo_tpot_ms:
            m.counter("slo_tpot_attained").inc()
        elif self.tracer.enabled:
            self.tracer.instant(self.track, "slo_miss", rid=s.req.rid,
                                phase="tpot",
                                over_ms=per_tok_ms - s.req.slo_tpot_ms)

    def _replay_done(self, sub, done):
        """Rebuild a preempted scan-family request's recurrent state
        bit-exactly: starting from the prompt-only prefill cache ``sub``,
        feed each already-generated ``done`` token through the decode
        step on a batch-1 slot pool — the same executable family the
        uninterrupted run decoded with, so the resumed stream's logits
        (and tokens) are byte-identical to never having been preempted.
        Returns (last logits, batch-1 pool cache); the last logits are
        the distribution for stream index ``len(done)``."""
        mini = self._slot_write(self._cache_expand(sub, 1), sub, 0)
        logits = None
        for t in done:
            logits, mini = self._decode(self.params, mini,
                                        jnp.asarray([[t]], jnp.int32))
        return logits, mini

    def session_admit(self, r: Request, tag: int, extra_row: int = 0,
                      admit_seq: int | None = None,
                      enqueue_t: float | None = None) -> Result | None:
        """Admit ``r`` into the first free slot.

        dense: prefill runs here (prefill-on-admit) and the first token is
        sampled; returns the finished Result when the token budget is
        satisfied by the admission itself, else None.

        paged: admission only installs the request and (under reserve)
        promises its worst case — the prefill itself runs *chunk by chunk*
        inside ``session_step``, allocating each chunk's block lazily, so
        no block is held before it is written and pool pressure during a
        long prompt's prefill surfaces exactly like decode-time growth
        (PoolPressure → cluster preemption, including of the half-prefilled
        request itself).  Always returns None; budget-satisfied-by-prefill
        results arrive from ``session_step``.

        ``tag`` is echoed back with the Result from ``session_step``;
        ``extra_row`` indexes ``extra_inputs``; ``admit_seq`` orders
        admissions globally for victim selection (defaults to a per-engine
        counter); ``enqueue_t`` is the clock time the request entered the
        caller's queue (recorded as its queue-age sample)."""
        with phase("engine.admit", self.tracer, self.track):
            return self._admit(r, tag, extra_row, admit_seq, enqueue_t)

    def _admit(self, r: Request, tag: int, extra_row: int,
               admit_seq: int | None,
               enqueue_t: float | None) -> Result | None:
        sess = self._require_session()
        slot = self.session_free_slot()
        if slot is None:
            raise RuntimeError("session_admit with no free slot")
        if admit_seq is None:
            admit_seq = sess.admit_counter
        sess.admit_counter = max(sess.admit_counter, admit_seq) + 1
        t0 = self.clock.now()
        if enqueue_t is not None:
            sess.metrics.histogram("queue_age_ms").observe(
                (t0 - enqueue_t) * 1e3)
        if self.kv_layout == "paged":
            prefill_pos = (self._n_prefix() + len(r.prompt) + len(r.done))
            self._check_budget(prefill_pos,
                               r.max_new_tokens - len(r.done), r.rid)
            if sess.cache is None:
                if self._pcache is not None:
                    # prefix cache: the previous session's device pool is
                    # revived so cached blocks' bytes are still resident
                    sess.cache, self._pcache = self._pcache, None
                else:
                    sess.cache = self.model.paged_cache_init(
                        batch=self.max_batch,
                        n_blocks=self.allocator.n_blocks,
                        block_size=self.block_size,
                        max_blocks=self.max_blocks,
                        dtype=self.model.cache_dtype(self.params))
            # Resolve + charge the pool atomically: between a lookup and
            # its incref/take_cached, a co-tenant replica's alloc in
            # another thread could otherwise evict the cached block out
            # from under us.  reserve() runs before any reference moves,
            # so a MemoryError here (a lost admission race under the
            # threaded driver) leaves the pool untouched and the
            # admission can simply be retried.
            with self.allocator.lock:
                hits, boundary = self._prefix_hits(r)
                reserve_left = 0
                if self._admission == "reserve":
                    # promise the whole worst case up front (minus blocks
                    # admitted by reference, plus the boundary COW copy
                    # and any cached revivals — see _admit_block_need);
                    # every lazy allocation converts one promise into a
                    # live block, so growth can never fail
                    reserve_left = (self._worst_blocks(r) - len(hits)
                                    + int(boundary))
                    n_cached = sum(self.allocator.is_cached(b)
                                   for _, b in hits)
                    self.allocator.reserve(reserve_left + n_cached)
                # reference each resident block (reviving cached ones)
                taken: list[int] = []
                for _, blk in hits:
                    if self.allocator.is_cached(blk):
                        # reviving costs one allocatable block; under
                        # reserve it was priced into the reservation
                        # above (and can never fail); under overcommit
                        # the revived block is itself part of n_free, so
                        # this never fails either
                        self.allocator.take_cached(
                            blk, self.owner,
                            from_reservation=self._admission == "reserve")
                    else:
                        self.allocator.incref(blk, self.owner)
                    taken.append(blk)
            # install the (now unevictable) referenced blocks in the
            # slot's block table — device-side, no pool lock needed
            for idx, blk in enumerate(taken):
                sess.cache = self._bt_set(sess.cache, slot, idx, blk)
            h = len(taken)
            # a fully-covered prefill still re-runs its final chunk (the
            # engine needs its logits) behind the COW barrier; partial
            # coverage resumes cold at the first miss
            chunks_done = h - 1 if boundary else h
            sess.metrics.counter("prefix_hits").inc(h)
            sess.metrics.counter("prefix_tokens_reused").inc(
                chunks_done * self.block_size)
            if r.done or r.requeues:
                sess.metrics.counter("requeued").inc()
            tr = self.tracer
            if tr.enabled:
                st = self._slot_track(slot)
                tr.instant(st, "admit", rid=r.rid, slot=slot,
                           readmit=bool(r.done or r.requeues),
                           prefix_hits=h,
                           prefix_tokens=chunks_done * self.block_size)
                if h:
                    tr.instant("pool", "kv_ref", rid=r.rid, n=h)
                if r.requeues:
                    # close the flow arrow the eviction opened: the trace
                    # draws preempt (victim slot) -> re-admission (here)
                    tr.flow_end(st, "preempt_flow",
                                f"preempt-{r.rid}-{r.requeues}")
            sess.slots[slot] = _Slot(
                req=r, tag=tag, tokens=[], ttft_ms=0.0, admit_seq=admit_seq,
                prefill_pos=prefill_pos, reserve_left=reserve_left,
                blocks=taken, shared_until=h,
                chunks_done=chunks_done, extra_row=extra_row,
                admit_t=(r.first_admit_t if r.first_admit_t is not None
                         else t0), enqueue_t=enqueue_t, span_t0=t0)
            sess.temps[slot] = r.temperature
            sess.rids[slot] = r.rid
            return None
        tr = self.tracer
        if tr.enabled:
            tr.instant(self._slot_track(slot), "admit", rid=r.rid,
                       slot=slot, readmit=bool(r.done or r.requeues),
                       prefix_hits=0, prefix_tokens=0)
            if r.requeues:
                tr.flow_end(self._slot_track(slot), "preempt_flow",
                            f"preempt-{r.rid}-{r.requeues}")
        # scan families re-admit by *replay*: chunkwise prefill covers
        # only the original prompt (the computation the uninterrupted run
        # performed) and the generated ``done`` tokens are stepped through
        # the decode recurrence afterwards (``_replay_done``).  The
        # chunked prefill and the stepwise recurrence are mathematically
        # but not bitwise interchangeable, so prefilling prompt+done
        # would perturb the resumed stream's logits.  KV families have no
        # such split (prefill writes per-position KV): prompt+done
        # prefills in one pass, byte-exactly.
        replay = bool(r.done) and self._slot_reset is not None
        prompt = np.asarray(
            list(r.prompt) + ([] if replay else list(r.done)), np.int32)
        plen = len(prompt)
        sb = self._bucket_len(plen)
        if self.bucket:
            # right-pad to the bucket and pass the true length: causality
            # hides the pads, pad KV lands past pos (masked in decode and
            # overwritten as decode proceeds), so outputs are unchanged
            toks = np.zeros((1, sb), np.int32)
            toks[0, :plen] = prompt
            batch = {"tokens": jnp.asarray(toks),
                     "prefill_len": jnp.asarray([plen], np.int32),
                     **self._gather_extra([extra_row])}
        else:
            batch = {"tokens": jnp.asarray(prompt[None]),
                     **self._gather_extra([extra_row])}
        self._prefill_shapes.add(batch["tokens"].shape[1])
        with phase("engine.prefill", tr, self.track, rid=r.rid, tokens=plen):
            logits, sub = self._prefill(self.params, batch)
            if replay:
                logits, sub = self._replay_done(sub, r.done)
                sess.metrics.counter("resume_replay_tokens").inc(len(r.done))
            # sub["pos"] covers any model-side prefix (e.g. vlm patches)
            prefill_pos = int(np.asarray(sub["pos"]).reshape(()))
            self._check_budget(prefill_pos, r.max_new_tokens - len(r.done),
                               r.rid)
            if sess.cache is None:
                sess.cache = self._cache_expand(sub, self.max_batch)
            sess.cache = self._slot_write(sess.cache, sub, slot)
            # the request's t-th token always uses stream index t, so a
            # re-admitted (preempted) request resumes its stream at len(done)
            tok = self._sample(logits, jnp.full((1,), r.temperature),
                               sess.key, jnp.asarray([r.rid], np.int32),
                               jnp.asarray([len(r.done)], np.int32))
            tok = int(np.asarray(jax.block_until_ready(tok))[0])
        t1 = self.clock.now()
        ttft_ms = (t1 - t0) * 1e3
        if tr.enabled:
            tr.complete(self._slot_track(slot), "prefill", t0, t1,
                        rid=r.rid, tokens=plen)
        at = self.attr
        if at.enabled:
            cost = at.phase_cost(
                ("prefill", self.model.cfg.name, batch["tokens"].shape[1]),
                self._prefill, (self.params, batch))
            at.record_prefill(sess.metrics, tr, self._slot_track(slot),
                              t0=t0, t1=t1, cost=cost)
        if r.done or r.requeues:
            sess.metrics.counter("requeued").inc()
        if not r.done:
            sess.metrics.histogram("ttft_ms").observe(ttft_ms)
            if r.slo_ttft_ms is not None:
                self._observe_slo_ttft(r, slot, enqueue_t, t0, t1)
        if r.first_ttft_ms is not None:
            ttft_ms = r.first_ttft_ms   # re-admission: keep the real TTFT
        self._emit_token(sess, r, tok, len(r.done))
        s = _Slot(req=r, tag=tag, tokens=[tok], ttft_ms=ttft_ms,
                  admit_seq=admit_seq, prefill_pos=prefill_pos, admit_t=t0,
                  enqueue_t=enqueue_t, span_t0=t0, first_tok_t=t1)
        if len(r.done) + 1 >= r.max_new_tokens:
            res = self._finish(s)       # satisfied by prefill alone
            self._release(s, slot)
            if tr.enabled:
                self._trace_finish(s, slot, self.clock.now())
            return res
        sess.slots[slot] = s
        sess.toks[slot, 0] = tok
        sess.temps[slot] = r.temperature
        sess.rids[slot] = r.rid
        sess.tok_idx[slot] = len(r.done) + 1
        return None

    def session_step(self) -> list[tuple[int, Result]]:
        """One scheduler step over the slot pool: finish any pending
        chunked prefills (paged layout), then one decode launch.  Returns
        the (tag, Result) pairs that finished this step; empty when no
        slot is live.  Under overcommit admission, raises PoolPressure
        when lazy block growth (a prefill chunk's block or a decode
        slot's next write position) finds the pool empty - the decode has
        not run, prefill chunks already computed and blocks already grown
        stay put, and the call can be retried after the caller frees
        blocks (``session_preempt``) - a retried step resumes a
        half-prefilled slot at its next chunk."""
        sess = self._require_session()
        with phase("engine.step", self.tracer, self.track,
                   step_num=sess.metrics.counter("decode_steps").n):
            return self._step(sess)

    def _step(self, sess: _Session) -> list[tuple[int, Result]]:
        bsz = self.max_batch
        if self.kv_layout == "paged":
            for i in range(bsz):
                s = sess.slots[i]
                if s is not None and s.chunks_done is not None:
                    left = (blocks_needed(s.prefill_pos, self.block_size)
                            - s.chunks_done)
                    with phase("engine.prefill", self.tracer, self.track,
                               rid=s.req.rid, chunks=left):
                        res = self._advance_prefill(sess, i, s)
                    if res is not None:     # satisfied by prefill alone
                        # park it: a PoolPressure later in this same step
                        # must not lose an already-released slot's Result
                        sess.finished_pending.append((s.tag, res))
                        self._release(s, i)
                        sess.slots[i] = None
                        if self.tracer.enabled:
                            self._trace_finish(s, i, self.clock.now())
        active = [i for i in range(bsz) if sess.slots[i] is not None]
        if self.kv_layout == "paged":
            # lazy growth: each slot's next write position must have a
            # block before the step; under reserve admission these
            # allocations can never fail mid-flight
            for i in active:
                s = sess.slots[i]
                pos = s.prefill_pos + s.steps
                while len(s.blocks) * self.block_size <= pos:
                    self._grow_slot(sess, i, s)
        # past the last allocation: nothing below can raise PoolPressure,
        # so parked prefill-phase Results can leave the session now
        finished, sess.finished_pending = sess.finished_pending, []
        if not active:
            return finished
        # one decode step over the whole slot pool (fixed shapes; idle
        # slots compute too - their rows are masked by per-slot pos and
        # fully rewritten on the next admission; paged idle rows write
        # into the null block)
        tr = self.tracer
        t0 = self.clock.now()
        with phase("engine.decode_dispatch", tr, self.track):
            logits, sess.cache = self._decode(self.params, sess.cache,
                                              jnp.asarray(sess.toks))
        # the decode launch returns asynchronously: [t0, t_disp] is host
        # dispatch (trace/lowering lookup + enqueue), the np.asarray
        # below blocks until the device result lands, so [t_disp, t1]
        # is sampling dispatch + device compute + transfer
        t_disp = self.clock.now()
        with phase("engine.sample_dispatch", tr, self.track):
            nxt = self._sample(
                logits, jnp.asarray(sess.temps), sess.key,
                jnp.asarray(sess.rids), jnp.asarray(sess.tok_idx))
        with phase("engine.result_wait", tr, self.track):
            nxt = np.asarray(nxt)
        t1 = self.clock.now()
        dt = t1 - t0
        m = sess.metrics
        m.counter("decode_steps").inc()
        m.counter("busy_slot_steps").inc(len(active))
        m.counter("offered_slot_steps").inc(bsz)
        if tr.enabled:
            tr.complete(self.track, "step", t0, t1, active=len(active))
        at = self.attr
        if at.enabled:
            # shapes only (the post-step cache aliases the pre-step
            # shapes); a memo hit is a dict lookup, a miss lowers this
            # jitted decode AOT without executing or donating anything
            cost = at.phase_cost(
                ("decode", self.kv_layout, self.model.cfg.name, bsz),
                self._decode, (self.params, sess.cache,
                               jnp.asarray(sess.toks)))
            at.record_step(m, tr, self.track, t0=t0, t_disp=t_disp, t1=t1,
                           active=len(active), width=bsz, cost=cost)
        with phase("engine.emit", tr, self.track):
            for i in active:
                s = sess.slots[i]
                s.tokens.append(int(nxt[i]))
                self._emit_token(sess, s.req, int(nxt[i]),
                                 len(s.req.done) + len(s.tokens) - 1)
                s.steps += 1
                s.decode_s += dt
                sess.toks[i, 0] = nxt[i]
                sess.tok_idx[i] += 1
                if len(s.req.done) + len(s.tokens) >= s.req.max_new_tokens:
                    finished.append((s.tag, self._finish(s)))
                    self._release(s, i)
                    sess.slots[i] = None   # freed: refilled on next admit
                    if tr.enabled:
                        self._trace_finish(s, i, t1)
        return finished

    def _grow_slot(self, sess: _Session, i: int, s: _Slot) -> None:
        """Allocate slot ``i``'s next block and install it in the block
        table (lazy growth, shared by prefill chunks and decode writes).
        Under reserve admission one standing promise becomes live — the
        allocation draws *from the reservation* (``from_reservation=``),
        so it can spend blocks other requests' promises hold back, and
        the allocator retires the promise atomically with the grant;
        under overcommit an empty pool surfaces as PoolPressure."""
        with phase("engine.grow", self.tracer, self.track):
            blk = self._alloc_block(i, from_reservation=s.reserve_left > 0)
            if s.reserve_left:
                s.reserve_left -= 1
            if self.tracer.enabled:
                self.tracer.instant("pool", "kv_alloc", rid=s.req.rid, n=1,
                                    block=blk)
            sess.cache = self._bt_set(sess.cache, i, len(s.blocks), blk)
            s.blocks.append(blk)

    def _alloc_block(self, i: int, *, from_reservation: bool) -> int:
        """One pool allocation with overcommit pressure translation."""
        try:
            return self.allocator.alloc(self.owner,
                                        from_reservation=from_reservation)
        except MemoryError as e:
            if self._admission == "overcommit":
                if self.tracer.enabled:
                    self.tracer.instant("pool", "pool_pressure",
                                        owner=self.owner, slot=i)
                raise PoolPressure(self.owner, i) from e
            raise

    def _cow_block(self, sess: _Session, i: int, s: _Slot, c: int) -> None:
        """Copy-on-write barrier for chunk ``c`` of slot ``i``: the slot is
        about to write into ``blocks[c]``, which it holds by reference from
        the prefix index.  If any other request also holds it, allocate a
        private block, copy the shared bytes, and swap the table entry
        (the shared block just loses this slot's reference); a sole holder
        rewrites in place — the recompute produces identical bytes, so the
        index entry stays valid either way.  Resumable: a PoolPressure
        from the allocation mutates nothing."""
        old = s.blocks[c]
        if self.allocator.refcount(old) > 1:
            with phase("engine.grow", self.tracer, self.track):
                blk = self._alloc_block(i,
                                        from_reservation=s.reserve_left > 0)
                if s.reserve_left:
                    s.reserve_left -= 1
                sess.cache = self._copy_block(sess.cache, np.int32(blk),
                                              np.int32(old))
                sess.cache = self._bt_set(sess.cache, i, c, blk)
                self.allocator.free([old], self.owner)
                s.blocks[c] = blk
                if self.tracer.enabled:
                    self.tracer.instant("pool", "kv_cow", rid=s.req.rid,
                                        alloc=1, freed=1, block=blk)
        s.shared_until = c

    def _chunk_tokens(self, r: Request, chunk: int) -> jnp.ndarray:
        """(1, block_size) token feed for combined positions
        ``[chunk*bs, (chunk+1)*bs)``: prompt + done ids where the position
        maps to a token, 0 where it is a model-side prefix row (vlm
        patches, re-embedded from ``extra_inputs`` by the model) or
        right-pad past the prompt (masked out causally and overwritten as
        decode proceeds)."""
        bs = self.block_size
        npre = self._n_prefix()
        seq = list(r.prompt) + list(r.done)
        toks = np.zeros((1, bs), np.int32)
        lo = max(chunk * bs, npre)
        hi = min((chunk + 1) * bs, npre + len(seq))
        if hi > lo:
            toks[0, lo - chunk * bs:hi - chunk * bs] = seq[lo - npre:
                                                           hi - npre]
        return jnp.asarray(toks)

    def _advance_prefill(self, sess: _Session, i: int,
                         s: _Slot) -> Result | None:
        """Run slot ``i``'s remaining prefill chunks, allocating each
        chunk's block just before computing it (resumable: PoolPressure
        from an allocation leaves ``chunks_done`` and the blocks already
        written intact, and a retried step continues from the next chunk).
        On completion samples the request's first token; returns the
        finished Result when the token budget is satisfied by the prefill
        itself, else None."""
        r = s.req
        n_chunks = blocks_needed(s.prefill_pos, self.block_size)
        extra = self._gather_extra([s.extra_row])   # same rows every chunk
        logits = None
        while s.chunks_done < n_chunks:
            c = s.chunks_done
            if c < s.shared_until:
                # write barrier: this chunk is about to rewrite a block
                # referenced from the prefix index (a full-boundary hit
                # recomputes its final chunk for the logits) — give the
                # slot a private copy first if anyone else reads it
                self._cow_block(sess, i, s, c)  # may raise PoolPressure
            if len(s.blocks) <= c:
                self._grow_slot(sess, i, s)     # may raise PoolPressure
            batch = {"tokens": self._chunk_tokens(r, c), **extra}
            self._prefill_shapes.add(("chunk", self.block_size))
            at = self.attr
            tc0 = self.clock.now() if at.enabled else 0.0
            with self.tracer.span(self._slot_track(i), "chunk",
                                  rid=r.rid, chunk=c):
                logits, sess.cache = self._prefill_chunk(
                    self.params, sess.cache, batch, np.int32(i),
                    np.int32(c), np.int32(s.prefill_pos))
            s.chunks_done += 1
            if at.enabled:
                cost = at.phase_cost(
                    ("prefill_chunk", self.model.cfg.name, self.block_size),
                    self._prefill_chunk,
                    (self.params, sess.cache, batch, np.int32(i),
                     np.int32(c), np.int32(s.prefill_pos)))
                at.record_prefill(sess.metrics, self.tracer,
                                  self._slot_track(i), t0=tc0,
                                  t1=self.clock.now(), cost=cost)
        if self.prefix_cache:
            # publish every full prompt-prefix block (re-registering a hit
            # is a no-op; a COW'd boundary block supersedes the old entry).
            # Decode writes always land past prefill_pos — in blocks beyond
            # the full spans — so registered bytes are pure prefill output
            seq = list(r.prompt) + list(r.done)
            for c, key in enumerate(
                    kvcache.prefix_chain_keys(seq, self.block_size)):
                self.allocator.register(key, s.blocks[c], self.owner)
        tok = self._sample(logits, jnp.full((1,), r.temperature),
                           sess.key, jnp.asarray([r.rid], np.int32),
                           jnp.asarray([len(r.done)], np.int32))
        tok = int(np.asarray(jax.block_until_ready(tok))[0])
        t1 = self.clock.now()
        ttft_ms = (t1 - s.admit_t) * 1e3
        if self.tracer.enabled:
            # this admission's prefill: s.span_t0 (admit), not s.admit_t
            # (which spans back across preemptions to the first attempt)
            self.tracer.complete(self._slot_track(i), "prefill",
                                 s.span_t0, t1, rid=r.rid,
                                 chunks=n_chunks, tokens=s.prefill_pos)
        if not r.done:
            sess.metrics.histogram("ttft_ms").observe(ttft_ms)
            if r.slo_ttft_ms is not None:
                self._observe_slo_ttft(r, i, s.enqueue_t, s.admit_t, t1)
        s.ttft_ms = (r.first_ttft_ms if r.first_ttft_ms is not None
                     else ttft_ms)
        s.first_tok_t = t1
        s.tokens.append(tok)
        self._emit_token(sess, r, tok, len(r.done))
        s.chunks_done = None            # prefill complete: decode from here
        if len(r.done) + 1 >= r.max_new_tokens:
            return self._finish(s)
        sess.toks[i, 0] = tok
        sess.tok_idx[i] = len(r.done) + 1
        return None

    def session_preempt(self, slot: int) -> tuple[int, Request]:
        """Evict the request in ``slot``: free its blocks back to the pool
        and return ``(tag, requeued request)`` - the requeued request
        carries the tokens generated so far in ``done``, so a later
        re-admission prefills prompt + done and resumes the sampled stream
        at index len(done), reproducing the uninterrupted output exactly.
        A slot still mid-prefill (chunked paged prefill) is a valid
        victim: its ``done`` is unchanged and the whole prompt re-prefills
        later."""
        sess = self._require_session()
        s = sess.slots[slot]
        if s is None:
            raise ValueError(f"slot {slot} is not live")
        requeued = dataclasses.replace(
            s.req, done=tuple(s.req.done) + tuple(s.tokens),
            first_ttft_ms=(s.ttft_ms if s.tokens else s.req.first_ttft_ms),
            # s.admit_t already spans back to the first admission (set
            # from first_admit_t on re-admissions), so a chain of
            # mid-prefill evictions keeps the original TTFT base
            first_admit_t=s.admit_t, requeues=s.req.requeues + 1)
        tr = self.tracer
        if tr.enabled:
            st = self._slot_track(slot)
            t1 = self.clock.now()
            if s.steps:
                tr.complete(st, "decode", s.first_tok_t, t1,
                            rid=s.req.rid, tokens=s.steps)
            tr.complete(st, f"req {s.req.rid}", s.span_t0, t1,
                        rid=s.req.rid, preempted=True)
            tr.instant(st, "preempt", rid=s.req.rid,
                       tokens_done=len(requeued.done),
                       mid_prefill=s.chunks_done is not None)
            # open the flow arrow; the requeue/abort that answers this
            # eviction closes it (fid matches the requeued copy's count)
            tr.flow_start(st, "preempt_flow",
                          f"preempt-{s.req.rid}-{requeued.requeues}")
        self._release(s, slot)
        sess.slots[slot] = None
        sess.metrics.counter("preempted").inc()
        return s.tag, requeued

    def session_abort(self) -> None:
        """Tear down an open session after a failure, returning any blocks
        and reservations to the pool so the engine (and a shared pool's
        co-tenants) stay usable.  The device cache is rebuilt per session,
        so host-side block ownership is the only state that must survive."""
        sess = self._sess
        if sess is None:
            return
        if self.tracer.enabled:
            for i, s in enumerate(sess.slots):
                if s is not None:
                    self.tracer.instant(self._slot_track(i), "abort",
                                        rid=s.req.rid)
        if self.kv_layout == "paged":
            for s in sess.slots:
                if s is not None:
                    if s.blocks:
                        self.allocator.free(s.blocks, self.owner)
                    self.allocator.unreserve(s.reserve_left)
            if self.prefix_cache:
                # the aborted session's device pool is not trustworthy
                # (a failure may have left blocks half-written): drop it
                # and de-index everything this engine registered — cached
                # blocks return to the raw free list, so the pool still
                # drains clean
                self._pcache = None
                self.allocator.flush_index(self.owner)
        self._sess = None

    def end_session(self) -> EngineStats:
        """Close the session and return its aggregate stats."""
        sess = self._require_session()
        if self.session_active:
            raise RuntimeError("end_session with live slots (drain or "
                               "preempt them first)")
        if sess.finished_pending:
            raise RuntimeError(
                "end_session with undelivered finished Results (a "
                "PoolPressure interrupted their step; call session_step "
                "once more to collect them)")
        wall = self.clock.now() - sess.t_start
        stats = EngineStats.from_registry(
            sess.metrics, mode="continuous", wall_s=wall,
            kv_layout=self.kv_layout,
            prefill_compiles=len(self._prefill_shapes),
            block_util_peak=(self.allocator.stats().peak_utilization
                             if self.kv_layout == "paged" else 0.0),
            sched_policy=self.policy.name)
        self.last_metrics = sess.metrics
        if self.kv_layout == "paged" and self.prefix_cache:
            # keep the device pool alive across sessions: cached blocks'
            # bytes must stay resident for a later session to hit them
            self._pcache = sess.cache
        self._sess = None
        return stats

    def _finish(self, s: _Slot) -> Result:
        per_tok = s.decode_s * 1e3 / max(s.steps, 1)
        tokens = list(s.req.done) + s.tokens
        m = self._sess.metrics
        m.counter("generated_tokens").inc(len(tokens))
        if s.steps:
            m.histogram("tpot_ms").observe(per_tok)
            if s.req.slo_tpot_ms is not None:
                self._observe_slo_tpot(s, per_tok)
        return Result(s.req.rid, tokens, s.ttft_ms, per_tok)

    def _trace_finish(self, s: _Slot, i: int, t1: float) -> None:
        """Close a finished request's spans on its slot track: the decode
        stretch (first token -> finish), the whole-admission request
        span, and the ``finish`` instant."""
        tr = self.tracer
        st = self._slot_track(i)
        if s.steps:
            tr.complete(st, "decode", s.first_tok_t, t1, rid=s.req.rid,
                        tokens=s.steps)
        tr.complete(st, f"req {s.req.rid}", s.span_t0, t1, rid=s.req.rid)
        tr.instant(st, "finish", rid=s.req.rid,
                   tokens=len(s.req.done) + len(s.tokens))

    def _release(self, s: _Slot, i: int) -> None:
        """Free slot ``i``'s cache-side state.

        dense + scan family: zero the slot's recurrent state and position
        (``model.cache_slot_reset``) so nothing of the finished/preempted
        request survives in the pool — the no-leak invariant the
        regression tests assert directly.

        paged: drop the slot's block references — an unshared block
        returns to the pool immediately, a shared one stays live for its
        other holders, a registered last-reference block parks in the
        cached LRU — and park the block-table row on the null block so
        idle decode writes cannot touch recycled blocks."""
        with phase("engine.release", self.tracer, self.track):
            if self.kv_layout != "paged":
                if (self._slot_reset is not None
                        and self._sess.cache is not None):
                    self._sess.cache = self._slot_reset(self._sess.cache, i)
                return
            if self.tracer.enabled and s.blocks:
                self.tracer.instant("pool", "kv_free", rid=s.req.rid,
                                    n=len(s.blocks))
            self.allocator.free(s.blocks, self.owner)
            self.allocator.unreserve(s.reserve_left)
            s.blocks, s.reserve_left = [], 0
            self._sess.cache = self._slot_release(self._sess.cache, i)

    # ------------------------------------------------------------------
    # Continuous batching (slot pool + admission scheduler).
    # ------------------------------------------------------------------

    def stream(self, requests: list[Request], key=None):
        """Streaming ``generate``: a generator yielding
        :class:`TokenEvent` rows as tokens are sampled (per-rid events
        arrive in index order; cross-request interleaving follows the
        scheduler).  The run itself executes on a background thread;
        once the generator is exhausted ``last_stats``/``last_metrics``
        hold the finished run's aggregates, and any engine exception
        re-raises here.  Abandoning the generator early leaves the run
        to finish in the background (daemon thread)."""
        return _stream_events(
            lambda cb: self.generate(requests, key=key, on_token=cb))

    def _generate_continuous(self, items, key, on_token=None) \
            -> list[Result]:
        """items: [(submission order, Request)]; results align with items."""
        self.begin_session(key, on_token)
        queue = collections.deque(
            (seq, order, r) for seq, (order, r) in enumerate(items))
        results: list[Result | None] = [None] * len(items)
        try:
            while queue or self.session_active:
                # admission: refill every free slot before the next decode
                # step.  The fifo policy admits strictly in arrival order
                # (no skip-ahead, so a big request cannot starve under
                # paged admission); reordering policies pick the minimum
                # order_key instead — but still stop at the first
                # inadmissible pick rather than skipping past it, so the
                # no-starvation property holds per policy choice too
                while queue and self.session_free_slot() is not None:
                    if self.policy.reorders:
                        now = self.clock.now()
                        item = min(queue,
                                   key=lambda it: self.policy.order_key(
                                       it[0], it[2], self._sess.t_start,
                                       now))
                    else:
                        item = queue[0]
                    seq, order, r = item
                    if not self.session_can_admit(r):
                        break
                    queue.remove(item)
                    res = self.session_admit(r, tag=seq, extra_row=order,
                                             enqueue_t=self._sess.t_start)
                    if res is not None:
                        results[seq] = res
                if queue and not self.session_active:
                    # nothing live here yet the head cannot be admitted:
                    # only reachable when a shared pool's co-tenant holds
                    # the blocks - fail loudly instead of spinning (a
                    # cluster driver interleaves engines; generate cannot)
                    raise MemoryError(
                        f"engine owner={self.owner!r} is idle but the "
                        f"shared pool cannot admit rid="
                        f"{queue[0][2].rid} (co-tenants hold "
                        f"{self.allocator.n_live} blocks, "
                        f"{self.allocator.n_reserved} reserved)")
                for tag, res in self.session_step():
                    results[tag] = res
        except BaseException:
            # keep the allocator consistent if anything aborts the batch
            # mid-schedule
            self.session_abort()
            raise
        self.last_stats = self.end_session()
        return results

    # ------------------------------------------------------------------
    # Lock-step group batching (legacy / scan-cache fallback).
    # ------------------------------------------------------------------

    def _pad_prompts(self, prompts: list[list[int]]) -> np.ndarray:
        # left-pad to a common length (uniform-position cache layout)
        maxlen = max(len(p) for p in prompts)
        out = np.zeros((len(prompts), maxlen), np.int32)
        for i, p in enumerate(prompts):
            out[i, maxlen - len(p):] = p
        return out

    def _generate_lockstep(self, items, key) -> list[Result]:
        """items: [(submission order, Request)]; results align with items."""
        results: list[Result | None] = [None] * len(items)
        queue = [(seq, order, r) for seq, (order, r) in enumerate(items)]
        m = MetricsRegistry()
        t_start = self.clock.now()
        while queue:
            group = queue[: self.max_batch]
            queue = queue[self.max_batch:]
            self._generate_group(group, key, results, m)
        wall = self.clock.now() - t_start
        m.counter("generated_tokens").inc(
            sum(len(r.tokens) for r in results))
        self.last_metrics = m
        self.last_stats = EngineStats.from_registry(
            m, mode="lockstep", wall_s=wall,
            prefill_compiles=len(self._prefill_shapes))
        return results

    def _generate_group(self, group, key, results, m: MetricsRegistry):
        reqs = [r for _, _, r in group]
        prompts = self._pad_prompts([list(r.prompt) + list(r.done)
                                     for r in reqs])
        self._prefill_shapes.add(prompts.shape[1])
        batch = {"tokens": jnp.asarray(prompts),
                 **self._gather_extra([order for _, order, _ in group])}
        t0 = self.clock.now()
        logits, cache = self._prefill(self.params, batch)
        jax.block_until_ready(logits)
        t_pf = self.clock.now()
        prefill_ms = (t_pf - t0) * 1e3
        if self.tracer.enabled:
            self.tracer.complete(self.track, "prefill", t0, t_pf,
                                 group=len(reqs))
        remaining = [r.max_new_tokens - len(r.done) for r in reqs]
        max_new = max(remaining)
        if self._slot_capable:
            # uniform-position KV layout: the whole group decodes in step,
            # so the group's slowest member sets the write budget (scan/ring
            # cache families manage their own state length)
            self._check_budget(int(np.asarray(cache["pos"])), max_new,
                               [r.rid for r in reqs])
        temps = jnp.asarray([r.temperature for r in reqs], jnp.float32)
        rids = jnp.asarray([r.rid for r in reqs], np.int32)
        base_idx = np.asarray([len(r.done) for r in reqs], np.int32)
        toks = np.asarray(self._sample(logits, temps, key, rids,
                                       jnp.asarray(base_idx)))[:, None]
        outs = [[int(toks[i, 0])] for i in range(len(reqs))]
        t1 = self.clock.now()
        n_steps = 0
        for _ in range(max_new - 1):
            logits, cache = self._decode(self.params, cache,
                                         jnp.asarray(toks, jnp.int32))
            n_steps += 1
            toks = np.asarray(self._sample(
                logits, temps, key, rids,
                jnp.asarray(base_idx + n_steps)))[:, None]
            for i, r in enumerate(reqs):
                if len(outs[i]) < remaining[i]:
                    outs[i].append(int(toks[i, 0]))
        jax.block_until_ready(logits)
        t2 = self.clock.now()
        decode_ms = (t2 - t1) * 1e3 / max(n_steps, 1)
        if self.tracer.enabled and n_steps:
            self.tracer.complete(self.track, "decode_group", t1, t2,
                                 steps=n_steps, group=len(reqs))
        busy_total = 0
        # recompute busy slot-steps: request i is busy for its first
        # (remaining - 1) decode steps of this group
        for rem in remaining:
            busy_total += min(max(rem - 1, 0), max(n_steps, 0))
        m.counter("decode_steps").inc(n_steps)
        m.counter("busy_slot_steps").inc(busy_total)
        m.counter("offered_slot_steps").inc(self.max_batch * n_steps)
        for _ in reqs:
            m.histogram("ttft_ms").observe(prefill_ms)
        for i, (seq, _, r) in enumerate(group):
            results[seq] = Result(r.rid, list(r.done) + outs[i], prefill_ms,
                                  decode_ms)
            if remaining[i] > 1:
                m.histogram("tpot_ms").observe(decode_ms)
