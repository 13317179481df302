"""Serving launcher: continuous-batching generation over the Model API.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --prompts "1 2 3" "4 5" --max-new 16

Every cell of the scheduler matrix (see docs/serving.md) is reachable
from here: ``--mode`` picks the scheduler (continuous/lockstep),
``--kv-layout`` the cache layout (dense/paged; scan families — ssm,
hybrid, encdec — serve continuous on dense), ``--admission`` the paged
admission policy (reserve/overcommit), ``--bucket`` the prefill
bucketing, and ``--replicas N`` (N > 1) serves through a multi-replica
cluster instead: N narrow engines behind a ``--router`` policy — sharing
one KV block pool with preemption under pool pressure for paged
families, per-replica slot state for scan families (see
repro.serving.cluster).  ``--driver threaded`` steps the cluster's
replicas on worker threads (overlapped dispatch, byte-identical
tokens); ``--stream`` prints every token the moment it is sampled
through the streaming generator API instead of waiting for full
completions.  ``--policy`` picks the scheduling policy
(fifo/priority/edf/slo_adaptive) and ``--slo-ttft``/``--slo-tpot``
attach per-request latency budgets, printed back as SLO attainment.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax

from ..compile_cache import enable_compile_cache
from ..configs import get_config, list_archs, smoke_config
from ..models import build_model
from ..serving import (DRIVERS, POLICIES, ROUTER_POLICIES, Attributor,
                       ClusterEngine, Request, ServeEngine, Tracer)


def main(argv=None):
    """Serve ``argv`` (default: the command line).  Returns the engine
    and each request's generated tokens, ``{rid: tokens}``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompts", nargs="+", default=["1 2 3", "7 8"],
                    help="space-separated token ids per prompt")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "continuous", "lockstep"])
    ap.add_argument("--kv-layout", default="dense",
                    choices=["dense", "paged"],
                    help="slot cache layout (scan families serve on "
                         "dense; paged needs transformer block hooks)")
    ap.add_argument("--admission", default=None,
                    choices=["reserve", "overcommit"],
                    help="paged admission: worst-case reservation vs "
                         "first-chunk overcommit (default: reserve for a "
                         "single engine, overcommit + preemption for a "
                         "cluster)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged layout: KV positions per pool block")
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="paged layout: pool size (default: the dense "
                         "footprint, max_batch * cache_len positions)")
    ap.add_argument("--bucket", default=None,
                    help="prefill length bucketing: 'pow2' or an integer "
                         "pad-to-multiple (default: exact lengths)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged layout: admit shared prompt prefixes by "
                         "referencing resident pool blocks (refcounted, "
                         "copy-on-write; see docs/serving.md)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a cluster of this many engine "
                         "replicas sharing one KV pool (--max-batch is the "
                         "cluster's total slot budget)")
    ap.add_argument("--router", default="round_robin",
                    choices=list(ROUTER_POLICIES),
                    help="cluster request-routing policy (--replicas > 1)")
    ap.add_argument("--driver", default="sequential",
                    choices=list(DRIVERS),
                    help="cluster step driver (--replicas > 1): "
                         "'sequential' steps replicas in one "
                         "deterministic loop, 'threaded' overlaps them "
                         "on worker threads (same tokens either way)")
    ap.add_argument("--policy", default="fifo", choices=list(POLICIES),
                    help="scheduling policy: fifo (legacy order), "
                         "priority, edf (earliest TTFT deadline first), "
                         "or slo_adaptive (EDF + deadline-protected "
                         "victim picks + slack routing + starvation "
                         "preemption; see docs/serving.md)")
    ap.add_argument("--slo-ttft", type=float, default=None, metavar="MS",
                    help="per-request first-token latency budget in ms "
                         "(applied to every prompt; default: "
                         "best-effort)")
    ap.add_argument("--slo-tpot", type=float, default=None, metavar="MS",
                    help="per-request decode budget in ms per output "
                         "token (default: best-effort)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are sampled (the "
                         "streaming generator API) instead of waiting "
                         "for each request to finish")
    ap.add_argument("--hysteresis", type=int, default=4,
                    help="cluster anti-thrash guard: a preempted request "
                         "is not re-admitted for this many scheduler "
                         "rounds (--replicas > 1)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record request-lifecycle telemetry and write a "
                         "Chrome-trace-event JSON (open at "
                         "https://ui.perfetto.dev; see "
                         "docs/observability.md)")
    ap.add_argument("--metrics", nargs="?", const=True, default=None,
                    metavar="OUT.json",
                    help="print the metrics-registry summary (p50/p90/p99 "
                         "TTFT+TPOT, queue age, occupancy/pool timelines); "
                         "with a file argument, also write the stats + "
                         "registry snapshot as JSON so serve runs feed "
                         "tools/bench_compare.py like the benches do")
    ap.add_argument("--attribution", action="store_true",
                    help="attach a utilization attributor: roofline-joined "
                         "per-step accounting (achieved FLOP/s vs peak, "
                         "bottleneck verdicts, fu_utilization; see "
                         "docs/observability.md)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))
    bucket = (int(args.bucket) if args.bucket and args.bucket != "pow2"
              else args.bucket)
    # per-request side inputs the tokenized --prompts cannot carry: stub
    # rows, one per prompt (vlm patch embeddings; encdec's conv/mel
    # frontend is a stub by assignment, so frames are synthesized too)
    extra = None
    if cfg.family == "vlm":
        import jax.numpy as jnp
        extra = {"patches": jnp.zeros(
            (len(args.prompts), cfg.n_patches, cfg.patch_embed_dim),
            jnp.bfloat16)}
    elif cfg.family == "encdec":
        import jax.numpy as jnp
        extra = {"frames": jnp.zeros((len(args.prompts), 16, cfg.d_model),
                                     jnp.bfloat16)}
    tracer = Tracer() if (args.trace or args.metrics) else None
    attribution = Attributor() if args.attribution else None
    if args.replicas > 1:
        if args.mode != "auto" or args.kv_layout != "dense":
            ap.error("--replicas > 1 always serves continuous and "
                     "resolves the KV layout per family (paged for "
                     "transformer families, dense slot state for scan "
                     "families); drop --mode/--kv-layout")
        eng = ClusterEngine(model, params, replicas=args.replicas,
                            total_slots=args.max_batch,
                            cache_len=args.cache_len, router=args.router,
                            extra_inputs=extra,
                            block_size=args.block_size,
                            n_blocks=args.n_blocks, bucket=bucket,
                            admission=args.admission or "overcommit",
                            preempt_hysteresis=args.hysteresis,
                            prefix_cache=args.prefix_cache,
                            driver=args.driver, policy=args.policy,
                            tracer=tracer, attribution=attribution)
    else:
        if args.driver != "sequential":
            ap.error("--driver threaded needs a cluster (--replicas > 1);"
                     " a single engine has nothing to overlap")
        eng = ServeEngine(model, params, max_batch=args.max_batch,
                          cache_len=args.cache_len, mode=args.mode,
                          extra_inputs=extra,
                          kv_layout=args.kv_layout,
                          block_size=args.block_size,
                          n_blocks=args.n_blocks, bucket=bucket,
                          admission=args.admission or "reserve",
                          prefix_cache=args.prefix_cache,
                          policy=args.policy,
                          tracer=tracer, attribution=attribution)
    reqs = [Request([int(t) % cfg.vocab_size for t in p.split()],
                    args.max_new, args.temperature, rid=i,
                    slo_ttft_ms=args.slo_ttft, slo_tpot_ms=args.slo_tpot)
            for i, p in enumerate(args.prompts)]
    if args.stream:
        if args.mode == "lockstep":
            ap.error("--stream needs the continuous scheduler (tokens "
                     "only exist one request at a time under lockstep)")
        # the deployment-shaped loop: consume the generator as tokens
        # land, print completions as their final token arrives
        streamed: dict[int, list[int]] = {}
        for ev in eng.stream(reqs):
            streamed.setdefault(ev.rid, []).append(ev.token)
            print(f"[stream] rid={ev.rid} i={ev.index} token={ev.token}"
                  f"{' (final)' if ev.final else ''}")
        for rid in sorted(streamed):
            print(f"[serve] rid={rid} tokens={streamed[rid]}")
        tokens = streamed
    else:
        tokens = {}
        for r in eng.generate(reqs):
            print(f"[serve] rid={r.rid} ttft={r.prefill_ms:.1f}ms "
                  f"decode={r.decode_ms_per_tok:.1f}ms/tok "
                  f"tokens={r.tokens}")
            tokens[r.rid] = r.tokens
    s = eng.last_stats
    paged = (f" block_util_peak={s.block_util_peak:.2f}"
             f" preempted={s.preempted} requeued={s.requeued}"
             if s.kv_layout == "paged" else "")
    if args.prefix_cache:
        paged += (f" prefix_hits={s.prefix_hits}"
                  f" prefix_reused={s.prefix_tokens_reused}")
    cluster = f" router={s.router_policy}" if s.router_policy else ""
    if args.slo_ttft is not None or args.slo_tpot is not None:
        cluster += (f" policy={s.sched_policy}"
                    f" slo_attainment={s.slo_attainment:.2f}"
                    f" (ttft {s.slo_ttft_attained}/{s.slo_ttft_total}"
                    f" tpot {s.slo_tpot_attained}/{s.slo_tpot_total})")
    print(f"[serve] mode={s.mode} kv={s.kv_layout} "
          f"tokens/s={s.tokens_per_s:.1f} "
          f"generated={s.generated_tokens} steps={s.decode_steps} "
          f"occupancy={s.occupancy:.2f} ttft_mean={s.ttft_ms_mean:.1f}ms "
          f"prefill_compiles={s.prefill_compiles}{paged}{cluster}")
    if args.metrics:
        print(f"[metrics] ttft_ms p50={s.ttft_ms_p50:.1f} "
              f"p90={s.ttft_ms_p90:.1f} p99={s.ttft_ms_p99:.1f} "
              f"mean={s.ttft_ms_mean:.1f}")
        print(f"[metrics] tpot_ms p50={s.tpot_ms_p50:.2f} "
              f"p90={s.tpot_ms_p90:.2f} p99={s.tpot_ms_p99:.2f} "
              f"mean={s.tpot_ms_mean:.2f}")
        print(f"[metrics] queue_age_ms mean={s.queue_age_ms_mean:.1f} "
              f"p99={s.queue_age_ms_p99:.1f}")
        if args.attribution:
            print(f"[metrics] attribution fu_utilization="
                  f"{s.fu_utilization:.3e} "
                  f"achieved_flops/s={s.achieved_flops_per_s:.3e} "
                  f"achieved_bytes/s={s.achieved_bytes_per_s:.3e} "
                  f"decode_ai={s.decode_ai:.2f} ridge={s.ridge_ai:.2f} "
                  f"bottleneck={s.bottleneck or '-'} "
                  f"prefill={s.prefill_bottleneck or '-'} "
                  f"verdicts={s.verdict_counts}")
        for name, val in sorted(eng.last_metrics.snapshot().items()):
            print(f"[metrics] {name}={val}")
        if isinstance(args.metrics, str):
            # machine-readable twin of the prints above: the stats view
            # plus the raw registry snapshot, in the shape
            # tools/bench_compare.py gates (stats.* / metrics.* keys)
            with open(args.metrics, "w") as f:
                json.dump({"bench": "repro.launch.serve",
                           "stats": dataclasses.asdict(s),
                           "metrics": eng.last_metrics.snapshot()},
                          f, indent=2, sort_keys=True, default=str)
            print(f"[metrics] wrote {args.metrics}")
    if args.trace:
        n = tracer.export(args.trace)
        print(f"[trace] wrote {n} events to {args.trace} "
              "(open at https://ui.perfetto.dev)")
    return eng, tokens


if __name__ == "__main__":
    enable_compile_cache()
    main()
