"""Sweeps that size a cell, run once when the cell is defined.

  python3 chipbench/sweep.py --workload <open-loop cell> \\
      --rates 1,1.5,2,2.5,3,3.5 --seconds 30 --seed 1
  python3 chipbench/sweep.py --workload qwen3-0.6b.decode-batch \\
      --engines '[{"max_batch": 8, "n_blocks": 1665}, {"max_batch": 16}]' \\
      --seconds 20 --seed 1

One process.  ``--rates``: one set-up, then one window per rate on the
same engine; per rate it prints the arrivals, how many had their first
token by the window's end, the tokens completed per second, the TTFT
median and 90th percentile, and the median queue wait.  The knee is the
highest rate at which the first tokens keep pace with the arrivals and
the TTFT tail does not grow with the window; it goes into
``cells/<cell>.json``.

``--engines``: for each set of ``ServeEngine`` arguments laid over the
configuration's, in the order given (put them in growing size: a
process's peak memory never falls), a set-up and one window of the
cell's own traffic; per engine it prints the tokens per second, the
95th percentile gap between tokens, the occupancy of the slots, the
median decode launch, and the device memory in use and at its peak.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def rates(cell, args):
    from chipbench import harness, stats
    s = harness.setup(cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(harness.traffic_of(cell), rate_per_s=rate,
                       drain_s=args.drain)
        v = harness.serve(cell, s, seed=args.seed, seconds=args.seconds,
                          trace=False, t_process=T_PROCESS, traffic=traffic)
        arrived = [r for r in v.reqs if v.t0 <= r.arrival < v.t1]
        on_time = sum(1 for r in arrived if r.token_times
                      and r.token_times[0] < v.t1)
        ttft = stats.ttft_ms(v)
        waits = [1e3 * (r.admitted - r.arrival) for r in arrived
                 if r.admitted is not None]
        print(json.dumps({
            "rate_per_s": rate, "arrived": len(arrived),
            "first_token_in_window": on_time,
            "tokens_per_s": harness.read_metric("output_tok_per_s", v),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "queue_wait_p50_ms": stats.percentile(waits, 50),
            "itl_p95_ms": stats.percentile(stats.itl_ms(v), 95)}),
            flush=True)


def engines(cell, args):
    import jax

    from chipbench import harness, stats
    for over in json.loads(args.engines):
        c = dataclasses.replace(cell, config=dict(
            cell.config, engine=dict(cell.config["engine"], **over)))
        t = time.perf_counter()
        s = harness.setup(c, args.seed)
        v = harness.serve(c, s, seed=args.seed, seconds=args.seconds,
                          trace=False, t_process=t)
        steps = [b - a for (a, _), (b, _) in zip(v.decode_log,
                                                 v.decode_log[1:])
                 if stats.in_window(v, a) and stats.in_window(v, b)]
        mem = jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "engine": c.config["engine"], "setup_s": v.setup_s,
            "tokens_per_s": harness.read_metric("output_tok_per_s", v),
            "itl_p95_ms": stats.percentile(stats.itl_ms(v), 95),
            "occupancy": harness.read_metric("occupancy.batch", v),
            "launch_p50_ms": 1e3 * (stats.percentile(steps, 50) or 0),
            "bytes_in_use": mem.get("bytes_in_use"),
            "peak_bytes_in_use": mem.get("peak_bytes_in_use")}),
            flush=True)
        del s, v
        gc.collect()


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates")
    p.add_argument("--engines")
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--drain", type=float, default=10.0)
    args = p.parse_args(argv)
    if (args.rates is None) == (args.engines is None):
        p.error("give one of --rates and --engines")
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    (rates if args.rates else engines)(cell, args)


if __name__ == "__main__":
    main()
