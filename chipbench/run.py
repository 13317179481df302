"""Run one benchmark cell once on the chip.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

From the root of a checkout.  Refuses to run (non-zero exit, no result)
unless JAX's devices are TPUs, at least as many as the cell asks for.
Set-up (device start, weights from the seed, engine, warm-up of every
shape the cell's traffic uses) is timed from process start to the
window's opening.  The window serves the cell's traffic for ``--seconds``;
with ``--trace 1`` the profiler records it and the per-layer metrics are
reported, otherwise the end-to-end ones.  Then the tokens served to a
sample of the requests, finished or still live, are compared with the
float32 reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit; the last lines of standard error repeat the checks.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        sys.exit("run.py: --seed must be >= 0")
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        sys.exit(f"run.py: cell {cell.name} needs {cell.chips} TPU chip(s); "
                 f"JAX found {len(devs)} device(s) of platform "
                 f"{devs[0].platform!r} ({devs[0].device_kind!r})")

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_process=T_PROCESS,
                              log=log)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} {c['rule']} {c['limit']} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
