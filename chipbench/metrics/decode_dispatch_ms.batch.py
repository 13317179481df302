"""Median duration (ms) of the window's ``engine.decode_dispatch`` spans:
the host's call into the jitted decode step, its token upload included,
until the launch is enqueued (the program's spans in the profiler trace,
``chipbench.phases``)."""
from chipbench import phases
from chipbench.stats import percentile


def read(run):
    red = phases.for_run(run)
    if red is None or not red["decode_dispatch_s"]:
        return None
    return 1e3 * percentile(red["decode_dispatch_s"], 50)
