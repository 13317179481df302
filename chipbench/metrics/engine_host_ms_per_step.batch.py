"""Median host time (ms) of the window's engine steps: each
``engine.step`` span less its ``engine.result_wait`` child, the wait for
the device's tokens (the program's spans in the profiler trace,
``chipbench.phases``)."""
from chipbench import phases
from chipbench.stats import percentile


def read(run):
    red = phases.for_run(run)
    if red is None or not red["step_host_s"]:
        return None
    return 1e3 * percentile(red["step_host_s"], 50)
