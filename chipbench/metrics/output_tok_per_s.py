"""Output tokens that landed in the window, per second of window (host
clock)."""
from chipbench.stats import in_window


def read(run):
    n = sum(1 for r in run.reqs for t in r.token_times if in_window(run, t))
    return n / (run.t1 - run.t0)
