"""Median time to first token (ms): scheduled arrival to the first
token, over every request that arrived in the window (host clock)."""
from chipbench.stats import percentile, ttft_ms


def read(run):
    return percentile(ttft_ms(run), 50)
