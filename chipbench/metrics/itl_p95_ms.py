"""95th percentile of the gap between consecutive tokens of a request
(ms), over every gap whose later token landed in the window (host
clock)."""
from chipbench.stats import itl_ms, percentile


def read(run):
    return percentile(itl_ms(run), 95)
