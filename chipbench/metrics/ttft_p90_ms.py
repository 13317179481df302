"""90th percentile of time to first token (ms), same samples as
``ttft_p50_ms`` (host clock)."""
from chipbench.stats import percentile, ttft_ms


def read(run):
    return percentile(ttft_ms(run), 90)
