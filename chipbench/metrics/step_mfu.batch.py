"""Whole step's share (%) of the chip's peak FLOP/s: the operations the
window's prompt and output tokens need (from the configuration's shapes,
``chipbench.counting``), per second of window, over the peak."""
from chipbench import counting
from chipbench.stats import decode_launches, prefilled


def read(run):
    m = run.model
    flops = sum(counting.prefill_flops(m, len(r.prompt))
                for r in prefilled(run))
    flops += sum(counting.token_flops(m, n)
                 for kv in decode_launches(run) for n in kv)
    if not flops:
        return None
    return 100.0 * flops / (run.t1 - run.t0) / run.peak["flops_per_s"]
