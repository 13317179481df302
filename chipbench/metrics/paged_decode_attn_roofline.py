"""The Pallas paged decode attention kernel's share (%) of its roofline:
per layer and launch, the larger of its operations over peak FLOP/s and
its bytes (live KV blocks, query, output) over peak bandwidth
(``chipbench.counting``), over the kernel's device time (profiler
trace)."""
from chipbench import counting
from chipbench.stats import decode_launches

KERNEL = "paged_decode_attention_pallas"


def read(run):
    if run.trace is None:
        return None
    t = run.trace["kernel_s"].get(KERNEL)
    calls = run.trace["kernel_launches"].get(KERNEL)
    launches = decode_launches(run)
    if not (t and calls and launches):
        return None
    bs = run.config["engine"]["block_size"]
    ideal = sum(counting.paged_attn_call(run.model, kv, bs, run.peak)
                for kv in launches) / len(launches)
    return 100.0 * ideal * calls / t
