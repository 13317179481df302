"""Decode launches' share (%) of the HBM roofline: the bytes a launch
must move for its live rows (weights, live KV or recurrent state,
``chipbench.counting``) at peak bandwidth, over the device time of a
decode launch (profiler trace)."""
from chipbench import counting
from chipbench.stats import decode_launches


def read(run):
    if run.trace is None:
        return None
    t = run.trace["exe_s"].get("decode")
    n = run.trace["exe_launches"].get("decode")
    launches = decode_launches(run)
    if not (t and n and launches):
        return None
    need = sum(counting.decode_step_bytes(run.model, kv) for kv in launches)
    per_launch = need / len(launches) / run.peak["hbm_bytes_per_s"]
    return 100.0 * per_launch * n / t
