"""The profiler-trace reduction: on hand-made events with exact answers,
on a slice of a trace recorded on a TPU v5e, and on a trace recorded
here through the profiler itself (host spans)."""
import gzip
import json
import os

import pytest

from chipbench import tracing

RULES = {"decode": {"module": "jit__unknown",
                    "op": "paged_decode_attention_pallas"},
         "prefill": {"module": "jit__unknown",
                     "op": "paged_prefill_attention_pallas"}}
KERNELS = ["paged_decode_attention_pallas", "paged_prefill_attention_pallas"]
DATA = os.path.join(os.path.dirname(__file__), "data")


def hand_made():
    ops = [("while.5", 100, 400), ("paged_decode_attention_pallas.9", 150, 200),
           ("fusion.1", 250, 300), ("copy.2", 500, 600),
           ("paged_prefill_attention_pallas.3", 700, 750)]
    modules = [("jit__unknown(11)", 90, 410), ("jit__sample_rows(33)", 480, 610),
               ("jit__unknown(22)", 690, 760)]
    host = [("window", 0, 1000), ("step", 50, 420), ("wait", 420, 600),
            ("admit", 600, 700), ("step", 700, 1000)]
    return tracing.Trace(ops=ops, modules=modules, host=host)


def test_hand_made_trace():
    r = tracing.reduce(hand_made(), RULES, KERNELS)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(1000 * ns)
    assert r["busy_s"] == pytest.approx(450 * ns)     # nested ops once
    assert r["exe_s"] == pytest.approx({"decode": 320 * ns,
                                        "prefill": 70 * ns,
                                        "jit__sample_rows": 130 * ns})
    assert r["exe_launches"] == {"decode": 1, "prefill": 1,
                                 "jit__sample_rows": 1}
    assert r["kernel_s"] == pytest.approx(
        {"paged_decode_attention_pallas": 50 * ns,
         "paged_prefill_attention_pallas": 50 * ns})
    ops = dict(r["device_ops"])                       # self time
    assert ops["while.5"] == pytest.approx(200 * ns)
    assert ops["copy.2"] == pytest.approx(100 * ns)
    assert r["idle_gaps"][0] == ["step", pytest.approx(250 * ns)]
    assert sorted(n for n, _ in r["idle_gaps"][1:]) == ["admit", "step",
                                                        "wait"]
    assert r["idle_by_span"] == pytest.approx(
        {"step": 350 * ns, "wait": 100 * ns, "admit": 100 * ns})


def test_window_clips_events():
    tr = hand_made()
    tr.host[0] = ("window", 200, 650)
    r = tracing.reduce(tr, RULES, KERNELS)
    assert r["busy_s"] == pytest.approx((200 + 100) * 1e-9)
    assert r["exe_s"]["decode"] == pytest.approx(210 * 1e-9)
    assert "prefill" not in r["exe_s"]


def test_no_window_is_an_error():
    tr = hand_made()
    tr.host = tr.host[1:]
    with pytest.raises(ValueError):
        tracing.reduce(tr, RULES, KERNELS)


def recorded():
    """A slice of a ``--trace 1`` run on one v5e, with the executable
    rules and kernels of its configuration file."""
    with gzip.open(os.path.join(DATA, "v5e_trace_slice.json.gz"), "rt") as f:
        d = json.load(f)
    tr = tracing.Trace(**{k: [tuple(e) for e in d[k]]
                          for k in ("ops", "modules", "host")})
    return tr, d["rules"], d["kernels"]


def test_recorded_tpu_trace():
    tr, rules, kernels = recorded()
    r = tracing.reduce(tr, rules, kernels)
    assert 0 < r["busy_s"] <= r["window_s"]
    # every op's self time adds up to the busy union
    lo, hi = next((s, e) for n, s, e in tr.host if n == "window")
    clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in tr.ops
               if e > lo and s < hi]
    assert sum(tracing._self_times(clipped).values()) * 1e-9 == \
        pytest.approx(r["busy_s"], rel=1e-9)
    assert r["exe_launches"]["decode"] >= 1
    assert r["exe_s"]["decode"] <= r["window_s"]
    for k in kernels:
        assert r["kernel_s"].get(k, 0) <= r["exe_s"]["decode"] + \
            r["exe_s"].get("prefill", 0)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("step"):
            jnp.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation("wait"):
            pass
    jax.profiler.stop_trace()
    tr = tracing.load(tracing.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in tr.host]
    assert names.count("window") == 1
    assert "step" in names and "wait" in names
    r = tracing.reduce(tr, RULES, KERNELS)
    assert r["window_s"] > 0
