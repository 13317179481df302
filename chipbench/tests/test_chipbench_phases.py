"""The program's phase spans: the reduction on hand-made spans with exact
answers, on the recorded v5e slice (which predates them), and read from
a traced smoke run through the profiler itself."""
import gzip
import json
import os
import types

import pytest

from chipbench import harness, phases, tracing
from chipbench.tests import smoke

NS = 1e-9
DATA = os.path.join(os.path.dirname(__file__), "data")


def hand_made():
    """One harness ``step`` holding one engine step, an ``admit`` holding
    a dense admission's prefill, a collection on another thread, and the
    window's tail under no span at all."""
    ops = [(100, 300), (600, 700)]
    a, b = "main", "other-thread"
    host = [("window", 0, 1000, a),
            ("step", 0, 500, a),
            ("engine.step", 10, 490, a),
            ("engine.decode_dispatch", 20, 100, a),
            ("engine.sample_dispatch", 100, 110, a),
            ("engine.result_wait", 110, 320, a),
            ("engine.emit", 320, 480, a),
            ("python.gc.gen2", 330, 360, a),
            ("engine.release", 400, 450, a),
            ("admit", 500, 560, a),
            ("engine.admit", 505, 555, a),
            ("engine.prefill", 510, 550, a),
            ("step", 560, 950, a),
            ("python.gc.gen1", 720, 760, b)]
    return phases.Spans(ops=ops, host=host)


def test_hand_made_phases():
    r = phases.reduce(hand_made())
    assert r["window_s"] == pytest.approx(1000 * NS)
    assert r["idle_s"] == pytest.approx(700 * NS)
    got = {n: (p["count"], p["total_s"] / NS, p["self_s"] / NS)
           for n, p in r["phases"].items()}
    want = {"engine.step": (1, 480, 20),
            "engine.decode_dispatch": (1, 80, 80),
            "engine.sample_dispatch": (1, 10, 10),
            "engine.result_wait": (1, 210, 210),
            "engine.emit": (1, 160, 80),
            "python.gc.gen2": (1, 30, 30),
            "engine.release": (1, 50, 50),
            "engine.admit": (1, 50, 10),
            "engine.prefill": (1, 40, 40),
            "python.gc.gen1": (1, 40, 40)}
    assert got.keys() == want.keys()
    for n, w in want.items():
        assert got[n] == pytest.approx(w), n
    # the step's host time leaves out its wait for the device
    assert r["step_host_s"] == pytest.approx([270 * NS])
    assert r["decode_dispatch_s"] == pytest.approx([80 * NS])


def test_hand_made_idle_goes_to_the_innermost_span():
    r = phases.reduce(hand_made())
    idle = {n: d / NS for n, d in r["idle_by_phase"].items()}
    assert idle == pytest.approx({
        "step": 10 + 10 + 40 + 210,     # under no program span
        "step/engine.step": 20,
        "step/engine.decode_dispatch": 80,
        "step/engine.result_wait": 20,
        "step/engine.emit": 80,
        "step/python.gc.gen2": 30,
        "step/engine.release": 50,
        "admit": 10,
        "admit/engine.admit": 10,
        "admit/engine.prefill": 40,
        "step/python.gc.gen1": 40,      # another thread's span counts
        "other": 50})                   # under no span at all
    assert sum(idle.values()) == pytest.approx(700)
    assert r["idle_in_program"] == pytest.approx(370 / 700)
    # a gap takes the name of its largest piece
    assert [n for n, _ in r["idle_gaps"]] == [
        "step/engine.emit", "step", "step/engine.decode_dispatch"]
    assert [d / NS for _, d in r["idle_gaps"]] == pytest.approx(
        [300, 300, 100])
    assert "idle_in_program=52.857" in phases.summary(r)


def test_window_cuts_spans_and_drops_cut_steps():
    sp = hand_made()
    sp.host[0] = ("window", 200, 1000, "main")
    r = phases.reduce(sp)
    assert r["phases"]["engine.step"]["total_s"] == pytest.approx(290 * NS)
    assert "engine.decode_dispatch" not in r["phases"]
    assert r["step_host_s"] == [] and r["decode_dispatch_s"] == []
    sp.host = sp.host[1:]
    with pytest.raises(ValueError):
        phases.reduce(sp)


def recorded():
    with gzip.open(os.path.join(DATA, "v5e_trace_slice.json.gz"), "rt") as f:
        d = json.load(f)
    tr = tracing.Trace(**{k: [tuple(e) for e in d[k]]
                          for k in ("ops", "modules", "host")})
    return tr, d["rules"], d["kernels"]


def test_recorded_slice_reads_as_before():
    """The slice holds no program span: the existing reduction and its
    readers read what they read before this module existed, and the
    phase reduction names every gap by the harness span, as they do."""
    tr, rules, kernels = recorded()
    r = tracing.reduce(tr, rules, kernels)
    assert r["window_s"] == pytest.approx(0.308668284, rel=1e-12)
    assert r["busy_s"] == pytest.approx(0.308652035, rel=1e-12)
    assert r["exe_s"] == pytest.approx({"decode": 0.2833202,
                                        "prefill": 0.02533692}, rel=1e-12)
    assert r["exe_launches"] == {"decode": 2, "prefill": 2}
    assert r["kernel_s"] == pytest.approx(
        {"paged_decode_attention_pallas": 0.240723203,
         "paged_prefill_attention_pallas": 0.007804291}, rel=1e-12)
    assert r["kernel_launches"] == {"paged_decode_attention_pallas": 29,
                                    "paged_prefill_attention_pallas": 29}
    assert r["device_ops"][0] == ["paged_decode_attention_pallas.9",
                                  pytest.approx(0.240723203, rel=1e-12)]
    assert r["idle_by_span"] == pytest.approx(
        {"step": 5.932e-06, "admit": 1.0317e-05}, rel=1e-9)
    run = types.SimpleNamespace(trace=r)
    assert harness.read_metric("idle_share.batch", run) == pytest.approx(
        100 * (1 - 0.308652035 / 0.308668284), rel=1e-9)

    p = phases.reduce(phases.Spans(
        ops=[(s, e) for _, s, e in tr.ops],
        host=[(n, s, e, 0) for n, s, e in tr.host]))
    assert p["phases"] == {} and p["step_host_s"] == []
    assert p["idle_in_program"] == 0
    assert p["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"],
                                        rel=1e-9)
    assert [n for n, _ in p["idle_gaps"]] == [n for n, _ in r["idle_gaps"]]


NEW = ("engine_host_ms_per_step.batch", "decode_dispatch_ms.batch")


def test_readers_report_nothing_without_program_spans(tmp_path,
                                                      monkeypatch):
    """A program that opens no phase spans (an older commit) gives a
    traced run whose line leaves the new metrics out; an untraced run
    reads nothing."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("step"):
            jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    for name in NEW:
        assert harness.read_metric(name, types.SimpleNamespace(
            trace={})) is None
        assert harness.read_metric(name, types.SimpleNamespace(
            trace=None)) is None


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_traced_smoke_run_reads_the_phases(family, tmp_path, monkeypatch,
                                           capsys):
    """The harness's own traced window on a smoke engine: both readers
    find the engine's steps in the profile the run wrote, and the run
    logs one ``[phases]`` line."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    cell = smoke.cell(family, "decode-batch")
    s = harness.setup(cell, 5, compile_cache=False)
    view = harness.serve(cell, s, seed=5, seconds=1.0, trace=True,
                         t_process=0.0)
    got = {n: harness.read_metric(n, view) for n in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    err = capsys.readouterr().err
    assert err.count("[phases]") == 1
    assert "engine.step" in err and "engine.result_wait" in err
    red = phases.for_run(view)
    assert 0 < red["idle_in_program"] <= 1
    steps = red["phases"]["engine.step"]
    assert steps["count"] >= len(red["step_host_s"]) > 0
