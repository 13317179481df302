"""The engine's phase spans (``telemetry.phase``): where they land in a
``jax.profiler`` trace of a paged and a dense session, their mirror on
an attached ``Tracer``, the garbage-collection spans, and that none of
it touches the tokens."""
import gc

import jax
import pytest

from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import FakeClock, Request, ServeEngine, Tracer
from repro.serving import telemetry
from repro.serving.telemetry import validate_lifecycle

#: the benchmark harness's own span names, which no program span may take
HARNESS = {"admit", "step", "drain", "wait", "window"}

SESSIONS = {
    # chunked prefill over two blocks, decode growing into a third
    "paged": ("qwen3-0.6b", dict(kv_layout="paged", block_size=8,
                                 cache_len=64)),
    # prefill on admit, recurrent state reset on release
    "dense": ("xlstm-350m", dict(kv_layout="dense", cache_len=64)),
}


@pytest.fixture(scope="module")
def models():
    out = {}
    for arch in {a for a, _ in SESSIONS.values()}:
        cfg = smoke_config(arch)
        model = build_model(cfg)
        out[arch] = (cfg, model, model.init(jax.random.key(0)))
    return out


def _engine(models, kind, **kw):
    arch, eng_kw = SESSIONS[kind]
    cfg, model, params = models[arch]
    return cfg, ServeEngine(model, params, max_batch=2, mode="continuous",
                            **eng_kw, **kw)


def _requests(vocab):
    return [Request([(3 * i + j) % vocab for j in range(12 + i)], 10 - 3 * i,
                    rid=i) for i in range(2)]


def _session(eng, vocab):
    """Admit two requests and step until both finish; the tokens."""
    got = {}
    eng.begin_session(on_token=lambda ev: got.setdefault(ev.rid, [])
                      .append(ev.token))
    for r in _requests(vocab):
        eng.session_admit(r, tag=r.rid)
    while eng.session_active:
        eng.session_step()
    eng.end_session()
    return got


def _profiled(fn, tmp_path):
    """Run ``fn`` under the profiler; its result and the host spans as
    (name, start_ns, end_ns, line)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, (p.name, k))
             for p in pd.planes if p.name.startswith("/host:")
             for k, line in enumerate(p.lines) for e in line.events]
    return out, spans


def _inside(spans, outer, name):
    n0, s0, e0, line = outer
    return sorted((s, e) for n, s, e, ln in spans
                  if n == name and ln == line and s0 <= s and e <= e0)


@pytest.mark.parametrize("kind", sorted(SESSIONS))
def test_profiler_trace_holds_engine_phases(models, kind, tmp_path):
    cfg, eng = _engine(models, kind)
    _, spans = _profiled(lambda: _session(eng, cfg.vocab_size), tmp_path)
    names = {n for n, _, _, _ in spans}
    assert not names & HARNESS
    steps = [x for x in spans if x[0] == "engine.step"]
    decoding = [x for x in steps if _inside(spans, x,
                                            "engine.decode_dispatch")]
    assert decoding
    for st in decoding:
        (d,) = _inside(spans, st, "engine.decode_dispatch")
        (w,) = _inside(spans, st, "engine.result_wait")
        (m,) = _inside(spans, st, "engine.emit")
        (sd,) = _inside(spans, st, "engine.sample_dispatch")
        assert d[1] <= sd[0] and sd[1] <= w[0] and w[1] <= m[0]
    # each request finishes, and its release runs inside the emit loop
    releases = [r for m in spans if m[0] == "engine.emit"
                for r in _inside(spans, m, "engine.release")]
    assert len(releases) == 2
    admits = [x for x in spans if x[0] == "engine.admit"]
    assert len(admits) == 2
    if kind == "paged":
        # admission installs; prefill and growth run inside steps
        prefills = [p for st in steps
                    for p in _inside(spans, st, "engine.prefill")]
        assert len(prefills) == 2
        grows = [g for st in steps for g in _inside(spans, st, "engine.grow")]
        # 12 + 13 prompt positions over blocks of 8, then decode past 16
        assert len(grows) >= 5
    else:
        for a in admits:
            assert len(_inside(spans, a, "engine.prefill")) == 1
        assert "engine.grow" not in names


@pytest.mark.parametrize("kind", sorted(SESSIONS))
def test_tokens_identical_with_profiler_on(models, kind, tmp_path):
    cfg, eng = _engine(models, kind)
    off = _session(eng, cfg.vocab_size)
    on, spans = _profiled(lambda: _session(eng, cfg.vocab_size), tmp_path)
    assert any(n == "engine.step" for n, _, _, _ in spans)
    assert on == off
    assert [len(off[r.rid]) for r in _requests(cfg.vocab_size)] == [10, 7]


def test_tracer_export_holds_the_phases(models, tmp_path):
    cfg, eng = _engine(models, "paged")
    tracer = Tracer()
    eng.set_tracer(tracer)
    _session(eng, cfg.vocab_size)
    events = tracer.events()
    validate_lifecycle(events)
    doc = tracer.chrome_trace()
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"engine.step", "engine.admit", "engine.prefill", "engine.grow",
            "engine.decode_dispatch", "engine.sample_dispatch",
            "engine.result_wait", "engine.emit", "engine.release"} <= names
    assert not names & {"device", "dispatch"}
    on_track = {e.name for e in events if e.track == eng.track}
    assert "engine.step" in on_track


def test_phase_records_on_the_tracer_clock():
    tracer = Tracer(clock=FakeClock(start=2.0, tick=0.5))
    with telemetry.phase("engine.prefill", tracer, "replica0", rid=3):
        with telemetry.phase("engine.grow", tracer, "replica0"):
            pass
    with telemetry.phase("engine.step", tracer, "replica0", step_num=7):
        pass
    with telemetry.phase("engine.emit"):      # no tracer: nothing recorded
        pass
    got = [(e.name, e.track, e.ts, e.dur, e.args) for e in tracer.events()]
    assert got == [("engine.grow", "replica0", 2.5, 0.5, {}),
                   ("engine.prefill", "replica0", 2.0, 1.5, {"rid": 3}),
                   ("engine.step", "replica0", 4.0, 0.5, {})]


def test_gc_spans_cover_generations_one_and_two(tmp_path):
    telemetry.install_gc_spans()
    telemetry.install_gc_spans()
    assert gc.callbacks.count(telemetry._gc_callback) == 1

    def collect():
        gc.collect(0)
        gc.collect(1)
        gc.collect(2)

    _, spans = _profiled(collect, tmp_path)
    names = [n for n, _, _, _ in spans if n.startswith("python.gc.")]
    assert set(names) == {"python.gc.gen1", "python.gc.gen2"}
