"""One run of one cell: build, warm up, serve the window, read, check.

Everything is found by name.  ``BENCHMARK.json`` names the cell's
configuration (``configs/<name>.json``: the program's ``ModelConfig``
fields under ``"model"``, the ``ServeEngine`` arguments under
``"engine"``, the reference family and the trace's executable names) and
traffic mix (``traffic/<name>.json``, whose ``"loop"`` names the
generator module).  ``cells/<cell>.json`` holds what belongs to the pair:
the knee its rate is set from and its correctness limits.  Each metric
the cell reports is ``metrics/<name>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import time
from pathlib import Path

import jax

from . import check, tracing
from .client import Client, Req
from .generators import lengths

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent
#: fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = PKG / ".traces"


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    cell: dict       # cells/<name>.json
    e2e: list        # metric entries of BENCHMARK.json for this cell
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(entries):
        return [m for m in entries
                if name in m.get("workloads", [name])]

    return Cell(name=name, chips=w["chips"],
                config=_json(root / conf["file"]),
                traffic=_json(PKG / "traffic" / f"{w['traffic']}.json"),
                cell=_json(PKG / "cells" / f"{name}.json"),
                e2e=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def read_metric(name: str, run) -> float | None:
    """Load ``metrics/<name>.py`` and read it off ``run``."""
    path = PKG / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


class CompileCounter:
    """Executables compiled, or loaded from the compile cache, while
    ``on`` is set: JAX times each with one ``backend_compile_duration``
    event (``jax.monitoring``)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, secs, **kw):
        if self.on and name == self.EVENT:
            self.n += 1


class Window:
    """Opens and closes the measured window: the compile count, the
    slot-step counters, and (traced runs) the profiler."""

    def __init__(self, engine, counter: CompileCounter, trace_dir):
        self.engine = engine
        self.counter = counter
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None
        self.steps0 = self.steps1 = (0, 0)
        self._span = None

    def start(self) -> float:
        if self.trace_dir is not None:
            # host spans (TraceMe) yes, a span per Python call no
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("window")
            self._span.__enter__()
        self.steps0 = self.engine.session_slot_steps()
        self.counter.on = True
        self.t0 = time.perf_counter()
        return self.t0

    def end(self) -> None:
        if self.t0 is None or self.t1 is not None:
            return
        self.t1 = time.perf_counter()
        self.counter.on = False
        self.steps1 = self.engine.session_slot_steps()
        if self._span is not None:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()


@dataclasses.dataclass
class RunView:
    """What a metric reader sees of one run."""
    model: dict
    config: dict
    traffic: dict
    t0: float
    t1: float
    setup_s: float
    reqs: list
    decode_log: list
    prefill_log: list
    slot_steps: tuple
    compiles_in_window: int
    device_kind: str
    trace: dict | None = None

    @property
    def peak(self) -> dict:
        """The chip's peaks (``peaks.json``); an error on a device the
        table does not hold, such as the CPU."""
        from . import counting
        return counting.peaks(self.device_kind)


def _engine_parts():
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.models.layers import PT
    from repro.serving import ServeEngine
    return ModelConfig, build_model, PT, ServeEngine


def warm_up(engine, prompt_lens: list[int], vocab: int) -> None:
    """Serve one short request per prompt length the cell can draw (one
    length where the engine prefills in fixed chunks), concurrently, so
    that every executable of the window is compiled or loaded first."""
    if engine.kv_layout == "paged":
        prompt_lens = prompt_lens[:1] * min(2, engine.max_batch)
    drv = Client(engine, traced=False)
    drv.begin()
    try:
        queue = [Req(rid=i, prompt=[(7 * i + 3) % vocab] * n, max_new=3,
                     arrival=0.0) for i, n in enumerate(prompt_lens)]
        for r in queue:
            drv.add(r)
        while queue or drv.live:
            while queue and drv.can_admit(queue[0]):
                drv.admit(queue.pop(0))
            if engine.session_active:
                drv.step()
    finally:
        drv.abort()


def device_info() -> dict:
    devs = jax.devices()
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


@dataclasses.dataclass
class Setup:
    """A built and warmed-up engine with its weights."""
    params: object
    engine: object
    gen: object          # the traffic's generator module
    counter: CompileCounter
    device: dict


def traffic_of(cell: Cell) -> dict:
    """The mix's parameters with the cell's own (its rate) over them."""
    return {**cell.traffic, **cell.cell.get("traffic", {})}


def setup(cell: Cell, seed: int, counter: CompileCounter | None = None,
          *, compile_cache: bool = True) -> Setup:
    """Compile cache, weights from ``seed``, engine, warm-up."""
    if compile_cache:
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = counter or CompileCounter()
    ModelConfig, build_model, PT, ServeEngine = _engine_parts()
    from .weights import make_weights
    mconf = cell.config["model"]
    model = build_model(ModelConfig(**mconf))
    params = make_weights(model.templates, seed,
                          lambda x: isinstance(x, PT))
    jax.block_until_ready(params)
    engine = ServeEngine(model, params, **cell.config["engine"])
    gen = importlib.import_module(
        f"chipbench.generators.{cell.traffic['loop']}")
    warm_up(engine, lengths.support(cell.traffic["prompt_len"]),
            mconf["vocab_size"])
    return Setup(params, engine, gen, counter,
                 device_info())


def serve(cell: Cell, s: Setup, *, seed: int, seconds: float, trace: bool,
          t_process: float, traffic: dict | None = None) -> RunView:
    """The window (and the drain after it) on the built engine."""
    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = Window(s.engine, s.counter, trace_dir)
    drv = Client(s.engine, traced=trace)
    s.counter.n = 0
    s.gen.run(traffic or traffic_of(cell), drv, seed=seed, seconds=seconds,
              vocab=cell.config["model"]["vocab_size"],
              max_batch=s.engine.max_batch, window=window)
    t0 = window.t0
    busy, offered = (a - b for a, b in zip(window.steps1, window.steps0))
    view = RunView(
        model=cell.config["model"], config=cell.config,
        traffic=traffic or traffic_of(cell), t0=t0, t1=t0 + seconds,
        setup_s=t0 - t_process, reqs=list(drv.reqs.values()),
        decode_log=drv.decode_log, prefill_log=drv.prefill_log,
        slot_steps=(busy, offered), compiles_in_window=s.counter.n,
        device_kind=s.device["kind"])
    if trace:
        tr = tracing.load(tracing.find_xplane(str(trace_dir)))
        view.trace = tracing.reduce(tr, cell.config["executables"],
                                    cell.config.get("kernels", []))
    return view


def attempted_failed(view: RunView) -> tuple[int, int]:
    """Open loop: requests that arrived in the window, and those of them
    with no first token by the end of the drain.  Closed loop: requests
    that received a token in the window (none can fail short of an
    error, which ends the run)."""
    if view.traffic["loop"] == "open_loop":
        arrived = [r for r in view.reqs if view.t0 <= r.arrival < view.t1]
        return len(arrived), sum(1 for r in arrived if not r.tokens)
    return sum(1 for r in view.reqs
               if any(view.t0 <= t < view.t1 for t in r.token_times)), 0


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process: float, log=print, compile_cache: bool = True) -> dict:
    """The run's result object (the last line of standard output)."""
    s = setup(cell, seed, compile_cache=compile_cache)
    view = serve(cell, s, seed=seed, seconds=seconds, trace=trace,
                 t_process=t_process)
    dev = dict(s.device, memory_peak_bytes=peak_bytes())
    attempted, failed = attempted_failed(view)
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": dev}
    lateness = sorted(r.enqueued - r.arrival for r in view.reqs)
    log(f"[run] window {seconds}s setup_s={view.setup_s:.3f} "
        f"requests={len(view.reqs)} attempted={attempted} failed={failed} "
        f"compiles_in_window={view.compiles_in_window} "
        f"generator lateness p50/max ms="
        f"{1e3 * lateness[len(lateness) // 2] if lateness else 0:.3f}/"
        f"{1e3 * lateness[-1] if lateness else 0:.3f}")
    if trace:
        red = view.trace
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log(f"[trace] busy_s={red['busy_s']} window_s={red['window_s']} "
            f"exe_s={red['exe_s']} exe_launches={red['exe_launches']} "
            f"kernel_s={red['kernel_s']} idle_by_span={red['idle_by_span']}")
    for m in cell.per_layer if trace else cell.e2e:
        v = read_metric(m["name"], view)
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    # the program's state goes before the reference runs
    picked = check.sample(view.reqs, seed, cell.cell["sample_requests"])
    params = s.params
    del s, view
    gc.collect()
    ref = check.Reference(cell.config["reference"], cell.config["model"],
                          params)
    t_ref = time.perf_counter()
    gaps = check.served_gaps(ref, picked)
    n_tok = sum(len(r.tokens) for r in picked)
    ok, checks = check.judge(gaps, n_tok, cell.cell)
    log(f"[check] requests={len(picked)} tokens={n_tok} "
        f"gaps={[round(g, 6) for g in gaps]} "
        f"reference_s={time.perf_counter() - t_ref:.3f}")
    result["correct"] = bool(ok and failed == 0)
    result["checks"] = checks
    return result
