"""The entry point's refusals, and a cell, configuration, mix and metric
that exist only as new files are found by name."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import harness

ROOT = str(harness.ROOT)


def _run(args, cwd, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=300)


def test_run_refuses_a_host_without_a_tpu():
    p = _run(["chipbench/run.py", "--workload", "qwen3-0.6b.decode-batch",
              "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
             ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".traces"))
    p = _run(["chipbench/run.py", "--workload", "qwen3-0.6b.decode-batch",
              "--seed", "1", "--seconds", "1", "--trace", "0"], tmp_path,
             env={"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


NEW_METRIC = '''"""Requests that arrived in the window (host clock)."""
from chipbench.stats import in_window


def read(run):
    return sum(1 for r in run.reqs if in_window(run, r.arrival))
'''


def test_new_cell_from_new_files_only(tmp_path):
    """Copy the benchmark, add a configuration, a mix, a cell file and a
    metric as new files and entries, and run the new cell (device check
    skipped, smoke widths) with no existing file edited."""
    from chipbench.tests import smoke
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".traces"))
    pkg = tmp_path / "chipbench"
    before = {p: p.read_bytes() for p in pkg.rglob("*") if p.is_file()}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    conf = json.load(open(pkg / "configs" / "qwen3-0.6b.json"))
    conf.update(name="tiny-dense", model=smoke.MODELS["dense"],
                engine=smoke.ENGINES["dense"])
    (pkg / "configs" / "tiny-dense.json").write_text(json.dumps(conf))
    (pkg / "traffic" / "trickle.json").write_text(json.dumps(
        dict(smoke.TRAFFIC["chat"], rate_per_s=3.0)))
    (pkg / "cells" / "tiny-dense.trickle.json").write_text(json.dumps(
        {"sample_requests": 2, "logit_gap": 0.01, "compared_tokens": 4}))
    (pkg / "metrics" / "arrivals.trickle.py").write_text(NEW_METRIC)
    bench["configs"].append({"name": "tiny-dense", "source": "test",
                             "file": "chipbench/configs/tiny-dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-dense.trickle",
                               "config": "tiny-dense", "traffic": "trickle",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "arrivals.trickle", "unit": "count",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["tiny-dense.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import sys, json, time; sys.path[:0] = [%r, %r]\n"
        "from chipbench import harness\n"
        "c = harness.load_cell('tiny-dense.trickle')\n"
        "r = harness.run_cell(c, seed=9, seconds=2.0, trace=False,"
        " t_process=time.perf_counter(), log=lambda *a: None,"
        " compile_cache=False)\n"
        "print(json.dumps(r))\n") % (os.path.join(ROOT, "src"),
                                     str(tmp_path))
    p = _run(["-c", code], tmp_path)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"], r
    assert r["metrics"]["arrivals.trickle"]["value"] == 6
    # metrics listed for other cells only stay out of the line
    assert set(r["metrics"]) == {"itl_p95_ms", "setup_s", "arrivals.trickle"}
    after = {p: p.read_bytes() for p in before}
    assert after == before        # nothing that was there changed
