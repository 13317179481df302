"""The persistent compilation cache lives in one fixed place: where
``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``, and
importing the launchers turns nothing on."""
import json
import os
import subprocess
import sys
from pathlib import Path

from helpers import SRC

from repro.compile_cache import REPO_CACHE_DIR

_SCRIPT = """
import json, os, random, jax
import repro.launch.serve, repro.launch.train
from repro.compile_cache import enable_compile_cache
after_import = jax.config.jax_compilation_cache_dir
path = enable_compile_cache()
# a constant no earlier run compiled, so this run must write an entry
salt = random.random()
jax.jit(lambda x: x * salt + 1.0)(jax.numpy.ones(3)).block_until_ready()
print(json.dumps({"after_import": after_import, "path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _run(cache_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + env.get("PYTHONPATH", ""))
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    proc = subprocess.run([sys.executable, "-c", _SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_cache_dir_from_env_and_nowhere_else(tmp_path):
    repo_before = _entries(REPO_CACHE_DIR)
    out = _run(str(tmp_path))
    assert out["after_import"] == str(tmp_path)     # jax reads the variable
    assert out["path"] == out["config"] == str(tmp_path)
    assert _entries(tmp_path)
    assert _entries(REPO_CACHE_DIR) == repo_before


def test_cache_dir_defaults_to_checkout():
    before = _entries(REPO_CACHE_DIR)
    out = _run(None)
    assert out["after_import"] is None
    assert out["path"] == out["config"] == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR.parent == Path(SRC).parent
    assert _entries(REPO_CACHE_DIR) - before
