"""Shared test utilities, including an optional-``hypothesis`` shim.

Property tests import ``given``/``settings``/``st`` (and the stateful
API: ``RuleBasedStateMachine``/``rule``/``invariant``/``precondition``/
``run_state_machine_as_test``) from here instead of from ``hypothesis``
directly.  When hypothesis is installed the real objects are
re-exported; when it is missing the shim turns every ``@given``-decorated
test (and every ``run_state_machine_as_test`` call) into a skipped test
with a clear reason, so tier-1 collection never errors on the missing
dependency.  Suites that want coverage either way pair each hypothesis
test with a seeded-PRNG fallback gated on ``HAS_HYPOTHESIS``.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

try:
    from hypothesis import given, settings, strategies as st  # noqa: F401
    from hypothesis.stateful import (RuleBasedStateMachine,  # noqa: F401
                                     invariant, precondition, rule,
                                     run_state_machine_as_test)
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

    class _Strategy:
        """Opaque stand-in so module-level strategy expressions evaluate."""

        def __init__(self, name="strategy"):
            self._name = name

        def __call__(self, *a, **kw):
            return _Strategy(self._name)

        def __getattr__(self, item):
            return _Strategy(f"{self._name}.{item}")

    class _StrategiesModule:
        def __getattr__(self, item):
            return _Strategy(f"st.{item}")

    st = _StrategiesModule()

    def given(*_args, **_kwargs):
        def deco(fn):
            # NB: no functools.wraps - copying fn's signature would make
            # pytest treat the hypothesis-drawn arguments as fixtures.
            def skipper():
                pytest.skip("hypothesis not installed (see "
                            "requirements-dev.txt); property test skipped")
            skipper.__name__ = fn.__name__
            skipper.__doc__ = fn.__doc__
            return skipper
        return deco

    class _Settings:
        """No-op hypothesis.settings replacement (decorator + profiles)."""

        def __init__(self, *a, **kw):
            pass

        def __call__(self, fn):
            return fn

        @staticmethod
        def register_profile(*a, **kw):
            pass

        @staticmethod
        def load_profile(*a, **kw):
            pass

    settings = _Settings

    class RuleBasedStateMachine:
        """Stand-in base so state-machine classes still define cleanly."""

        def __init__(self):
            pass

    def _passthrough_decorator(*_args, **_kwargs):
        def deco(fn):
            return fn
        return deco

    rule = _passthrough_decorator
    invariant = _passthrough_decorator
    precondition = _passthrough_decorator

    def run_state_machine_as_test(machine_cls, *, settings=None):
        pytest.skip("hypothesis not installed (see requirements-dev.txt); "
                    "stateful property test skipped")


def run_with_devices(script: str, n_devices: int = 8, timeout=600):
    """Run a python snippet in a subprocess with N fake CPU devices.
    The snippet must print 'PASS' on success."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    # every snippet gets the repo's mesh constructor
    prelude = "from repro.launch.mesh import make_mesh\n"
    proc = subprocess.run([sys.executable, "-c",
                           prelude + textwrap.dedent(script)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-4000:]}"
    assert "PASS" in proc.stdout, f"stdout:\n{proc.stdout[-2000:]}" \
                                  f"\nstderr:\n{proc.stderr[-2000:]}"
    return proc.stdout
