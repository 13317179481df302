"""A whole run at smoke widths with the device check skipped: sound, it
reads ``correct``; with the timed path broken underneath, it does not.
The faults a serving cell can have (``chipbench.faults``): a token
altered where it is produced, and a decode step that returns its state
unchanged."""
import time

import pytest

from chipbench import faults, harness
from chipbench.tests import smoke

CASES = [("dense", "chat"), ("ssm", "decode-batch")]
LIMIT = {"dense": 0.01, "ssm": 0.3}     # test_chipbench_reference


def run(family, mix, seed=2**31 + 77):
    cell = smoke.cell(family, mix, logit_gap=LIMIT[family])
    return harness.run_cell(cell, seed=seed, seconds=2.0, trace=False,
                            t_process=time.perf_counter(),
                            log=lambda *_: None, compile_cache=False)


@pytest.mark.parametrize("family,mix", CASES)
def test_sound_run_is_correct(family, mix):
    r = run(family, mix)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert "setup_s" in r["metrics"] and "itl_p95_ms" in r["metrics"]


@pytest.mark.parametrize("family,mix", CASES)
def test_token_altered_where_produced(family, mix):
    with faults.planted("token", family):
        r = run(family, mix)
    assert not r["correct"]
    assert not r["checks"]["logit_gap"]["ok"]


@pytest.mark.parametrize("family,mix", CASES)
def test_decode_step_returns_state_unchanged(family, mix):
    with faults.planted("stale", family):
        r = run(family, mix)
    assert not r["correct"]
