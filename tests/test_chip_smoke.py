"""chip_smoke.py's phases at smoke widths on the CPU, kernels in interpret
mode, so the bring-up script keeps working between chip runs.  Only the
device gate differs from the chip run: here it must refuse."""
import importlib.util
import os
import sys

import pytest

from repro.kernels import ops

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_at_smoke_size(chip_smoke, capsys):
    # 300: past one flash tile (128) and one mLSTM chunk (256), neither
    # a multiple of it
    sizes = chip_smoke.Sizes(prompt_lens=(37, 300), cache_len=320,
                             max_new=4)
    chip_smoke.run_phases(sizes, smoke=True, impl="interpret")
    out = capsys.readouterr().out
    for phase in "abcd":
        assert f"[phase {phase}] ok requests=2 tokens_per_request=4" in out
    assert "paged decode step: pallas_call=yes" in out
    assert out.count("interpret vs xla max_abs_err/max_abs=") == 2


def test_chip_smoke_refuses_without_tpu(chip_smoke):
    with pytest.raises(SystemExit) as e:
        chip_smoke.check_device()
    assert "no TPU" in str(e.value)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_chip_smoke_refuses_non_pallas_impl(chip_smoke, monkeypatch, impl):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    with pytest.raises(SystemExit) as e:
        chip_smoke.check_kernel_impl()
    assert f"REPRO_KERNEL_IMPL={impl!r}" in str(e.value)
    # on the CPU the backend default is xla, which the gate refuses too
    monkeypatch.delenv("REPRO_KERNEL_IMPL")
    with ops.impl_scope(impl):
        with pytest.raises(SystemExit) as e:
            chip_smoke.check_kernel_impl()
    assert f"implementation is {impl!r}" in str(e.value)
