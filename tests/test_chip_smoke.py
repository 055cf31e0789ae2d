"""chip_smoke.py on the CPU: its entry point refuses any platform but a
TPU, and its one-chip phases run end to end at smoke size (the rehearsal
of the chip run: same calls, same checks, toy widths)."""
import importlib.util
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_platform_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert f"found platform {jax.devices()[0].platform!r}" in out.err
    assert out.out == ""                  # no result line, nothing served


def test_one_chip_phases_at_smoke_size(chip_smoke, capsys):
    before = {id(a) for a in jax.live_arrays()}
    chip_smoke.one_chip("smoke", jax.devices()[0])
    out = capsys.readouterr().out
    assert "gateway: 8/8 requests finished" in out
    assert "batch: 8/8 requests finished" in out
    assert "0 compiles after warmup" in out
    # each phase released its replica (weights, KV pool) before the next
    assert not [a for a in jax.live_arrays() if id(a) not in before]
