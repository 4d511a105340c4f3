"""``chip_smoke.py``'s model phase at a tiny size on the CPU, Pallas in
interpret mode: the script's control flow and checks, without the chip
(on the chip it runs at mamba2-780m's published widths)."""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import pytest

from repro.configs.mamba2_780m import SMOKE


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_model_phase_small(chip_smoke):
    out = chip_smoke.model_phase(
        SMOKE.replace(kernel_mode="interpret"), requests=3, prompt_len=16,
        new_tokens=4, slots=2, train_batch=2, train_seq=32, train_steps=2)
    assert out["ssd_err"] <= chip_smoke.SSD_RTOL
    assert out["logits_err"] <= chip_smoke.LOGITS_RTOL
    assert len(out["losses"]) == 2
    assert all(math.isfinite(x) for x in out["losses"])
