"""CPU rehearsal of ``chip_smoke.py``, the one-chip bring-up script.

The phases run here end to end at a tiny shape (Pallas kernels
interpreted, the read and merge paths on their XLA backends), which finds
wrong arguments, control flow and reference checks before any chip time
is spent.  ``main()`` itself must refuse to run off the TPU.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod           # dataclasses look it up there
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules[spec.name]


def test_phases_pass_at_tiny_scale(chip_smoke, tmp_path):
    smoke = chip_smoke.Smoke(chip_smoke.Scale(300, 200, 9000, reads=64),
                             tmp_path)
    try:
        lines = {name: smoke.run_phase(name)
                 for name in chip_smoke.Smoke.PHASES}
    finally:
        smoke.close()
    assert lines["twin_burst"]["twins"] == 16
    assert lines["fresh_burst"]["twins"] == 0
    assert lines["rotate"]["n_frozen"] == 300 + 64
    assert lines["recover"]["replayed"] == 6   # rotate, onboard, 4 ratings


def test_main_refuses_to_run_off_the_tpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    assert '"ok": true' not in capsys.readouterr().out
