"""The control: the same timed path with its float64 scoring turned off
(``repro.compat.enable_x64`` scoping nothing) must fail the comparison.

At the cells' own sizes this runs on the chip through ``bench/control.py``;
here every cell runs at a tiny size on the CPU.
"""
import pytest

from bench import control


@pytest.mark.parametrize("cell", ["explore_10m", "explore_1m_within_s10",
                                  "advisor_open_uniform"])
def test_float32_scoring_fails(tiny, fresh_programs, monkeypatch, cell):
    control.lower_precision(monkeypatch.setattr)
    res = tiny(cell)
    assert not res["correct"]
    assert res["check"]["rel_err"]["value"] > 1e-9
