"""The traffic generator is a pure function of its seed."""
import json
import pathlib

import numpy as np
import pytest

from bench import generator

BENCH = pathlib.Path(__file__).resolve().parents[1]
BIG_SEED = 2**31 + 12345


def _data(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


@pytest.mark.parametrize("traffic", ["sweep_10m", "sweep_1m_within_s10"])
def test_sweep_lists_repeat_per_seed(traffic):
    cfg, tr = _data("configs", "paper_microbench_space"), _data("traffic",
                                                                traffic)
    a = generator.sweep_lists(cfg, tr, BIG_SEED, 3)
    b = generator.sweep_lists(cfg, tr, BIG_SEED, 3)
    c = generator.sweep_lists(cfg, tr, BIG_SEED + 1, 3)
    d = generator.sweep_lists(cfg, tr, BIG_SEED, 4)
    assert a == b
    assert a != c and a != d
    # every sweep of the mix has one shape
    for lists in (a, c, d):
        assert {k: len(v) for k, v in lists.items()} == \
               {k: len(v) for k, v in a.items()}
        assert all(v == sorted(v) for k, v in lists.items()
                   if k in ("n_ga", "n_elems", "delta"))
    lo, hi = cfg["ranges"]["n_ga"]
    assert lo <= min(a["n_ga"]) and max(a["n_ga"]) <= hi


def test_sweep_grid_sizes():
    cfg = _data("configs", "paper_microbench_space")
    big = generator.sweep_lists(cfg, _data("traffic", "sweep_10m"), 1, 0)
    small = generator.sweep_lists(cfg, _data("traffic",
                                             "sweep_1m_within_s10"), 1, 0)
    assert generator.grid_size(big) == 10_240_000
    assert generator.grid_size(small) == 1_024_000


def test_open_loop_repeats_per_seed():
    cfg, tr = _data("configs", "advisor_service"), _data("traffic",
                                                         "open_uniform")
    specs, due = generator.open_loop(cfg, tr, BIG_SEED, 2.0)
    specs2, due2 = generator.open_loop(cfg, tr, BIG_SEED, 2.0)
    specs3, due3 = generator.open_loop(cfg, tr, BIG_SEED + 1, 2.0)
    assert specs == specs2 and np.array_equal(due, due2)
    assert specs != specs3
    assert len(specs) == len(due)
    assert np.all(np.diff(due) > 0) and due[-1] < 2.0
    rate = tr["rate_per_s"]
    assert abs(len(due) - 2 * rate) < 6 * np.sqrt(2 * rate)
    keys = {tuple(sorted(s.items())) for s in specs}
    assert len(keys) == len(specs)                   # no design repeats
    share = np.mean(["app" in s for s in specs])
    assert abs(share - tr["app_share"]) < 0.05
