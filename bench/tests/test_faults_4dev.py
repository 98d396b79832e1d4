"""The four-chip cell at a tiny size on four forced CPU devices (a fresh
process: the device count is fixed when jax starts; the harness's look for
a chip is skipped): a sound run is correct and runs the fused step on all
four devices, and a run whose merge leaves one chip's carry out is not."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

#: Whole runs of ``explore_10m_4chip`` shrunk as ``conftest.shrink`` does,
#: at a chunk that gives every device two chunks; the sound one records how
#: many chips' states each fused fold merged.  Prints one JSON line.
_RUNS = r"""
import json
from bench import run
from bench.tests.conftest import load_cell, shrink
from repro.core import device_stream as ds

def tiny(name):
    spec = shrink(load_cell(name))
    spec["traffic"]["chunk"] = 1024
    return spec

run.load_cell = tiny
merge, merged = ds.DeviceSweep._merge, []

def spy(self, reducers, sig, chips):
    merged.append(len(chips))
    return merge(self, reducers, sig, chips)

ds.DeviceSweep._merge = spy
out = {"sound": run.run_cell("explore_10m_4chip", seed=2**31 + 11,
                             seconds=0.5, trace=False),
       "merged": sorted(set(merged))}
ds.DeviceSweep._merge = (lambda self, reducers, sig, chips:
                         merge(self, reducers, sig, chips[:1] + chips[2:]))
out["chip_left_out"] = run.run_cell("explore_10m_4chip", seed=2**31 + 11,
                                    seconds=0.5, trace=False)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    out = subprocess.run([sys.executable, "-c", _RUNS], capture_output=True,
                         text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(runs):
    res = runs["sound"]
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == 4
    # each fused fold, the warm-up's included, merged four chips' states
    assert runs["merged"] == [4]


def test_chip_left_out_of_the_merge(runs):
    res = runs["chip_left_out"]
    assert res["device"]["count"] == 4
    assert not res["correct"]
    assert res["check"]["count_err"]["value"] > 0
