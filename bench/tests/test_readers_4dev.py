"""The cross-chip merge reader: on synthetic layers, on a profile without
its span (an older program's), and on the profile the sweep driver sums
from the program on four forced CPU devices."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = "sweep_merge_ns_per_point"


def reader():
    spec = importlib.util.spec_from_file_location(
        "reader_" + NAME, ROOT / "bench" / "metrics" / f"{NAME}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer(points=1000, **prof):
    return {"kind": "sweep", "points": points, "profile": prof}


@pytest.mark.parametrize("prof,want", [
    (dict(path="device-fused", plan_s=1e-3, close_s=3e-3, merge_s=2e-3),
     2000.0),
    (dict(path="device-fused", plan_s=1e-3, close_s=3e-3), None),
    (dict(path="host-stream", plan_s=1e-3, close_s=2e-3, pull_s=5e-3),
     None),
])
def test_reader_on_synthetic_layer(prof, want):
    got = reader()(layer(**prof), None)
    assert got == (None if want is None else pytest.approx(want))


def test_reader_is_silent_without_points_or_sweeps():
    assert reader()(layer(points=0, merge_s=1e-3), None) is None
    assert reader()({"kind": "serve"}, None) is None


_WINDOW = r"""
import contextlib, json
from bench import run
from bench.tests.conftest import load_cell, shrink

spec = shrink(load_cell("explore_10m_4chip"))
spec["traffic"]["chunk"] = 1024
mod = run._load_module(run.BENCH / "drivers" / "sweep.py")
drv = mod.Driver(spec["config"], spec["traffic"], seed=2**31 + 7)
drv.warm_up()
out = drv.window(0.2, profile=True,
                 annotate=lambda _name: contextlib.nullcontext())
print(json.dumps(out["layer"], default=str))
"""


def test_reader_on_the_program():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    out = subprocess.run([sys.executable, "-c", _WINDOW],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["profile"]["devices"] == 4
    assert reader()(got, None) > 0
