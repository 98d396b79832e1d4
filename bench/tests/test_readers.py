"""The program-span readers: on synthetic layers, on a profile without the
spans (an older program's), and on the profiles the sweep driver sums
from the program at a tiny size."""
import contextlib
import importlib.util
import pathlib

import pytest

from bench import run

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
READERS = ["sweep_edges_ns_per_point", "stream_mask_ns_per_point",
           "stream_pack_ns_per_point", "stream_pull_ns_per_point"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer(points=1000, **prof):
    return {"kind": "sweep", "points": points, "profile": prof}


HOST = dict(path="host-stream", plan_s=1e-3, close_s=2e-3, mask_s=3e-3,
            pack_s=4e-3, pull_s=5e-3, enumerate_s=0.0, reduce_s=0.0)
FUSED = dict(path="device-fused", plan_s=1e-3, open_s=2e-3, close_s=3e-3,
             pull_s=7e-3)


@pytest.mark.parametrize("name,prof,want", [
    ("sweep_edges_ns_per_point", HOST, 3000.0),
    ("sweep_edges_ns_per_point", FUSED, 6000.0),
    ("stream_mask_ns_per_point", HOST, 3000.0),
    ("stream_pack_ns_per_point", HOST, 4000.0),
    ("stream_pull_ns_per_point", HOST, 5000.0),
    ("stream_mask_ns_per_point", FUSED, None),
    ("stream_pack_ns_per_point", FUSED, None),
    ("stream_pull_ns_per_point", FUSED, None),
])
def test_reader_on_synthetic_layer(name, prof, want):
    got = reader(name)(layer(**prof), None)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_its_spans(name):
    """A program without the spans reports only the older keys."""
    old = {"path": "host-stream", "enumerate_s": 1e-3, "score_s": 2e-3,
           "reduce_s": 1e-3, "transfer_s": 1e-3, "total_s": 5e-3}
    assert reader(name)(layer(**old), None) is None
    assert reader(name)(layer(points=0, **HOST), None) is None
    assert reader(name)({"kind": "serve"}, None) is None


@pytest.mark.parametrize("cell,present", [
    ("explore_10m", {"sweep_edges_ns_per_point"}),
    ("explore_1m_within_s10", set(READERS)),
])
def test_readers_on_the_program(tiny, fresh_programs, cell, present):
    spec = run.load_cell(cell)          # shrunk by the tiny fixture
    mod = run._load_module(run.BENCH / "drivers" / "sweep.py")
    drv = mod.Driver(spec["config"], spec["traffic"], seed=2**31 + 7)
    drv.warm_up()
    out = drv.window(0.2, profile=True,
                     annotate=lambda _name: contextlib.nullcontext())
    got = {n: reader(n)(out["layer"], None) for n in READERS}
    assert {n for n, v in got.items() if v is not None} == present
    assert all(v > 0 for v in got.values() if v is not None)
