"""Trace reduction, on synthetic intervals and on a small trace recorded on
a TPU v5e (``bench/testdata/record.py``: one fused sweep of one chunk)."""
import pathlib

import numpy as np
import pytest

from bench import trace

TRACE = pathlib.Path(__file__).resolve().parents[1] / "testdata" / \
    "sweep_trace.xplane.pb"


def naive_busy(ops, lo, hi) -> float:
    """Busy time by walking the sorted, clipped intervals one by one."""
    total, end = 0.0, -np.inf
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in ops
                       if e > lo and s < hi):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def test_merge_and_gaps_synthetic():
    ops = [(5, 7, "a"), (0, 2, "b"), (1, 3, "c"), (9, 12, "d"), (6, 6.5, "e")]
    busy = trace.merge(ops, 0.5, 11)
    assert busy.tolist() == [[0.5, 3.0], [5.0, 7.0], [9.0, 11.0]]
    assert trace.gaps(busy, 0.5, 11).tolist() == [[3.0, 5.0], [7.0, 9.0]]
    assert trace.gaps(trace.merge([], 0, 4), 0, 4).tolist() == [[0.0, 4.0]]
    assert np.sum(busy[:, 1] - busy[:, 0]) == naive_busy(ops, 0.5, 11)


def test_gap_named_by_innermost_host_event():
    host = [(0, 100, "bench.window"), (0, 100, "bench.sweep"),
            (40, 60, "np.asarray(jax.Array)"), (10, 20, "shard_args")]
    assert trace.name_gap(45, 55, host) == "np.asarray(jax.Array)"
    assert trace.name_gap(5, 25, host) == "shard_args"
    assert trace.name_gap(25, 35, host) == "bench.sweep"
    assert trace.name_gap(200, 300, host) == "untraced host time"


@pytest.mark.parametrize("hlo,want", [
    ("%fusion.6 = u32[131072]{0:T(1024)} fusion(u32[128]{0:T(128)S(1)} "
     "%custom-call.10, s32[131072]{0:T(1024)S(1)} %clamp.8), kind=kCustom, "
     "calls=%fused_computation.6",
     "fusion.6 = u32[131072] fusion(u32[128], s32[131072])"),
    ("%custom-call.1 = u32[]{:T(128)} custom-call(s64[]{:T(128)} %a.1), "
     "custom_call_target=\"X64SplitLow\"", "custom-call.1 = u32[] custom-call(s64[])"),
    ("%fusion.6 = (f32[131072]{0:T(1024)}, f32[131072]{0:T(1024)}) "
     "fusion(s64[131072]{0} %p.1), kind=kLoop, calls=%fused_computation.6",
     "fusion.6 = (f32[131072], f32[131072]) fusion(s64[131072])"),
])
def test_op_name(hlo, want):
    assert trace.op_name(hlo) == want


def test_recorded_chip_trace():
    pd = trace.load(str(TRACE))
    devices, host = trace.planes(pd)
    assert len(devices) == 1                  # one v5e chip
    (lo, hi), = [(s, e) for s, e, n in host if n == trace.WINDOW]
    red = trace.reduce(pd)
    assert red["n_devices"] == 1
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert red["busy_s"] == pytest.approx(naive_busy(devices[0], lo, hi) / 1e9)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s_sum"] == red["busy_s"]
    ops = [d for _, d in red["device_ops"]]
    assert 0 < len(ops) <= trace.TOP and ops == sorted(ops, reverse=True)
    gap_s = [d for _, d in red["idle_gaps"]]
    assert gap_s == sorted(gap_s, reverse=True)
    # the busy time and every idle gap of the one device tile the window
    busy = trace.merge(devices[0], lo, hi)
    idle = trace.gaps(busy, lo, hi)
    assert np.sum(busy[:, 1] - busy[:, 0]) + np.sum(idle[:, 1] - idle[:, 0]) \
        == pytest.approx(hi - lo)
    assert gap_s[0] == pytest.approx(np.max(idle[:, 1] - idle[:, 0]) / 1e9)
    assert all(isinstance(n, str) and n for n, _ in red["idle_gaps"])
