"""Program spans and counters of ``Session.sweep`` on its three paths.

Each path is a case of every test: the trace holds ``repro.sweep`` with
the path's stages nested inside it, ``profile=True`` adds no device
synchronization, the profile's older keys are sums of its spans and its
counters add up, and profiling leaves the report as it is.
"""
import glob
import sys
import threading

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import Session, Space, compat
from repro.core import LsuType
from repro.core import device_stream as dev
from repro.core import spans
from repro.core.stream import default_reducers
from repro.search import ResourceEnvelope, within

GRID = dict(
    lsu_type=[LsuType.BC_ALIGNED, LsuType.BC_WRITE_ACK,
              LsuType.ATOMIC_PIPELINED],
    n_ga=[1, 2, 4, 8], simd=[1, 4, 16], n_elems=[1 << 14, 1 << 16],
    delta=[1, 7])
N = 3 * 4 * 3 * 2 * 2
CHUNK = 100


def _wide(cols):
    return np.asarray(cols["n_ga"]) > 1


#: path -> (sweep keywords, the spans that must nest inside repro.sweep)
PATHS = {
    "materialized": ({}, {"repro.sweep.enumerate", "repro.sweep.score",
                          "repro.chunk.pack", "repro.chunk.upload",
                          "repro.chunk.dispatch", "repro.chunk.pull"}),
    "host-stream": ({"chunk_size": CHUNK, "constraints": (_wide,)},
                    {"repro.sweep.plan", "repro.chunk.mask",
                     "repro.chunk.decode", "repro.chunk.pack",
                     "repro.chunk.upload", "repro.chunk.dispatch",
                     "repro.chunk.pull", "repro.chunk.fold",
                     "repro.sweep.close"}),
    "device-fused": ({"chunk_size": CHUNK},
                     {"repro.sweep.plan", "repro.sweep.open",
                      "repro.sweep.upload", "repro.sweep.compile",
                      "repro.sweep.dispatch", "repro.sweep.wait",
                      "repro.sweep.pull", "repro.sweep.close"}),
}

multi_device = pytest.mark.skipif(
    jax.local_device_count() > 1,
    reason="one-device sweeps; several devices are test_device_stream's")


def _sweep(path: str, profile: bool = True):
    kw, _ = PATHS[path]
    return Session(backend="jax-jit").sweep(Space.grid(**GRID),
                                            profile=profile, **kw)


def _host_events(log_dir) -> list:
    """``(line, start_ns, end_ns, name, stats)`` of every host event."""
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for pl in ProfileData.from_file(path).planes:
        if pl.name.startswith("/host:"):
            for i, ln in enumerate(pl.lines):
                out.extend((i, ev.start_ns, ev.end_ns, ev.name,
                            dict(ev.stats)) for ev in ln.events)
    return out


@multi_device
@pytest.mark.parametrize("path", list(PATHS))
def test_trace_nests_the_path_spans_in_the_sweep(path, tmp_path):
    _sweep(path)     # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        rep = _sweep(path)
    events = _host_events(tmp_path)
    (outer,) = [e for e in events if e[3] == "repro.sweep"]
    line, lo, hi, _, args = outer
    inside = {e[3] for e in events if e[0] == line and lo <= e[1]
              and e[2] <= hi and e[3].startswith("repro.")}
    assert PATHS[path][1] <= inside
    assert args["path"] == path
    assert args["points"] == N
    assert args["lanes"] == rep.profile["lanes"]
    assert args.get("lookups") == rep.profile.get("lookups")


@multi_device
@pytest.mark.parametrize("path", list(PATHS))
def test_profile_adds_no_device_sync(path, monkeypatch):
    _sweep(path)

    def refuse(*_a, **_k):
        raise AssertionError("a profiled sweep waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    assert _sweep(path).profile["path"] == path


@multi_device
@pytest.mark.parametrize("path", list(PATHS))
def test_legacy_keys_are_span_sums(path):
    rep = _sweep(path)
    prof = rep.profile
    assert prof["path"] == path
    assert prof["transfer_s"] == pytest.approx(
        prof.get("upload_s", 0.0) + prof.get("pull_s", 0.0))
    feasible = rep.n_points
    if path == "materialized":
        assert prof["chunks"] == 1 and prof["lanes"] == N
        assert prof["score_s"] >= prof["dispatch_s"] + prof["pull_s"]
    else:
        assert prof["enumerate_s"] == prof.get("decode_s", 0.0)
        assert prof["reduce_s"] == prof.get("fold_s", 0.0)
        assert prof["score_s"] == pytest.approx(
            prof.get("pack_s", 0.0) + prof["dispatch_s"])
        assert prof["chunks"] == -(-N // CHUNK)
        assert prof["lanes"] == prof["chunks"] * CHUNK
    assert prof["feasible"] == feasible
    if path == "host-stream":
        assert feasible < N
        assert prof["device_calls"] == prof["chunks"]
        assert prof["uploads"] % prof["chunks"] == 0
        assert prof["pulls"] % prof["chunks"] == 0
    if path == "device-fused":
        drv = dev.DeviceSweep.build(Session(backend="jax-jit").plan(
            Space.grid(**GRID), chunk_size=CHUNK))
        sig = drv._sig(default_reducers())
        with compat.enable_x64():
            leaves = len(jax.tree_util.tree_leaves(drv._init_carry(sig)))
        assert prof["pulls"] == leaves
        assert prof["device_calls"] == prof["chunks"]
        assert prof["compile_s"] <= prof["dispatch_s"]
        assert prof["enumerate_s"] == prof["reduce_s"] == 0.0
    children = {"materialized": ("enumerate_s", "score_s"),
                "host-stream": ("plan_s", "mask_s", "decode_s", "pack_s",
                                "upload_s", "dispatch_s", "pull_s",
                                "fold_s", "close_s"),
                "device-fused": ("plan_s", "open_s", "dispatch_s",
                                 "wait_s", "close_s")}[path]
    assert sum(prof[k] for k in children) <= prof["total_s"]


@multi_device
@pytest.mark.parametrize("path", list(PATHS))
def test_profile_leaves_the_report_unchanged(path):
    plain, profiled = _sweep(path, profile=False), _sweep(path)
    assert plain.profile is None and profiled.profile is not None
    assert plain.top_k(10) == profiled.top_k(10)
    np.testing.assert_array_equal(plain.pareto(), profiled.pareto())
    assert plain.stats == profiled.stats
    a, b = plain.summary(), profiled.summary()
    b.pop("profile")
    assert a == b


@multi_device
def test_constrained_sweep_takes_the_fused_path():
    """An envelope is masked inside the fused step: the sweep reports the
    device path, no reason for the host, and the host stream's count of
    kept points."""
    env = (within(ResourceEnvelope(lsu_ports=5, interconnect_bytes=256)),)
    fused = Session(backend="jax-jit").sweep(
        Space.grid(**GRID), chunk_size=CHUNK, constraints=env, profile=True)
    host = Session().sweep(Space.grid(**GRID), chunk_size=CHUNK,
                           constraints=env, profile=True)
    assert fused.profile["path"] == "device-fused"
    assert "host_reason" not in fused.profile
    assert host.profile["path"] == "host-stream"
    assert fused.profile["feasible"] == host.profile["feasible"] \
        == fused.n_points
    assert 0 < fused.n_points < N
    assert fused.summary()["n_candidates"] == N


@multi_device
def test_fused_merge_span_and_devices(tmp_path):
    """The fused path merges the chips' states into the reducers inside
    ``repro.sweep.merge``, a child of ``repro.sweep.close``, and counts the
    devices that held a range; the host stream reports its devices under
    the same key."""
    _sweep("device-fused")
    with jax.profiler.trace(str(tmp_path)):
        rep = _sweep("device-fused")
    events = _host_events(tmp_path)
    (merge,) = [e for e in events if e[3] == "repro.sweep.merge"]
    assert any(e[3] == "repro.sweep.close" and e[0] == merge[0]
               and e[1] <= merge[1] and merge[2] <= e[2] for e in events)
    prof = rep.profile
    assert 0.0 <= prof["merge_s"] <= prof["close_s"]
    assert prof["devices"] == 1
    assert _sweep("host-stream").profile["devices"] == prof["devices"]


#: case -> (grid widening of the benchmark grid, constrained, one-hot
#: lookups a step, gathers a step)
LOOKUP_CASES = {
    "unconstrained": ({}, False, 19, 0),
    "within_envelope": ({}, True, 26, 0),
    # 129 n_ga values pad to two buckets: that table is gathered
    "long_axis": ({"n_ga": list(range(1, 130))}, False, 18, 1),
}


@multi_device
@pytest.mark.parametrize("case", list(LOOKUP_CASES))
def test_fused_profile_counts_table_lookups(case):
    """On the benchmark grid the fused step reads its tables by 19 one-hot
    lookups a step, 26 under a board's envelope; a table past one bucket
    is gathered.  Both are counted as the step is traced, times the steps
    run."""
    from benchmarks.sweep_bench import STREAM_GRIDS
    from repro.hw import get as hw_get

    widen, constrained, lookups, gathers = LOOKUP_CASES[case]
    constraints = ((within(hw_get("stratix10_ddr4_1866").envelope),)
                   if constrained else ())
    plan = Session(backend="jax-jit").plan(
        Space.grid(**dict(STREAM_GRIDS["10m"], **widen)), chunk_size=CHUNK,
        constraints=constraints)
    prof: dict = {}
    dev.DeviceSweep.build(plan).fold_range(0, 3 * CHUNK, default_reducers(),
                                           prof)
    assert prof["device_calls"] == 3
    assert prof["lookups"] == lookups * prof["device_calls"]
    assert prof["lookup_gathers"] == gathers * prof["device_calls"]


def test_span_fills_the_profile_and_counts():
    prof: dict = {}
    with spans.span("sweep.open", prof):
        with spans.span("sweep.upload", prof):
            pass
    spans.count(prof, "pulls", 3)
    spans.count(prof, "pulls")
    spans.count(None, "pulls")
    with spans.span("sweep.open", prof) as sp:
        sp.annotate(chunks=2)
    assert set(prof) == {"open_s", "upload_s", "pulls"}
    assert prof["pulls"] == 4
    assert prof["open_s"] >= prof["upload_s"] >= 0.0


def test_profile_counts_survive_threads():
    """The numpy backend fills one profile from its chunk threads: no
    update may be lost."""
    prof: dict = {}

    def work():
        for _ in range(2000):
            spans.count(prof, "lanes", 3)
            with spans.span("chunk.fold", prof):
                pass

    threads = [threading.Thread(target=work) for _ in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert prof["lanes"] == 16 * 2000 * 3
    assert prof["fold_s"] > 0.0
