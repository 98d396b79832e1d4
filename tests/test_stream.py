"""Streaming sweep engine: bit-equality with the materialized path across
all three backends, chunk-size/order invariance of the folded Pareto front,
reducer semantics, and the path a four-device host takes."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import Session, Space
from repro.core import DDR4_1866, DDR4_2666, LsuType
from repro.core.stream import (GridEnumerator, ParetoReducer, StatsReducer,
                               TopKReducer, run_stream)
from repro.core.sweep import _grid_points, pareto_front

ALL_TYPES = [LsuType.BC_ALIGNED, LsuType.BC_NON_ALIGNED,
             LsuType.BC_WRITE_ACK, LsuType.ATOMIC_PIPELINED]

#: Shared grid of the acceptance criterion: 4*3*3*2*3*2*2 = 864 points.
GRID = dict(
    lsu_type=ALL_TYPES,
    n_ga=[1, 2, 4],
    simd=[1, 4, 16],
    n_elems=[1 << 14, 1 << 16],
    delta=[1, 2, 7],
    include_write=[False, True],
    dram=[DDR4_1866, DDR4_2666],
)


@pytest.fixture(scope="module")
def materialized():
    return Session().sweep(Space.grid(**GRID))


def _assert_stream_matches(st, mat):
    """Front ids, top-k rows, summary and survivor estimates all bit-equal."""
    assert st.is_streaming and st.n_points == mat.n_points
    front_mat = np.asarray(mat.pareto())
    front_st = np.asarray(st.point_ids)[st.pareto()]
    np.testing.assert_array_equal(np.sort(front_st), front_mat)
    assert st.top_k(10) == mat.top_k(10)
    sm = {k: v for k, v in mat.summary().items() if k != "backend"}
    ss = {k: v for k, v in st.summary().items() if k != "backend"}
    assert ss == sm                               # min/counts are exact
    sel = np.asarray(st.point_ids)
    np.testing.assert_array_equal(np.asarray(st.t_exe),
                                  np.asarray(mat.t_exe)[sel])
    np.testing.assert_array_equal(np.asarray(st.resource),
                                  np.asarray(mat.resource)[sel])
    np.testing.assert_array_equal(np.asarray(st.memory_bound),
                                  np.asarray(mat.memory_bound)[sel])
    assert st.rows(st.pareto()) == mat.rows(front_mat)


class TestStreamingEqualsMaterialized:
    def test_numpy_batch_nondividing_chunk(self, materialized):
        """chunk=100 does not divide 864: the padded tail must be masked."""
        st = Session().sweep(Space.grid(**GRID), chunk_size=100)
        _assert_stream_matches(st, materialized)

    def test_numpy_batch_threaded(self, materialized):
        """The thread-pool path folds in submission order — identical."""
        st = Session().sweep(Space.grid(**GRID), chunk_size=64, workers=3)
        _assert_stream_matches(st, materialized)

    def test_scalar_backend(self, materialized):
        st = Session(backend="scalar").sweep(Space.grid(**GRID),
                                             chunk_size=128)
        _assert_stream_matches(st, materialized)

    def test_jax_jit_backend(self, materialized):
        pytest.importorskip("jax")
        st = Session(backend="jax-jit").sweep(
            Space.grid(**GRID).stream(chunk_size=100))
        _assert_stream_matches(st, materialized)

    def test_stats_sums_agree(self, materialized):
        st = Session().sweep(Space.grid(**GRID), chunk_size=37)
        assert st.stats["t_exe_sum"] == pytest.approx(
            float(np.sum(materialized.t_exe)), rel=1e-9)
        assert st.stats["total_bytes_sum"] == pytest.approx(
            float(np.sum(np.asarray(materialized.estimate.total_bytes))),
            rel=1e-9)
        assert st.stats["t_exe_min_id"] == int(np.argmin(materialized.t_exe))

    @pytest.mark.parametrize("chunk", [37, 100, 864, 4096])
    def test_chunk_size_invariance(self, materialized, chunk):
        st = Session().sweep(Space.grid(**GRID), chunk_size=chunk)
        np.testing.assert_array_equal(
            np.asarray(st.point_ids)[st.pareto()],
            np.asarray(materialized.pareto()))
        assert st.top_k(5) == materialized.top_k(5)

    def test_hardware_axis_and_calibration(self):
        """Hardware-axis overrides + session calibration stream identically
        (the no-double-scaling rule of Session.sweep)."""
        import dataclasses

        import repro.hw as hw

        sp = Space.grid(
            lsu_type=[LsuType.BC_ALIGNED, LsuType.BC_WRITE_ACK],
            n_ga=[1, 2], n_elems=[1 << 14],
            hardware=[None, hw.get("stratix10_ddr4_2666"),
                      hw.get("stratix10_ddr4_1866")
                      .with_host_factor(2.0).with_name("x2")])
        sess = dataclasses.replace(Session(), calibration_factor=1.5)
        mat = sess.sweep(sp)
        st = sess.sweep(sp, chunk_size=5)
        _assert_stream_matches(st, mat)
        assert {r["hardware"] for r in st.rows()} <= \
            {"", "stratix10_ddr4_2666", "x2"}


class TestGridEnumerator:
    def test_codes_match_materialized_grid(self):
        """Mixed-radix decode reproduces the materialized point order."""
        from repro.core.sweep import _normalize_axes

        points, n, cats = _grid_points(GRID)
        enum = GridEnumerator(_normalize_axes(GRID))
        assert enum.n == n
        codes = enum.codes(np.arange(n))
        for name, (table, idx) in cats.items():
            np.testing.assert_array_equal(codes[name], idx)
        rng = np.random.default_rng(0)
        some = rng.integers(0, n, size=50)
        sub = enum.codes(some)
        for name, (table, idx) in cats.items():
            np.testing.assert_array_equal(sub[name], idx[some])

    def test_empty_axis_yields_empty_grid(self):
        """An empty axis makes the grid empty, not invalid: n == 0 and
        codes of an empty id batch decode to empty columns."""
        enum = GridEnumerator({"a": [1, 2], "b": []})
        assert enum.n == 0
        codes = enum.codes(np.empty(0, dtype=np.int64))
        assert set(codes) == {"a", "b"}
        assert all(len(v) == 0 for v in codes.values())


def _synthetic_cols(n, seed=0):
    rng = np.random.default_rng(seed)
    vals = rng.random((n, 2))
    dup = rng.integers(0, n, n // 10)
    vals[dup] = vals[rng.integers(0, n, n // 10)]       # duplicated rows
    return {"id": np.arange(n, dtype=np.int64),
            "t_exe": vals[:, 0], "resource": vals[:, 1]}


def _fold_pareto(cols, bounds, order):
    """Fold ``cols`` chunked at ``bounds``, visiting chunks in ``order``."""
    red = ParetoReducer()
    chunks = np.split(np.arange(len(cols["id"])), bounds)
    for ci in order:
        idx = chunks[ci]
        if len(idx):
            red.update({k: v[idx] for k, v in cols.items()})
    return red.ids


class TestFoldInvariance:
    def test_chunk_partition_and_order_seeded(self):
        """Deterministic version of the property: the folded front equals
        the whole-space front under arbitrary partitions and fold orders."""
        cols = _synthetic_cols(600)
        ref = np.asarray(pareto_front(
            np.stack([cols["t_exe"], cols["resource"]], 1)))
        rng = np.random.default_rng(42)
        for trial in range(10):
            n_cuts = int(rng.integers(0, 12))
            bounds = np.sort(rng.integers(0, 600, n_cuts))
            order = rng.permutation(n_cuts + 1)
            got = _fold_pareto(cols, bounds, order)
            np.testing.assert_array_equal(got, ref), trial

    def test_hypothesis_property(self):
        hypothesis = pytest.importorskip(
            "hypothesis", reason="hypothesis not installed")
        import hypothesis.strategies as st

        cols = _synthetic_cols(300, seed=3)
        ref = np.asarray(pareto_front(
            np.stack([cols["t_exe"], cols["resource"]], 1)))

        @hypothesis.settings(max_examples=30, deadline=None)
        @hypothesis.given(
            cuts=st.lists(st.integers(0, 299), max_size=10),
            seed=st.integers(0, 2**31 - 1))
        def prop(cuts, seed):
            bounds = np.sort(np.asarray(cuts, dtype=np.int64))
            order = np.random.default_rng(seed).permutation(len(bounds) + 1)
            np.testing.assert_array_equal(
                _fold_pareto(cols, bounds, order), ref)

        prop()


class TestReducers:
    def test_topk_matches_stable_argsort(self):
        cols = _synthetic_cols(500, seed=1)
        cols["t_exe"] = np.round(cols["t_exe"], 2)      # force value ties
        red = TopKReducer(k=25, key="t_exe")
        for idx in np.split(np.arange(500), [123, 307, 499]):
            red.update({k: v[idx] for k, v in cols.items()})
        ref = np.argsort(cols["t_exe"], kind="stable")[:25]
        np.testing.assert_array_equal(red.ids, ref)

    def test_topk_fewer_points_than_k(self):
        cols = _synthetic_cols(5)
        red = TopKReducer(k=10)
        red.update(cols)
        assert len(red.ids) == 5

    def test_stats_exact(self):
        cols = _synthetic_cols(400, seed=2)
        cols["memory_bound"] = cols["t_exe"] > 0.5
        cols["total_bytes"] = cols["resource"] * 100
        red = StatsReducer()
        for idx in np.split(np.arange(400), [97, 250]):
            red.update({k: v[idx] for k, v in cols.items()})
        s = red.summary()
        assert s["n_points"] == 400
        assert s["memory_bound_points"] == int(cols["memory_bound"].sum())
        assert s["t_exe_min"] == float(cols["t_exe"].min())
        assert s["t_exe_min_id"] == int(np.argmin(cols["t_exe"]))

    def test_run_stream_pads_and_masks(self):
        seen = []

        def eval_chunk(ids):
            seen.append(ids.copy())
            assert len(ids) == 7                    # fixed shape, always
            return {"id": ids, "t_exe": ids.astype(np.float64),
                    "resource": np.ones(len(ids)),
                    "memory_bound": np.zeros(len(ids), bool),
                    "total_bytes": np.ones(len(ids))}

        stats = StatsReducer()
        out = run_stream(17, 7, eval_chunk, [stats])
        assert out.n_chunks == 3 and stats.n_points == 17
        assert stats.t_exe_sum == float(np.arange(17).sum())  # pad masked
        assert all(len(s) == 7 for s in seen)

    def test_reducer_list_reuse_does_not_contaminate(self):
        """Session.sweep folds into copies, so passing the same reducer
        instances to two sweeps keeps the reports independent."""
        reds = [ParetoReducer(), TopKReducer(3), StatsReducer()]
        r1 = Session().sweep(Space.grid(n_ga=[1, 2], n_elems=[1 << 14]),
                             reducers=reds)
        r2 = Session().sweep(Space.grid(n_ga=[4, 8], n_elems=[1 << 14]),
                             reducers=reds)
        assert r1.stats["n_points"] == 2 and r2.stats["n_points"] == 2
        assert {row["n_ga"] for row in r2.top_k(2)} == {4, 8}
        assert {row["n_ga"] for row in r1.top_k(2)} == {1, 2}
        # the caller's instances are untouched
        assert reds[1].cols is None and reds[2].n_points == 0

    def test_validation(self):
        with pytest.raises(ValueError):
            TopKReducer(k=0)
        with pytest.raises(ValueError):
            ParetoReducer(objectives=())
        with pytest.raises(ValueError):
            run_stream(4, 0, lambda ids: {}, [])


#: case -> (sweep constraints as Python source, the path the sweep takes)
#: case -> (constraints, path taken, devices that scored)
MULTI_DEVICE = {
    "fused": ("()", "device-fused", 4),
    "callable_constraint": ("(lambda cols: np.asarray(cols['n_ga']) > 1,)",
                            "host-stream", 1),
}


class TestMultiDevice:
    @pytest.mark.parametrize("case", list(MULTI_DEVICE))
    def test_four_device_host_matches_materialized(self, case):
        """4 forced host devices: the jax-jit streaming sweep folds to the
        same front/top-k as the numpy materialized path.  Unconstrained it
        runs the fused step on all four devices; a callable constraint
        takes the host stream, which scores on the default device."""
        pytest.importorskip("jax")
        constraints, path, devices = MULTI_DEVICE[case]
        code = textwrap.dedent("""
            import json
            import numpy as np
            import jax
            from repro import Session, Space
            from repro.core import LsuType

            assert jax.local_device_count() == 4
            sp = Space.grid(
                lsu_type=[LsuType.BC_ALIGNED, LsuType.BC_WRITE_ACK,
                          LsuType.ATOMIC_PIPELINED],
                n_ga=[1, 2, 4], simd=[1, 4, 16], n_elems=[1 << 14],
                delta=[1, 7])
            cons = %s
            mat = Session().sweep(sp, constraints=cons)
            # a constrained materialized report holds its feasible rows
            full = Session().sweep(sp)
            ids = (np.flatnonzero(cons[0](full.points)) if cons
                   else np.arange(len(full.t_exe)))
            st = Session(backend="jax-jit").sweep(sp, chunk_size=12,
                                                  constraints=cons)
            front_mat = np.sort(ids[np.asarray(mat.pareto())]).tolist()
            front_st = np.sort(
                np.asarray(st.point_ids)[st.pareto()]).tolist()
            prof = Session(backend="jax-jit").sweep(
                sp, chunk_size=12, constraints=cons, profile=True).profile
            print(json.dumps({
                "path": prof["path"], "devices": prof["devices"],
                "host_reason": prof.get("host_reason"),
                "front_mat": front_mat, "front_st": front_st,
                "topk_equal": st.top_k(5) == mat.top_k(5),
                "summary_equal": st.summary()["t_exe_min_ms"]
                    == mat.summary()["t_exe_min_ms"],
            }))
        """) % constraints
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=600,
                             env=env)
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["front_st"] == res["front_mat"]
        assert res["topk_equal"] and res["summary_equal"]
        assert res["path"] == path and res["devices"] == devices
        if path == "device-fused":
            assert res["host_reason"] is None
        else:
            assert res["host_reason"].startswith("constrained plan")
