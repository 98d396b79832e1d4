"""Distributed sweep substrate: picklable SweepPlan round trips, mergeable
reducer invariance under arbitrary partitions/merge trees, the
coordinator/worker process pool (bit-equality, fault re-issue), the
backend × executor error matrix, and empty grids end-to-end."""
import pickle

import numpy as np
import pytest

import repro
from repro import Session, Space
from repro.core import DDR4_1866, DDR4_2666, LsuType
from repro.core import distributed as dist
from repro.core.stream import (ParetoReducer, StatsReducer, SweepPlan,
                               TopKReducer, default_reducers)

#: Small grid exercising categorical axes (lsu_type/dram) + a hardware axis
#: through every plan/distributed path: 2*3*2*2*2 = 48 points.
GRID = dict(
    lsu_type=[LsuType.BC_ALIGNED, LsuType.ATOMIC_PIPELINED],
    n_ga=[1, 2, 4],
    simd=[1, 16],
    n_elems=[1 << 12, 1 << 14],
    dram=[DDR4_1866, DDR4_2666],
)


@pytest.fixture(scope="module")
def plan():
    return Session().plan(Space.grid(**GRID), chunk_size=8)


@pytest.fixture(scope="module")
def serial(plan):
    """The single-pass serial fold every partitioned run must reproduce."""
    reducers = default_reducers()
    plan.run(reducers)
    return reducers


def _stats(reducers):
    return next(r for r in reducers if isinstance(r, StatsReducer))


def _assert_matches_serial(merged, serial):
    """front membership, top-k order incl. ties, stats (var to 1e-12)."""
    for got, ref in zip(merged, serial):
        if isinstance(got, ParetoReducer):
            np.testing.assert_array_equal(got.ids, ref.ids)
        elif isinstance(got, TopKReducer):
            np.testing.assert_array_equal(got.ids, ref.ids)
            np.testing.assert_array_equal(got.cols["t_exe"],
                                          ref.cols["t_exe"])
        elif isinstance(got, StatsReducer):
            g, r = got.summary(), ref.summary()
            for k in ("n_points", "memory_bound_points", "t_exe_min",
                      "t_exe_min_id", "t_exe_sum", "total_bytes_sum",
                      "t_exe_mean"):
                assert g[k] == r[k], k             # bit-equal by contract
            assert g["t_exe_var"] == pytest.approx(r["t_exe_var"],
                                                   rel=1e-12, abs=1e-24)


def _fold_partition(plan, bounds, reducers=None):
    """Fold each chunk-aligned range [bounds[i], bounds[i+1]) into its own
    fresh reducer set; returns the list of per-range reducer sets."""
    parts = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        rs = default_reducers() if reducers is None \
            else [r.fresh() for r in reducers]
        plan.run_range(int(lo), int(hi), rs)
        parts.append(rs)
    return parts


def _merge_tree(parts, order):
    """Merge the per-range reducer sets pairwise in ``order`` (a permutation
    of range indices) — an arbitrary left-deep merge tree."""
    base = [r.fresh() for r in parts[0]]
    for i in order:
        for b, p in zip(base, parts[i]):
            b.merge(type(b).from_state(p.state_dict()))
    return base


def _random_bounds(rng, n, n_chunks, chunk):
    cuts = np.sort(rng.choice(np.arange(1, n_chunks), size=min(
        int(rng.integers(0, 4)), n_chunks - 1), replace=False))
    return [0] + [int(c) * chunk for c in cuts] + [n]


class TestSweepPlan:
    def test_pickle_round_trip(self, plan):
        assert pickle.loads(pickle.dumps(plan)) == plan

    def test_json_round_trip(self, plan):
        assert SweepPlan.from_json(plan.to_json()) == plan

    def test_json_round_trip_hardware_axis(self):
        import repro.hw as hw

        p = Session().plan(Space.grid(
            n_ga=[1, 2], n_elems=[1 << 12],
            hardware=[None, hw.get("tpu_v4")]), chunk_size=4)
        p2 = SweepPlan.from_json(p.to_json())
        assert p2 == p
        # the rebuilt evaluator must score the hardware axis identically
        ids = np.arange(p.n, dtype=np.int64)
        a, b = p.evaluator()(ids), p2.evaluator()(ids)
        np.testing.assert_array_equal(a["t_exe"], b["t_exe"])

    def test_rebuilt_plan_scores_identically(self, plan, serial):
        clone = SweepPlan.from_json(plan.to_json())
        reducers = default_reducers()
        clone.run(reducers)
        _assert_matches_serial(reducers, serial)

    def test_plan_matches_session_sweep(self, plan, serial):
        rep = Session().sweep(Space.grid(**GRID), chunk_size=8)
        assert rep.stats["t_exe_sum"] == _stats(serial).summary()["t_exe_sum"]
        np.testing.assert_array_equal(
            np.sort(np.asarray(rep.point_ids)[rep.pareto()]),
            np.sort(serial[0].ids))

    def test_run_range_requires_chunk_alignment(self, plan):
        with pytest.raises(ValueError, match="chunk"):
            plan.run_range(3, plan.n, default_reducers())
        with pytest.raises(ValueError, match="chunk"):
            plan.run_range(0, 9, default_reducers())

    def test_bad_backend_rejected(self, plan):
        with pytest.raises(ValueError, match="backend"):
            SweepPlan(lists=dict(plan.lists), backend="cuda")


class TestMergeInvariance:
    """Folding any partition of id ranges and merging in any tree order is
    equivalent to the serial fold (satellite: property tests)."""

    @pytest.mark.parametrize("backend", ["numpy-batch", "scalar", "jax-jit"])
    def test_partition_and_merge_tree_seeded(self, backend):
        if backend == "jax-jit":
            pytest.importorskip("jax")
        plan = Session(backend=backend).plan(Space.grid(**GRID),
                                             chunk_size=8)
        ref = default_reducers()        # same-backend serial fold
        plan.run(ref)
        rng = np.random.default_rng(7)
        for trial in range(4 if backend == "numpy-batch" else 2):
            bounds = _random_bounds(rng, plan.n, plan.n_chunks,
                                    plan.chunk_size)
            parts = _fold_partition(plan, bounds)
            order = rng.permutation(len(parts))
            merged = _merge_tree(parts, order)
            _assert_matches_serial(merged, ref)

    def test_merge_preserves_topk_id_ties(self):
        """Equal values order by id whatever partition held them."""
        cols = {"id": np.arange(8, dtype=np.int64),
                "t_exe": np.zeros(8), "resource": np.zeros(8)}
        serial_r = TopKReducer(k=4)
        serial_r.update(cols)
        a, b = TopKReducer(k=4), TopKReducer(k=4)
        a.update({k: v[4:] for k, v in cols.items()})   # high ids first
        b.update({k: v[:4] for k, v in cols.items()})
        a.merge(b)
        np.testing.assert_array_equal(a.ids, serial_r.ids)
        np.testing.assert_array_equal(a.ids, np.arange(4))

    def test_merge_type_mismatch_rejected(self):
        with pytest.raises(TypeError):
            StatsReducer().merge(TopKReducer())
        with pytest.raises(ValueError):
            TopKReducer(k=3).merge(TopKReducer(k=5))
        with pytest.raises(ValueError):
            ParetoReducer().merge(ParetoReducer(objectives=("t_exe",)))

    def test_hypothesis_property(self, plan, serial):
        hypothesis = pytest.importorskip(
            "hypothesis", reason="hypothesis not installed")
        import hypothesis.strategies as st

        n_chunks, chunk = plan.n_chunks, plan.chunk_size

        @hypothesis.settings(max_examples=20, deadline=None)
        @hypothesis.given(
            cuts=st.lists(st.integers(1, n_chunks - 1), unique=True,
                          max_size=n_chunks - 1),
            seed=st.integers(0, 2**31 - 1))
        def prop(cuts, seed):
            bounds = [0] + sorted(int(c) * chunk for c in cuts) + [plan.n]
            parts = _fold_partition(plan, bounds)
            order = np.random.default_rng(seed).permutation(len(parts))
            _assert_matches_serial(_merge_tree(parts, order), serial)

        prop()


class TestDistributedExecutor:
    def test_processes_bit_equal_to_threads(self, serial):
        rep_t = Session().sweep(Space.grid(**GRID), chunk_size=8)
        rep_p = Session().sweep(Space.grid(**GRID), chunk_size=8,
                                executor="processes", workers=2)
        np.testing.assert_array_equal(rep_p.point_ids, rep_t.point_ids)
        np.testing.assert_array_equal(rep_p.front_idx, rep_t.front_idx)
        np.testing.assert_array_equal(rep_p.topk_idx, rep_t.topk_idx)
        assert rep_p.rows() == rep_t.rows()
        assert rep_p.stats["t_exe_sum"] == rep_t.stats["t_exe_sum"]
        assert rep_p.stats["t_exe_var"] == pytest.approx(
            rep_t.stats["t_exe_var"], rel=1e-12)
        assert rep_p.summary() == rep_t.summary()

    def test_killed_worker_reissued(self, plan, serial, tmp_path,
                                    monkeypatch):
        """A unit whose worker hard-exits mid-fold is re-issued and the
        merged result still matches the serial fold exactly."""
        marker = tmp_path / "killed"
        monkeypatch.setenv(dist._FAULT_ENV, f"1:kill:{marker}")
        reducers = default_reducers()
        out = dist.run_distributed(plan, reducers, workers=2, unit_chunks=2)
        assert marker.exists(), "fault never fired"
        _assert_matches_serial(out.reducers, serial)

    def test_straggling_worker_reissued(self, plan, serial, tmp_path,
                                        monkeypatch):
        """A hung worker trips the straggler timeout; the re-issued unit
        completes elsewhere (first result wins)."""
        marker = tmp_path / "hung"
        monkeypatch.setenv(dist._FAULT_ENV, f"1:hang:{marker}")
        reducers = default_reducers()
        out = dist.run_distributed(plan, reducers, workers=2, unit_chunks=2,
                                   straggler_timeout_s=1.0)
        assert marker.exists(), "fault never fired"
        _assert_matches_serial(out.reducers, serial)

    def test_custom_reducer_configuration_survives_transport(self, plan):
        """Workers rebuild reducers from state, so non-default k/objectives
        must round-trip through the task protocol."""
        reducers = (TopKReducer(k=3, key="resource"),)
        out = dist.run_distributed(plan, reducers, workers=1)
        ref = (TopKReducer(k=3, key="resource"), StatsReducer())
        plan.run(ref)
        np.testing.assert_array_equal(out.reducers[0].ids, ref[0].ids)


class TestExecutorErrorMatrix:
    """Every rejected backend × executor combination has a clear message."""

    def test_unknown_executor(self):
        with pytest.raises(ValueError, match="unknown executor 'mpi'"):
            Session().sweep(Space.grid(n_ga=[1]), executor="mpi")

    def test_workers_below_one(self):
        with pytest.raises(ValueError, match="workers must be >= 1"):
            Session().sweep(Space.grid(n_ga=[1]), workers=0)

    def test_threads_workers_on_jax_jit(self):
        with pytest.raises(ValueError, match="already uses every local chip"):
            Session(backend="jax-jit").sweep(Space.grid(n_ga=[1]),
                                             workers=2)

    def test_threads_workers_on_scalar(self):
        with pytest.raises(ValueError, match="GIL-bound"):
            Session(backend="scalar").sweep(Space.grid(n_ga=[1]), workers=2)

    def test_processes_on_random_space(self):
        with pytest.raises(TypeError, match="grid space"):
            Session().sweep(Space.random(4, seed=0, n_ga=(1, 8)),
                            executor="processes")

    def test_processes_on_jax_jit_refused_on_a_tpu_host(self, monkeypatch):
        """Each process worker would open the chip, which serves one
        process at a time."""
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="one process at a time"):
            Session(backend="jax-jit").sweep(Space.grid(n_ga=[1]),
                                             executor="processes")
        # the host backends' process pools open no device
        rep = Session().sweep(Space.grid(n_ga=[1, 2]), chunk_size=1,
                              executor="processes", workers=1)
        assert rep.n_points == 2

    @pytest.mark.parametrize("backend", ["numpy-batch", "scalar", "jax-jit"])
    def test_processes_accepts_every_backend_plan(self, backend):
        """executor='processes' is legal on all three backends (the plan
        rebuilds each backend's evaluator in the worker)."""
        if backend == "jax-jit":
            pytest.importorskip("jax")
        plan = Session(backend=backend).plan(Space.grid(n_ga=[1, 2]),
                                             chunk_size=2)
        assert plan.backend == backend      # would raise in __post_init__


class TestEmptyGrids:
    def test_materialized_empty(self):
        rep = Session().sweep(Space.grid(n_ga=[], simd=[1, 2]))
        assert rep.n_points == 0 and rep.rows() == []
        assert rep.summary()["n_points"] == 0
        assert rep.summary()["t_exe_min_ms"] == float("inf")
        with pytest.raises(ValueError, match="empty"):
            rep.best()

    def test_streaming_empty(self):
        rep = Session().sweep(Space.grid(n_ga=[], simd=[1, 2]),
                              chunk_size=4)
        assert rep.is_streaming and rep.n_points == 0
        assert rep.rows() == [] and len(rep.pareto()) == 0
        assert rep.top_k(5) == []
        assert rep.stats["t_exe_sum"] == 0.0

    def test_distributed_empty(self):
        rep = Session().sweep(Space.grid(n_ga=[], simd=[1, 2]),
                              executor="processes", workers=2)
        assert rep.n_points == 0 and rep.rows() == []

    def test_empty_plan_round_trips(self):
        p = Session().plan(Space.grid(n_ga=[], simd=[1]), chunk_size=4)
        assert p.n == 0 and p.n_chunks == 0
        assert SweepPlan.from_json(p.to_json()) == p


class TestServerSweep:
    def test_cached_and_bit_equal(self):
        sess = Session()
        with sess.serve() as srv:
            rep = srv.sweep(Space.grid(**GRID), chunk_size=8)
            again = srv.sweep(Space.grid(**GRID), chunk_size=8)
            assert again is rep                     # content-hash cache hit
            ref = sess.sweep(Space.grid(**GRID), chunk_size=8)
            assert rep.rows() == ref.rows()
            assert rep.summary() == ref.summary()

    def test_custom_reducers_bypass_cache(self):
        with Session().serve() as srv:
            a = srv.sweep(Space.grid(n_ga=[1, 2]), chunk_size=2,
                          reducers=[TopKReducer(k=1)])
            b = srv.sweep(Space.grid(n_ga=[1, 2]), chunk_size=2,
                          reducers=[TopKReducer(k=1)])
            assert a is not b

    def test_closed_server_rejects(self):
        srv = Session().serve()
        srv.close()
        with pytest.raises(repro.ServerClosed):
            srv.sweep(Space.grid(n_ga=[1]))
