"""The micro-batched serving layer (Session.serve / Server).

Acceptance hammer: N threads of concurrent estimates are **bit-equal** to
serial ``Session.estimate`` whatever batch each request lands in, on both
array backends.  Plus: fixed-shape padding equality, cache-hit semantics,
in-flight coalescing, timeout/overload/drain/close lifecycle, and the
seeded batch-composition-independence determinism sweep.
"""
import importlib.util
import itertools
import threading
import time

import numpy as np
import pytest

import repro
from repro import Design, Session
from repro.core import model_batch as mb
from repro.core.cache import LruCache
from repro.core.lsu import LsuType
from repro.core.serving import (
    RequestTimeout,
    Server,
    ServerClosed,
    ServerOverloaded,
    _next_pow2,
    pad_group_batch,
)

ALL_TYPES = [LsuType.BC_ALIGNED, LsuType.BC_NON_ALIGNED,
             LsuType.BC_WRITE_ACK, LsuType.ATOMIC_PIPELINED]


def _pool(n: int) -> list[Design]:
    """``n`` distinct designs spanning every LSU type and stride."""
    combos = itertools.cycle(
        (t, g, s, d) for t in ALL_TYPES for g in (1, 2, 3, 4)
        for s in (1, 4, 16) for d in (1, 3, 7))
    return [Design.microbench(t, n_ga=g, simd=s, delta=d,
                              n_elems=1 << (12 + i % 4),
                              name=f"pool-{i}")
            for i, (t, g, s, d) in zip(range(n), combos)]


def _eq(a: repro.Estimate, b: repro.Estimate) -> None:
    """Bit-equality of the numeric surface (not `design`/`cached` metadata)."""
    assert a.t_exe == b.t_exe
    assert a.t_ideal == b.t_ideal
    assert a.t_ovh == b.t_ovh
    assert a.bound_ratio == b.bound_ratio
    assert a.memory_bound == b.memory_bound
    assert a.total_bytes == b.total_bytes
    assert a.n_lsu == b.n_lsu


BACKENDS = ["numpy-batch",
            pytest.param("jax-jit", marks=pytest.mark.skipif(
                importlib.util.find_spec("jax") is None,
                reason="jax not installed"))]


class TestHammer:
    """The acceptance criterion: concurrent == serial, bit for bit."""

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_concurrent_bit_equal_to_serial(self, backend):
        sess = Session(backend=backend)
        designs = _pool(48)
        serial = {d.name: sess.estimate(d) for d in designs}
        results: dict[int, list] = {}
        errors: list[BaseException] = []

        def client(tid: int) -> None:
            rng = np.random.default_rng(tid)
            order = rng.permutation(len(designs))
            out = []
            try:
                for i in order:
                    out.append(srv.estimate(designs[i]))
            except BaseException as exc:  # noqa: BLE001 — surface in main thread
                errors.append(exc)
            results[tid] = out

        # cache off: every request must go through the batcher (coalescing
        # still allowed — a coalesced future is a batcher-scored row too)
        with sess.serve(max_batch=16, max_wait_ms=0.5, cache_size=0) as srv:
            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(8)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            stats = srv.stats()
        assert not errors
        n_results = 0
        for out in results.values():
            for est in out:
                _eq(est, serial[est.design.name])
                n_results += 1
        assert n_results == 8 * len(designs)
        assert stats["batches"] >= 1 and stats["error_rate"] == 0.0

    def test_result_carries_callers_design(self):
        """Coalesced or cached, `est.design` is the submitted object's name."""
        sess = Session()
        d = Design.microbench(LsuType.BC_ALIGNED, n_ga=2, name="mine")
        with sess.serve() as srv:
            assert srv.estimate(d).design.name == "mine"
            assert srv.estimate(d).design.name == "mine"   # cached path


class TestDeterminism:
    """Seeded sweep: per-design results are independent of which batch the
    design lands in, what its neighbours are, and where in the batch it
    sits — scored directly through `_score` for exact control of batch
    composition."""

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_batch_composition_independence(self, backend, seed):
        sess = Session(backend=backend)
        designs = _pool(24)
        serial = {d.name: sess.estimate(d) for d in designs}
        # max_batch bounds the padding target; direct _score chunks below
        # can be as large as the whole pool
        srv = sess.serve(max_batch=len(designs))
        try:
            rng = np.random.default_rng(seed)
            order = rng.permutation(len(designs))
            cuts = np.sort(rng.choice(
                np.arange(1, len(designs)), size=5, replace=False))
            for chunk in np.split(order, cuts):
                if not len(chunk):
                    continue
                batch = [designs[i] for i in chunk]
                for d, est in zip(batch, srv._score(batch)):
                    _eq(est, serial[d.name])
        finally:
            srv.close()


class TestPadding:
    """pad_group_batch: fixed shapes for jit, bit-equal real rows."""

    def _batch(self, designs):
        sess = Session()
        hw = [sess._hw_for(d) for d in designs]
        return mb.GroupBatch.from_kernels(
            [list(d.lsus) for d in designs],
            [h[0] for h in hw], [h[1] for h in hw],
            f=[d.f for d in designs])

    def test_padded_rows_bit_equal(self):
        designs = _pool(5)
        batch = self._batch(designs)
        m = len(np.asarray(batch.kernel))
        padded = pad_group_batch(batch, batch.n_kernels + 3, _next_pow2(m) * 2)
        ref = mb.estimate_batch(batch)
        got = mb.estimate_batch(padded)
        for fld in ("t_exe", "t_ideal", "t_ovh", "total_bytes"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, fld))[:batch.n_kernels],
                np.asarray(getattr(ref, fld)))

    def test_exact_shape_is_identity(self):
        batch = self._batch(_pool(3))
        m = len(np.asarray(batch.kernel))
        assert pad_group_batch(batch, batch.n_kernels, m) is batch

    def test_oversized_batch_rejected(self):
        batch = self._batch(_pool(4))
        with pytest.raises(ValueError, match="exceeds"):
            pad_group_batch(batch, batch.n_kernels - 1, 1 << 10)

    def test_next_pow2(self):
        assert [_next_pow2(n) for n in (1, 2, 3, 64, 65)] == \
            [1, 2, 4, 64, 128]


class TestCache:
    def test_hit_is_equal_and_marked(self):
        sess = Session()
        d = _pool(1)[0]
        with sess.serve() as srv:
            first = srv.estimate(d)
            second = srv.estimate(d)
            stats = srv.stats()
        assert first.cached is False
        assert second.cached is True
        _eq(second, first)
        _eq(first, sess.estimate(d))
        assert stats["cache"]["hits"] >= 1
        assert 0.0 < stats["cache_hit_rate"] <= 1.0

    def test_distinct_sessions_never_share_numbers(self):
        """The session salt keys hardware/calibration into the cache."""
        d = _pool(1)[0]
        a = Session().serve()
        b = Session().with_hardware(repro.hw.get("stratix10_ddr4_2666")).serve()
        try:
            ea, eb = a.estimate(d), b.estimate(d)
            assert ea.t_exe != eb.t_exe
            assert not eb.cached
        finally:
            a.close()
            b.close()

    def test_lru_evicts_in_insertion_order(self):
        c = LruCache(2)
        c.put("a", 1)
        c.put("b", 2)
        assert c.get("a") == 1          # refresh a
        c.put("c", 3)                   # evicts b
        assert c.get("b") is None
        assert c.get("a") == 1 and c.get("c") == 3
        s = c.stats()
        assert s["size"] == 2 and s["hits"] == 3 and s["misses"] == 1

    def test_zero_capacity_disables_caching(self):
        c = LruCache(0)
        c.put("a", 1)
        assert c.get("a") is None and c.stats()["size"] == 0

    def test_predict_memoizes(self):
        sess = Session()
        calls = []
        real = sess.predict

        def counting_predict(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        object.__setattr__(sess, "predict", counting_predict)  # frozen dc
        hlo = ("HloModule m\n\n"
               "ENTRY e (p.0: f32[1024,1024]) -> f32[1024,1024] {\n"
               "  %p.0 = f32[1024,1024]{1,0} parameter(0)\n"
               "  ROOT %n = f32[1024,1024]{1,0} negate(%p.0)\n"
               "}\n")
        with sess.serve() as srv:
            a = srv.predict(hlo)
            b = srv.predict(hlo)
        assert a is b                   # literally the cached object
        assert len(calls) == 1          # heavy parse ran once


class TestCoalescing:
    def test_identical_inflight_designs_share_one_future(self):
        sess = Session()
        d = _pool(1)[0]
        # long linger so all submits land while the first is still queued
        with sess.serve(max_batch=64, max_wait_ms=100.0, cache_size=0) as srv:
            futs = [srv.submit(d) for _ in range(16)]
            ests = [f.result(timeout=5) for f in futs]
            stats = srv.stats()
        assert len({id(f) for f in futs}) < 16
        assert stats["coalesced"] >= 1
        ref = sess.estimate(d)
        for est in ests:
            _eq(est, ref)


class TestTimeoutOverloadDrain:
    def test_blocking_estimate_times_out(self):
        sess = Session()
        # batcher lingers 500 ms on the first request -> 20 ms budget expires
        with sess.serve(max_batch=8, max_wait_ms=500.0, cache_size=0) as srv:
            with pytest.raises(RequestTimeout):
                srv.estimate(_pool(1)[0], timeout_ms=20)

    def test_expired_request_fails_before_scoring(self):
        sess = Session()
        designs = _pool(2)
        with sess.serve(max_batch=8, max_wait_ms=300.0, cache_size=0) as srv:
            ok = srv.submit(designs[0])                   # no deadline
            doomed = srv.submit(designs[1], timeout_ms=1)  # expires in queue
            assert ok.result(timeout=5).design.name == designs[0].name
            with pytest.raises(RequestTimeout):
                doomed.result(timeout=5)
            assert srv.stats()["expired"] == 1

    def test_overload_fast_fails(self):
        sess = Session()
        designs = _pool(4)
        srv = sess.serve(max_batch=1, max_wait_ms=0.0, cache_size=0,
                         max_queue=1)
        release = threading.Event()
        real_score = srv._score

        def slow_score(batch):
            release.wait(timeout=10)
            return real_score(batch)

        srv._score = slow_score
        try:
            busy = srv.submit(designs[0])
            for _ in range(1000):                   # batcher picked [0] up
                if srv._queue.empty():
                    break
                time.sleep(1e-3)
            queued = srv.submit(designs[1])         # fills the 1-slot queue
            with pytest.raises(ServerOverloaded):
                srv.submit(designs[2])
            assert srv.stats()["rejected_overload"] == 1
            release.set()
            busy.result(timeout=5)
            queued.result(timeout=5)
            # the rejected key was cleaned up: a retry succeeds
            assert srv.estimate(designs[2]).design.name == designs[2].name
        finally:
            release.set()
            srv.close()

    def test_drain_completes_everything(self):
        sess = Session()
        designs = _pool(20)
        srv = sess.serve(max_batch=4, max_wait_ms=5.0, cache_size=0)
        futs = [srv.submit(d) for d in designs]
        srv.drain(timeout_s=10)
        assert all(f.done() for f in futs)
        srv.close()
        assert srv.stats()["served"] == len(designs)


class TestLifecycle:
    def test_submit_after_close_raises(self):
        srv = Session().serve()
        srv.close()
        assert srv.closed
        with pytest.raises(ServerClosed):
            srv.submit(_pool(1)[0])
        srv.close()                     # idempotent

    def test_graceful_close_scores_queued_work(self):
        sess = Session()
        designs = _pool(10)
        srv = sess.serve(max_batch=4, max_wait_ms=50.0, cache_size=0)
        futs = [srv.submit(d) for d in designs]
        srv.close(drain=True)
        for d, f in zip(designs, futs):
            _eq(f.result(timeout=0), sess.estimate(d))

    def test_abrupt_close_fails_queued_work(self):
        sess = Session()
        srv = sess.serve(max_batch=64, max_wait_ms=500.0, cache_size=0)
        futs = [srv.submit(d) for d in _pool(6)]
        srv.close(drain=False)
        failed = 0
        for f in futs:
            try:
                f.result(timeout=5)
            except ServerClosed:
                failed += 1
        assert failed >= 1              # first batch may already be in flight

    def test_context_manager_exception_skips_drain(self):
        with pytest.raises(RuntimeError, match="boom"):
            with Session().serve(max_wait_ms=500.0, cache_size=0) as srv:
                srv.submit(_pool(1)[0])
                raise RuntimeError("boom")
        assert srv.closed

    def test_invalid_params_rejected(self):
        sess = Session()
        for kw in ({"max_batch": 0}, {"max_wait_ms": -1.0},
                   {"max_queue": 0}, {"timeout_ms": 0}):
            with pytest.raises(ValueError):
                sess.serve(**kw)


class TestStatsAndSurface:
    def test_stats_shape(self):
        sess = Session()
        with sess.serve() as srv:
            for d in _pool(8):
                srv.estimate(d)
            s = srv.stats()
        assert s["submitted"] == s["served"] == 8
        assert s["errors"] == 0 and s["error_rate"] == 0.0
        assert s["mean_batch"] >= 1.0
        lat = s["latency_ms"]
        assert lat["n"] == 8
        assert 0.0 < lat["p50"] <= lat["p99"]
        wait = s["queue_wait_ms"]
        assert wait["n"] == 8 and 0.0 <= wait["p50"] <= wait["p99"]
        assert s["queue_depth"] == 0 and s["inflight"] == 0

    def test_queue_wait_grows_behind_a_slow_estimator(self):
        """One request a batch, each scored in 50 ms: the k-th request of
        a burst waits about k batches in the queue, and its latency holds
        that wait and its own scoring."""
        sess = Session()
        srv = sess.serve(max_batch=1, max_wait_ms=0.0, cache_size=0)
        real_score = srv._score

        def slow_score(batch):
            time.sleep(0.05)
            return real_score(batch)

        srv._score = slow_score
        with srv:
            futs = [srv.submit(d) for d in _pool(4)]
            for f in futs:
                f.result(timeout=10)
            s = srv.stats()
        wait, lat = s["queue_wait_ms"], s["latency_ms"]
        assert wait["n"] == lat["n"] == 4
        assert wait["p50"] < wait["p99"]
        assert wait["p99"] >= 3 * 50 * 0.9        # three batches ahead
        assert lat["p99"] >= wait["p99"] + 50 * 0.9

    def test_public_surface(self):
        from repro import api

        for name in ("Server", "ServerClosed", "ServerOverloaded",
                     "RequestTimeout"):
            assert name in api.__all__
            assert getattr(repro, name) is getattr(api, name)
        assert isinstance(Session().serve(), Server) is True
        assert repro.Estimate(
            t_exe=1.0, t_ideal=1.0, t_ovh=0.0, bound_ratio=1.0,
            memory_bound=True, total_bytes=1.0, n_lsu=1).cached is False
