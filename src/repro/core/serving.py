"""Micro-batched design-advisor serving: the long-lived query front door.

The estimator is a pure function that answers "how fast will this design
run" in microseconds — the missing piece for the interactive-advisor use
case is a concurrent front door.  :class:`Server` (built by
``Session.serve()``) turns one :class:`~repro.api.Session` into a query
service:

* **Micro-batching** — ``estimate``/``submit`` calls from many threads land
  on a bounded queue; a background batcher thread collects up to
  ``max_batch`` requests (waiting at most ``max_wait_ms`` after the first),
  scores them in **one** batched ``estimate_many`` pass, and scatters the
  per-row results back to per-request futures.  Row ``i`` of a batch is
  bit-equal to the same design scored alone (the array core is row-
  independent; tests/test_serve.py hammers this), so batching is invisible
  to callers except in latency.
* **Fixed-shape chunks on jax-jit** — the jit backend compiles once per
  input shape, so ragged batches are padded the same way the streaming
  engine pads its last chunk (:mod:`repro.core.stream`): the kernel axis is
  padded to ``max_batch`` and the group axis to a power-of-two bucket by
  repeating a real row under a padding kernel id, then the padded tail is
  masked off the scattered results.  A handful of bucket shapes serve every
  request mix.
* **Result caching** — a content-hash LRU (:class:`repro.core.cache.LruCache`,
  keyed on the canonical ``Design`` + hardware + calibration hash) sits in
  front of the batcher, one level above the on-disk HLO-analysis cache of
  :mod:`repro.core.cache`: repeat queries (the advisor steady state) return
  without touching the queue, marked ``Estimate.cached``.  Identical
  designs *in flight* coalesce onto one future, so a miss storm for one hot
  design costs one batch slot.
* **Operability** — ``stats()`` exposes hit/miss/latency counters (p50/p99
  over a sliding window, and the queue wait before a batch takes a
  request), ``close(drain=True)`` performs a graceful drain,
  per-request deadlines fast-fail expired work before scoring, and a full
  queue fast-fails new submissions with :class:`ServerOverloaded` instead
  of building unbounded backlog.

This module is thread-and-stdlib only on top of the numpy core — jax loads
only if the session's backend asks for it.
"""
from __future__ import annotations

import dataclasses
import queue
import statistics
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import TYPE_CHECKING, Sequence

import numpy as np

from repro.core import model_batch as _mb
from repro.core.cache import LruCache, config_hash
from repro.core.spans import span

if TYPE_CHECKING:  # pragma: no cover — import cycle is runtime-lazy
    from repro.api import Design, Estimate, Session


class ServerError(RuntimeError):
    """Base class of serving-layer failures."""


class ServerClosed(ServerError):
    """The server no longer accepts (or could not finish) this request."""


class ServerOverloaded(ServerError):
    """The bounded request queue is full — fast-fail, caller may retry."""


class RequestTimeout(ServerError, TimeoutError):
    """The request's deadline passed before a result was produced."""


@dataclasses.dataclass
class _Request:
    """One queued estimate request (internal currency of the batcher)."""

    design: "Design"
    key: str
    future: Future
    t_enqueue: float
    deadline: float | None        # monotonic seconds; None = no deadline


_SHUTDOWN = object()              # queue sentinel: drain then exit


def _design_key(design: "Design", salt: str) -> str:
    """Canonical content hash of one design under one session context.

    ``name`` participates so coalesced requests always get back a result
    carrying *their* design verbatim; ``flops`` rides along in the repr.
    The session salt folds in hardware, calibration and backend, so one
    server never serves another context's numbers.
    """
    return config_hash({
        "lsus": [repr(l) for l in design.lsus],
        "dram": repr(design.dram), "bsp": repr(design.bsp),
        "f": design.f, "name": design.name, "flops": design.flops,
    }, salt=salt)


def _session_salt(session: "Session") -> str:
    return config_hash({
        "dram": repr(session.dram), "bsp": repr(session.bsp),
        "hw": repr(session.hw), "backend": session.backend,
        "calibration": session.calibration_factor,
        "hardware": repr(session.hardware),
    }, salt="serve-session")


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def pad_group_batch(batch: "_mb.GroupBatch", n_kernels: int, m_groups: int,
                    ) -> "_mb.GroupBatch":
    """Pad a ragged GroupBatch to fixed ``(n_kernels, m_groups)`` shape.

    Padding groups repeat row 0 (a real, finite row — no divide-by-zero
    surprises under jit) but belong to fresh *padding kernels* beyond the
    real ones, so every real kernel's segment sums are untouched and rows
    ``[0, real_n)`` of the padded estimate are bit-equal to the unpadded
    ones.  Mirrors the streaming engine's pad-the-last-chunk trick
    (:func:`repro.core.stream.run_stream`), applied to the request axis.
    """
    m = len(np.asarray(batch.kernel))
    if batch.n_kernels > n_kernels or m > m_groups:
        raise ValueError(
            f"batch ({batch.n_kernels} kernels, {m} groups) exceeds the "
            f"padding target ({n_kernels}, {m_groups})")
    if (batch.n_kernels == n_kernels and m == m_groups) or m == 0:
        return batch        # nothing to pad from (or with): keep as-is
    pad = m_groups - m
    kernel = np.concatenate([
        np.asarray(batch.kernel, dtype=np.int64),
        # spread padding rows over the padding kernels (wrapping) so no
        # padding kernel ever aggregates an outsized segment
        (n_kernels - 1 - (np.arange(pad, dtype=np.int64)
                          % max(1, n_kernels - batch.n_kernels)))
        if pad else np.empty(0, dtype=np.int64)])
    out = {"kernel": kernel, "n_kernels": n_kernels}
    for fld in dataclasses.fields(_mb.GroupBatch):
        if fld.name in out:
            continue
        col = np.asarray(getattr(batch, fld.name))
        out[fld.name] = np.concatenate(
            [col, np.repeat(col[:1], pad, axis=0)]) if pad else col
    return _mb.GroupBatch(**out)


class Server:
    """Concurrent micro-batching front door over one :class:`Session`.

    Build one with ``Session.serve(...)``; use it from any number of
    threads; close it (or use it as a context manager) when done::

        with Session().serve(max_batch=64) as srv:
            est = srv.estimate(design)            # blocking
            fut = srv.submit(design)              # Future[Estimate]
            print(srv.stats()["latency_ms"])

    Results are bit-equal to ``session.estimate(design)`` called serially,
    whatever batch a request lands in (tests/test_serve.py).
    """

    def __init__(self, session: "Session", *, max_batch: int = 64,
                 max_wait_ms: float = 1.0, cache_size: int = 4096,
                 max_queue: int = 1024, timeout_ms: float | None = None,
                 latency_window: int = 4096):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if max_wait_ms < 0:
            raise ValueError("max_wait_ms must be >= 0")
        if max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if timeout_ms is not None and timeout_ms <= 0:
            raise ValueError("timeout_ms must be > 0 (or None)")
        self.session = session
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.timeout_s = None if timeout_ms is None else float(timeout_ms) / 1e3
        self._salt = _session_salt(session)
        self._queue: queue.Queue = queue.Queue(maxsize=int(max_queue))
        self._cache: LruCache = LruCache(int(cache_size))
        self._lock = threading.Lock()
        self._inflight: dict[str, Future] = {}
        # id -> (design, key): advisor clients replay the same Design
        # objects, so skip re-hashing them on the hot path.  The strong ref
        # in the value pins the id for as long as the entry lives, and the
        # `is` check on read makes a stale id harmless either way.
        self._key_memo: dict[int, tuple] = {}
        self._closed = False
        self._latencies: deque[float] = deque(maxlen=int(latency_window))
        self._queue_waits: deque[float] = deque(maxlen=int(latency_window))
        self._counters = {
            "submitted": 0, "served": 0, "errors": 0, "coalesced": 0,
            "rejected_overload": 0, "expired": 0, "batches": 0,
            "batched_requests": 0, "max_batch_seen": 0,
        }
        self._thread = threading.Thread(
            target=self._batcher, name="repro-serve-batcher", daemon=True)
        self._thread.start()

    # -- client surface -----------------------------------------------------

    def submit(self, design: "Design",
               timeout_ms: float | None = None) -> Future:
        """Enqueue one design; returns a ``Future[Estimate]``.

        Fast paths: a cache hit resolves immediately without touching the
        queue; an identical design already in flight shares that request's
        future.  A full queue raises :class:`ServerOverloaded` *now* (the
        fast-fail overload policy) rather than queueing unboundedly.
        """
        if self._closed:
            raise ServerClosed("server is closed")
        memo = self._key_memo.get(id(design))
        if memo is not None and memo[0] is design:
            key = memo[1]
        else:
            key = _design_key(design, self._salt)
            if len(self._key_memo) >= 4 * self._cache.capacity + 64:
                self._key_memo.clear()
            self._key_memo[id(design)] = (design, key)
        t0 = time.monotonic()
        with self._lock:
            self._counters["submitted"] += 1
            hit = self._cache.get(key)
            if hit is not None:
                fut: Future = Future()
                fut.set_result(self._as_cached(hit, design))
                self._latencies.append(time.monotonic() - t0)
                self._counters["served"] += 1
                return fut
            shared = self._inflight.get(key)
            if shared is not None:
                self._counters["coalesced"] += 1
                return shared
            fut = Future()
            self._inflight[key] = fut
        t = timeout_ms if timeout_ms is not None else (
            None if self.timeout_s is None else self.timeout_s * 1e3)
        req = _Request(design=design, key=key, future=fut, t_enqueue=t0,
                       deadline=None if t is None else t0 + t / 1e3)
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            with self._lock:
                self._counters["rejected_overload"] += 1
                self._inflight.pop(key, None)
            raise ServerOverloaded(
                f"request queue full ({self._queue.maxsize} pending); "
                f"retry later or raise max_queue") from None
        return fut

    def estimate(self, design: "Design", *,
                 timeout_ms: float | None = None) -> "Estimate":
        """Blocking estimate through the batcher (the advisor entry point).

        ``timeout_ms`` (or the server default) bounds the wait; expiry
        raises :class:`RequestTimeout`.  The result is bit-equal to
        ``self.session.estimate(design)``.
        """
        fut = self.submit(design, timeout_ms=timeout_ms)
        t = timeout_ms if timeout_ms is not None else (
            None if self.timeout_s is None else self.timeout_s * 1e3)
        try:
            return fut.result(timeout=None if t is None else t / 1e3)
        # pre-3.11 concurrent.futures.TimeoutError is not the builtin one
        except (TimeoutError, _FutureTimeout):
            raise RequestTimeout(
                f"no result within {t:.1f} ms (queue depth "
                f"{self._queue.qsize()})") from None

    def predict(self, hlo_text: str, cost: dict | None = None, *,
                gather_row_bytes: float = 512.0):
        """Cached TPU-transplant step prediction (``Session.predict``).

        Predictions are pure in (hlo_text, cost, hw), so they share the
        server's LRU under a distinct key prefix; the heavy HLO parse runs
        at most once per unique executable text.
        """
        key = config_hash({"hlo": hlo_text, "cost": cost,
                           "gather_row_bytes": gather_row_bytes},
                          salt="predict-" + self._salt)
        with self._lock:
            hit = self._cache.get(key)
        if hit is not None:
            return hit
        out = self.session.predict(hlo_text, cost,
                                   gather_row_bytes=gather_row_bytes)
        with self._lock:
            self._cache.put(key, out)
        return out

    def sweep(self, space=None, *, chunk_size: int | None = None,
              reducers=None, executor: str = "threads",
              workers: int | None = None, **axes):
        """Design-space sweep behind the serving front door.

        Same calling surface as :meth:`repro.api.Session.sweep` (including
        ``executor="processes"`` for the coordinator/worker pool), plus the
        server's result cache: a grid space canonicalizes to its
        :class:`~repro.core.stream.SweepPlan` JSON, so repeat queries for
        the same space under the same session context return the finished
        :class:`~repro.api.SweepReport` without re-scoring, and identical
        sweeps *in flight* coalesce onto one run.  Custom ``reducers``
        (mutable instances) and ``Space.random`` spaces run uncached.
        """
        if self._closed:
            raise ServerClosed("server is closed")
        sp = self.session._as_space(space, axes)

        def run():
            return self.session.sweep(sp, chunk_size=chunk_size,
                                      reducers=reducers, workers=workers,
                                      executor=executor)

        if reducers is not None:
            return run()        # reducer instances carry uncanonical state
        try:
            plan = self.session.plan(sp, chunk_size=chunk_size)
        except TypeError:
            return run()        # non-grid space: no canonical plan to key on
        # Streaming and materialized reports answer different queries (held
        # rows vs the whole space), so the mode is part of the key even
        # though it never changes the numbers.
        streaming = (chunk_size is not None or sp.chunk_size is not None
                     or workers is not None or executor == "processes")
        key = config_hash({"plan": plan.to_json(), "streaming": streaming},
                          salt="sweep-" + self._salt)
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None:
                return hit
            shared = self._inflight.get(key)
            if shared is None:
                fut: Future = Future()
                self._inflight[key] = fut
        if shared is not None:
            with self._lock:
                self._counters["coalesced"] += 1
            return shared.result()
        try:
            report = run()
        except BaseException as exc:
            with self._lock:
                if self._inflight.get(key) is fut:
                    self._inflight.pop(key, None)
            fut.set_exception(exc)
            raise
        with self._lock:
            self._cache.put(key, report)
            if self._inflight.get(key) is fut:
                self._inflight.pop(key, None)
        fut.set_result(report)
        return report

    # -- lifecycle ----------------------------------------------------------

    def drain(self, timeout_s: float | None = None) -> None:
        """Block until every queued request has been scored."""
        deadline = None if timeout_s is None else time.monotonic() + timeout_s
        while not self._queue.empty() or self._inflight:
            if deadline is not None and time.monotonic() > deadline:
                raise RequestTimeout(
                    f"drain incomplete after {timeout_s:.1f}s "
                    f"(queue depth {self._queue.qsize()})")
            time.sleep(0.5e-3)

    def close(self, *, drain: bool = True, timeout_s: float = 30.0) -> None:
        """Stop accepting requests and shut the batcher down.

        ``drain=True`` (graceful) scores everything already queued first;
        ``drain=False`` fails pending futures with :class:`ServerClosed`.
        Idempotent; also runs on ``__exit__``.
        """
        if self._closed:
            return
        self._closed = True
        if not drain:
            # pull whatever is still queued and fail it
            while True:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    break
                if req is not _SHUTDOWN:
                    self._fail(req, ServerClosed("server closed before "
                                                 "this request was scored"))
        self._queue.put(_SHUTDOWN)
        self._thread.join(timeout=timeout_s)
        with self._lock:
            pending = list(self._inflight.values())
            self._inflight.clear()
        for fut in pending:
            if not fut.done():
                fut.set_exception(ServerClosed("server closed"))

    def __enter__(self) -> "Server":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=exc == (None, None, None))

    @property
    def closed(self) -> bool:
        return self._closed

    # -- telemetry ----------------------------------------------------------

    def stats(self) -> dict:
        """Hit/miss/latency counters (one consistent snapshot).

        ``latency_ms`` summarizes the last ``latency_window`` completed
        requests (submit -> result, cache hits included): p50/p99/mean.
        ``queue_wait_ms`` summarizes the last ``latency_window`` requests
        a batch took (submit -> the start of that batch): n/p50/p99.
        """
        with self._lock:
            lat = sorted(self._latencies)
            waits = sorted(self._queue_waits)
            counters = dict(self._counters)
            cache = self._cache.stats()
        n = len(lat)

        def pct(xs, q):
            return (xs[min(len(xs) - 1, int(q * (len(xs) - 1) + 0.999999))]
                    * 1e3 if xs else 0.0)

        served = max(1, counters["served"])
        return {
            **counters,
            "queue_depth": self._queue.qsize(),
            "inflight": len(self._inflight),
            "cache": cache,
            "cache_hit_rate": cache["hits"] / max(1, cache["hits"]
                                                  + cache["misses"]),
            "mean_batch": counters["batched_requests"] / max(
                1, counters["batches"]),
            "latency_ms": {
                "n": n,
                "p50": statistics.median(lat) * 1e3 if n else 0.0,
                "p99": pct(lat, 0.99),
                "mean": sum(lat) / n * 1e3 if n else 0.0,
            },
            "queue_wait_ms": {
                "n": len(waits),
                "p50": statistics.median(waits) * 1e3 if waits else 0.0,
                "p99": pct(waits, 0.99),
            },
            "served_per_batch": counters["served"] / max(
                1, counters["batches"]) if counters["batches"] else 0.0,
            "error_rate": counters["errors"] / served,
        }

    # -- batcher ------------------------------------------------------------

    def _collect(self) -> "list[_Request] | None":
        """Block for the first request, then fill the batch.

        Everything already queued is drained immediately; only a *partial*
        batch then lingers up to ``max_wait_ms`` for stragglers, so a lone
        request never waits longer than the window and a hot queue never
        waits at all.  Returns ``None`` on shutdown (after requeueing the
        sentinel so the drain path still scores what it collected).
        """
        try:
            first = self._queue.get()
        except (OSError, ValueError):  # pragma: no cover — interpreter exit
            return None
        if first is _SHUTDOWN:
            return None
        batch = [first]
        deadline = time.monotonic() + self.max_wait_s
        while len(batch) < self.max_batch:
            try:
                nxt = self._queue.get_nowait()
            except queue.Empty:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
            if nxt is _SHUTDOWN:
                self._queue.put(_SHUTDOWN)     # keep the signal for the loop
                break
            batch.append(nxt)
        return batch

    def _batcher(self) -> None:
        while True:
            with span("serve.collect"):
                batch = self._collect()
            if batch is None:
                return
            now = time.monotonic()
            with self._lock:
                self._queue_waits.extend(now - r.t_enqueue for r in batch)
            live: list[_Request] = []
            for req in batch:
                if req.deadline is not None and now > req.deadline:
                    self._fail(req, RequestTimeout(
                        "request expired in queue before scoring"))
                    with self._lock:
                        self._counters["expired"] += 1
                else:
                    live.append(req)
            if not live:
                continue
            try:
                results = self._score([r.design for r in live])
            except BaseException as exc:  # noqa: BLE001 — fail the batch, not the thread
                for req in live:
                    self._fail(req, exc)
                continue
            with span("serve.scatter"):
                now = time.monotonic()
                with self._lock:
                    self._counters["batches"] += 1
                    self._counters["batched_requests"] += len(live)
                    self._counters["max_batch_seen"] = max(
                        self._counters["max_batch_seen"], len(live))
                    for req, est in zip(live, results):
                        self._cache.put(req.key, est)
                        self._inflight.pop(req.key, None)
                        self._latencies.append(now - req.t_enqueue)
                        self._counters["served"] += 1
                for req, est in zip(live, results):
                    req.future.set_result(est)

    def _score(self, designs: "Sequence[Design]") -> "list[Estimate]":
        """One batched scoring pass (the only caller of the estimator).

        On the jax-jit backend the ragged design batch is padded to a fixed
        ``(max_batch, group-bucket)`` shape first so the jit core compiles
        once per bucket, like the streaming engine's fixed-shape chunks.
        Spans (:mod:`repro.core.spans`): ``serve.assemble`` (the
        ``GroupBatch`` and its padding), ``serve.score`` and
        ``serve.scatter`` (the rows back out); the batcher adds
        ``serve.collect`` and the futures' ``serve.scatter``.
        """
        if self.session.backend == "scalar":
            with span("serve.score"):
                return self.session.estimate_many(list(designs))
        with span("serve.assemble"):
            batch = self.session._batch_for(designs)
            if self.session.backend == "jax-jit":
                m = len(np.asarray(batch.kernel))
                batch = pad_group_batch(
                    batch, self.max_batch + 1,  # +1: a home for padding groups
                    _next_pow2(max(m, self.max_batch)))
        with span("serve.score"):
            est = _mb.batch_estimator(self.session.backend)(batch)
        with span("serve.scatter"):
            return self.session._rows_from(est, designs)

    # -- helpers ------------------------------------------------------------

    @staticmethod
    def _as_cached(est: "Estimate", design: "Design") -> "Estimate":
        return dataclasses.replace(est, design=design, cached=True)

    def _fail(self, req: _Request, exc: BaseException) -> None:
        with self._lock:
            self._counters["errors"] += 1
            cur = self._inflight.get(req.key)
            if cur is req.future:
                self._inflight.pop(req.key, None)
        if not req.future.done():
            req.future.set_exception(exc)
