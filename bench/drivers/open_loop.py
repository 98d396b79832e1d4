"""Open-loop single-design queries against ``Session.serve()``.

One client thread sends each query at its due time, whatever the server's
state, through the non-blocking ``submit``; each answer is timed from the
query's due time to the moment its future completes, so a stall is charged
to every query it delays.  Queries refused at submission count as failed.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench import compare, generator, stats


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro import Session

        self.config, self.traffic, self.seed = config, traffic, seed
        self.session = Session(backend="jax-jit")
        self.specs: list[dict] = []
        self.designs: list = []
        self.due = np.empty(0)
        self.answers: dict = {}
        self.server = None

    def _design(self, spec: dict):
        from repro import Design
        from repro.core import LsuType
        from repro.core.fpga import BspParams, DramParams

        dram = DramParams(**self.config["drams"][spec["dram"]])
        bsp = BspParams(**self.config["bsps"][spec["bsp"]])
        if "app" in spec:
            name = self.config["apps"][spec["app"]]["name"]
            return Design.from_app(name, spec["n_elems"], dram=dram, bsp=bsp)
        return Design.microbench(
            LsuType(spec["lsu_type"]), n_ga=spec["n_ga"], simd=spec["simd"],
            n_elems=spec["n_elems"], delta=spec["delta"],
            elem_bytes=spec["elem_bytes"],
            include_write=spec["include_write"],
            val_constant=spec["val_constant"], dram=dram, bsp=bsp)

    def build(self, seconds: float) -> None:
        self.specs, self.due = generator.open_loop(
            self.config, self.traffic, self.seed, seconds)
        self.designs = [self._design(s) for s in self.specs]

    def warm_up(self) -> None:
        """Compile every padded (max_batch + 1, power-of-two groups) shape
        the traffic can reach: one burst per shape, sent to a server that
        lingers long enough to batch the whole burst.  The bursts are
        aligned microbenchmarks of ``bucket / max_batch`` LSUs each, named
        apart from every query of the window."""
        from repro import Design
        from repro.core import LsuType

        cfg = self.config["server"]
        batch = cfg["max_batch"]
        top = _pow2(batch * generator.max_groups(self.config))
        with self.session.serve(**{**cfg, "max_wait_ms": 500.0}) as warm:
            bucket = _pow2(batch)
            while bucket <= top:
                per = bucket // batch        # groups per design
                burst = [Design.microbench(
                    LsuType.BC_ALIGNED, n_ga=max(1, per - 1), simd=1,
                    n_elems=1 << 10, include_write=per > 1,
                    name=f"warm-{bucket}-{k}")
                    for k in range(batch if per > 1 else 1)]
                for f in [warm.submit(d) for d in burst]:
                    f.result()
                bucket *= 2
        self.server = self.session.serve(**cfg)

    def window(self, seconds: float, profile: bool, annotate) -> dict:
        from repro.core.serving import ServerOverloaded

        srv = self.server
        n = len(self.designs)
        done = np.full(n, np.nan)
        sent = np.full(n, np.nan)
        refused = np.zeros(n, dtype=bool)
        futures: dict = {}
        finished = threading.Semaphore(0)

        def on_done(i):
            def cb(_fut):
                done[i] = time.perf_counter()
                finished.release()
            return cb

        before = srv.stats()
        t0 = time.perf_counter() + 0.01
        with annotate("bench.clients"):
            for i in range(n):
                due = t0 + self.due[i]
                now = time.perf_counter()
                if now < due:
                    time.sleep(due - now)
                    now = time.perf_counter()
                sent[i] = now
                try:
                    fut = srv.submit(self.designs[i])
                except ServerOverloaded:
                    refused[i] = True
                    continue
                futures[i] = fut
                fut.add_done_callback(on_done(i))
        closed = time.perf_counter()
        deadline = closed + self.traffic["drain_s"]
        for _ in futures:
            if not finished.acquire(timeout=max(0.0, deadline
                                                - time.perf_counter())):
                break
        after = srv.stats()
        errors = 0
        for i, fut in futures.items():
            if fut.done() and fut.exception() is None:
                self.answers[i] = fut.result()
            elif fut.done():
                errors += 1
        self.unanswered = sum(1 for f in futures.values() if not f.done())
        ok = np.asarray(sorted(self.answers), dtype=np.int64)
        due = t0 + self.due
        latency_ms = (done[ok] - due[ok]) * 1e3
        lag_ms = (sent - due)[~np.isnan(sent)] * 1e3
        batches = after["batches"] - before["batches"]
        scored = after["batched_requests"] - before["batched_requests"]
        return {
            "attempted": n,
            "failed": int(refused.sum()) + errors + self.unanswered,
            "metrics": {"query_p99_ms": stats.percentile(latency_ms, 99)},
            "layer": {"kind": "open_loop", "requests": scored,
                      "mean_batch": scored / batches if batches else None,
                      "lag_ms": lag_ms},
            "log": {"answered": len(ok), "refused": int(refused.sum()),
                    "errors": errors, "unanswered": self.unanswered,
                    "p50_ms": stats.percentile(latency_ms, 50),
                    "lag_p99_ms": stats.percentile(lag_ms, 99),
                    "send_s": closed - t0,
                    "drain_ms": (np.nanmax(done) - closed) * 1e3
                    if len(ok) else None,
                    "mean_batch":
                        scored / batches if batches else None},
        }

    def close(self) -> None:
        if self.server is not None:
            self.server.close()
        self.designs = []

    def check(self) -> dict:
        return compare.advisor_numbers(self.config, self.specs, self.answers,
                                       self.unanswered)
