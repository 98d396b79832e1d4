"""Design-space sweep benchmarks: batched vs scalar, streaming vs materialized.

The paper's value proposition is exploration speed; this benchmark measures
it twice over:

* ``sweep_speedup`` scores the same >= 10k-point design space per point
  through ``Session(backend="scalar")`` and through the batched
  ``Session.sweep``, verifies element-wise agreement, and reports the
  speedup plus the Pareto front of the space.
* ``stream_bench`` sweeps a >= 1M-point grid through the bounded-memory
  streaming engine on each backend (points/sec + peak RSS per backend) and
  against the legacy materialize-everything workflow — materialize the full
  grid, then run the pre-streaming scan-based Pareto front, a full-sort
  top-k and the summary — verifying that front membership, top-k rows and
  summary stats agree to 1e-6.

Run:  python -m benchmarks.sweep_bench  (or via benchmarks/run.py [--smoke])
"""
from __future__ import annotations

import time

import numpy as np

from repro import Design, Session, Space
from repro.core import DDR4_1866, DDR4_2666, LsuType, STRATIX10_BSP
from repro.core.fpga import BspParams
from repro.core.sweep import SweepResult, _pareto_scan

#: >= 10k-point space over every GMI LSU type, LSU count, SIMD width, input
#: size, stride, write inclusion, DRAM part and BSP variant.
FULL_AXES = dict(
    lsu_type=[LsuType.BC_ALIGNED, LsuType.BC_NON_ALIGNED,
              LsuType.BC_WRITE_ACK, LsuType.ATOMIC_PIPELINED],
    n_ga=[1, 2, 3, 4, 5],
    simd=[1, 2, 4, 8, 16],
    n_elems=[1 << 12, 1 << 14, 1 << 16, 1 << 18],
    delta=[1, 2, 3, 5, 7],
    include_write=[False, True],
    dram=[DDR4_1866, DDR4_2666],
    bsp=[STRATIX10_BSP, BspParams(burst_cnt=5, max_th=64)],
)

SMOKE_AXES = dict(
    lsu_type=[LsuType.BC_ALIGNED, LsuType.BC_NON_ALIGNED,
              LsuType.BC_WRITE_ACK, LsuType.ATOMIC_PIPELINED],
    n_ga=[1, 2, 4],
    simd=[1, 4, 16],
    n_elems=[1 << 14, 1 << 18],
    delta=[1, 2, 7],
    dram=[DDR4_1866, DDR4_2666],
)

#: 4*10*5*8*20*2*2*2*2*2 = 1,024,000-point grid for the streaming
#: benchmark (every simd value divides every n_elems value, as the engine
#: requires).
STREAM_AXES = dict(
    lsu_type=[LsuType.BC_ALIGNED, LsuType.BC_NON_ALIGNED,
              LsuType.BC_WRITE_ACK, LsuType.ATOMIC_PIPELINED],
    n_ga=list(range(1, 11)),
    simd=[1, 2, 4, 8, 16],
    n_elems=[1 << e for e in range(14, 22)],
    delta=list(range(1, 21)),
    include_write=[False, True],
    val_constant=[False, True],
    elem_bytes=[4, 8],
    dram=[DDR4_1866, DDR4_2666],
    bsp=[STRATIX10_BSP, BspParams(burst_cnt=5, max_th=64)],
)

#: 10,240,000-point grid (STREAM_AXES with n_ga widened to 1..100) for the
#: device-pipeline scale benchmark.  Materializing this space is off the
#: table (~GBs of columns), so ``stream10_bench`` checks the two streaming
#: backends against *each other* instead of a materialized baseline.
STREAM10_AXES = dict(STREAM_AXES, n_ga=list(range(1, 101)))

#: Named streaming grids the subprocess workers can rebuild by name.
STREAM_GRIDS = {"1m": STREAM_AXES, "10m": STREAM10_AXES}


def scalar_loop(res: SweepResult, session: Session | None = None) -> np.ndarray:
    """Score every point of ``res``'s design space with the scalar path."""
    P = res.points
    out = np.empty(res.n_points)
    sess = (session or Session()).with_backend("scalar")
    for i in range(res.n_points):
        design = Design.microbench(
            P["lsu_type"][i],
            n_ga=int(P["n_ga"][i]),
            simd=int(P["simd"][i]),
            n_elems=int(P["n_elems"][i]),
            delta=int(P["delta"][i]),
            elem_bytes=int(P["elem_bytes"][i]),
            include_write=bool(P["include_write"][i]),
            val_constant=bool(P["val_constant"][i]),
            dram=P["dram"][i], bsp=P["bsp"][i],
        )
        out[i] = sess.estimate(design).t_exe
    return out


def sweep_speedup(axes: dict | None = None, *,
                  session: Session | None = None) -> list[dict]:
    """One-row summary: points, batched/scalar wall time, speedup, fidelity.

    ``session`` selects the hardware context (e.g. built from a ``--hw``
    registry name); the default board otherwise.  A session carrying a
    hardware spec pins the memory system, so the explicit dram/bsp axes are
    dropped in its favor.
    """
    sess = (session or Session()).with_backend("numpy-batch")
    axes = dict(axes or FULL_AXES)
    if sess.hardware is not None:
        axes.pop("dram", None)
        axes.pop("bsp", None)
    space = Space.grid(**axes)
    t_batch = float("inf")          # min-of-3 damps first-call warmup costs
    for _ in range(3):
        t0 = time.perf_counter()
        res = sess.sweep(space)
        t_batch = min(t_batch, time.perf_counter() - t0)

    t0 = time.perf_counter()
    scalar = scalar_loop(res, session)
    t_scalar = time.perf_counter() - t0

    agree = bool(np.allclose(scalar, res.t_exe, rtol=1e-6, atol=0.0))
    max_rel = float(np.max(np.abs(scalar - res.t_exe)
                           / np.maximum(np.abs(scalar), 1e-300)))
    front = res.pareto()
    return [{
        "n_points": res.n_points,
        "batched_ms": round(t_batch * 1e3, 3),
        "scalar_ms": round(t_scalar * 1e3, 3),
        "speedup": round(t_scalar / t_batch, 1),
        "agree_rtol_1e6": agree,
        "max_rel_err": f"{max_rel:.2e}",
        "pareto_points": int(len(front)),
        "memory_bound_points": int(res.memory_bound.sum()),
    }]


def _peak_rss_mb() -> float:
    """Process high-water RSS in MB.

    ``ru_maxrss`` is a process-*lifetime* high-water mark, which is why
    ``stream_bench`` runs each streaming backend in its own subprocess:
    measured in-process, every run after the first would report the
    earlier run's peak.

    On Linux, prefer ``VmHWM`` from /proc/self/status: ``ru_maxrss`` also
    folds in the watermark of the pre-exec address space, so a worker
    forked from a parent that has already ballooned (e.g. the materialized
    1M baseline) would inherit the parent's peak.  ``VmHWM`` tracks the
    current mm only, which is fresh after exec.
    """
    import resource
    import sys

    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return rss / (1 << 20) if sys.platform == "darwin" else rss / 1024.0


def _stream_axes_for(session: Session, grid: str = "1m") -> dict:
    axes = dict(STREAM_GRIDS[grid])
    if session.hardware is not None:    # --hw pins the memory system
        axes.pop("dram", None)
        axes.pop("bsp", None)
    return axes


def _stream_once(sess: Session, axes: dict, chunk_size: int, k: int) -> dict:
    """One warmed, timed streaming sweep -> JSON-able result record.

    The warmup sweeps a one-point grid first: the engine pads every chunk
    to ``chunk_size``, so this compiles the jax-jit chunk executable at
    exactly the shape the timed run reuses — the timed numbers are
    steady-state throughput, not one-time jit compilation.
    """
    from repro.core.stream import default_reducers
    from repro.core.sweep import _as_list

    space = Space.grid(**axes)
    warmup = Space.grid(**{name: _as_list(v)[:1] for name, v in axes.items()})
    sess.sweep(warmup, chunk_size=chunk_size)
    t0 = time.perf_counter()
    rep = sess.sweep(space, chunk_size=chunk_size,
                     reducers=default_reducers(k))
    dt = time.perf_counter() - t0
    return {
        "n_points": rep.n_points,
        "seconds": dt,
        "peak_rss_mb": _peak_rss_mb(),
        "front_ids": np.sort(
            np.asarray(rep.point_ids)[rep.pareto()]).tolist(),
        "top_rows": rep.top_k(k),
        "stats": {
            "n_points": rep.stats["n_points"],
            "memory_bound_points": rep.stats["memory_bound_points"],
            "t_exe_min": rep.stats["t_exe_min"],
        },
    }


def _stream_worker(backend: str, chunk_size: int, k: int,
                   hw_name: str, grid: str = "1m") -> None:
    """Subprocess entry: run one backend's streaming sweep, print JSON."""
    import json

    sess = Session()
    if hw_name != "-":
        import repro.hw as hwreg

        sess = sess.with_hardware(hwreg.get(hw_name))
    rec = _stream_once(sess.with_backend(backend),
                       _stream_axes_for(sess, grid), chunk_size, k)
    print(json.dumps(rec))


def _parent_holds_device() -> bool:
    """Whether this process has already opened a non-CPU jax backend.

    An accelerator serves one process at a time, so a child that needs it
    would then fail or hang.
    """
    import jax
    from jax._src import xla_bridge

    return (xla_bridge.backends_are_initialized()
            and jax.default_backend() != "cpu")


def _run_stream_worker(backend: str, chunk_size: int, k: int,
                       hw_name: str, grid: str = "1m") -> dict:
    import json
    import os
    import pathlib
    import subprocess
    import sys

    if backend == "jax-jit" and _parent_holds_device():
        raise RuntimeError(
            "this process already holds the accelerator, so the jax-jit "
            "stream worker could not open it; run the streaming benchmarks "
            "before anything that touches jax in this process")
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(root / "src"), env.get("PYTHONPATH")) if p)
    # propagate -W flags (CI runs under -W error::DeprecationWarning; the
    # worker must keep proving the streaming path never hits a shim)
    warn_args = [a for opt in sys.warnoptions for a in ("-W", opt)]
    out = subprocess.run(
        [sys.executable, *warn_args, "-m", "benchmarks.sweep_bench",
         "--stream-worker", backend, str(chunk_size), str(k), hw_name, grid],
        capture_output=True, text=True, cwd=root, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"stream worker {backend} failed:\n"
                           f"{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _rows_close(a: list[dict], b: list[dict], rtol: float = 1e-6) -> bool:
    """Row-dict equality with ``rtol`` on float fields, exact elsewhere."""
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if set(ra) != set(rb):
            return False
        for key, va in ra.items():
            vb = rb[key]
            if isinstance(va, float) and isinstance(vb, float):
                if va != vb and abs(va - vb) > rtol * max(abs(va), abs(vb)):
                    return False
            elif va != vb:
                return False
    return True


def stream_bench(axes: dict | None = None, *, chunk_size: int = 1 << 17,
                 backends=("numpy-batch", "jax-jit"), k: int = 10,
                 session: Session | None = None) -> list[dict]:
    """Per-backend streaming throughput vs the materialize-everything path.

    For each backend: one streaming sweep of the >= 1M-point grid
    (points/sec, peak RSS) — in its *own subprocess* so peak RSS is that
    backend's, not the process high-water of whatever ran first (custom
    ``axes``/non-registry hardware fall back to in-process, where only the
    first backend's RSS is uncontaminated).  Then the legacy workflow once
    — materialize the whole space, scan-based Pareto front (the
    pre-streaming ``_pareto_scan``), full-sort top-k, summary — as the
    speedup baseline.  ``agree_1e6`` requires front *membership* to match
    exactly (the backends are bit-equal by construction, tested in
    tests/test_stream.py) and top-k row floats / ``t_exe_min`` to agree
    within rtol 1e-6.
    """
    sess0 = session or Session()
    hw_name = sess0.hardware.name if sess0.hardware is not None else "-"
    # Workers rebuild the session from scratch, so isolation is only sound
    # when this session *is* exactly what the worker would rebuild — the
    # default session, or one derived purely from a registered hardware
    # spec.  A calibrated or hand-tuned session falls back to in-process
    # (where only the first backend's RSS reading is uncontaminated).
    import repro.hw as hwreg

    if hw_name != "-":
        reconstructable = (_hw_registered(hw_name)
                           and sess0 == Session().with_hardware(
                               hwreg.get(hw_name)))
    else:
        reconstructable = sess0 == Session()
    isolate = axes is None and reconstructable
    axes = dict(axes) if axes is not None else _stream_axes_for(sess0)

    streamed: dict[str, dict] = {}
    for b in backends:
        if isolate:
            streamed[b] = _run_stream_worker(b, chunk_size, k, hw_name)
        else:
            streamed[b] = _stream_once(sess0.with_backend(b), axes,
                                       chunk_size, k)

    # Legacy baseline: materialize everything, then select.  (Runs after
    # the streaming measurements so the in-process fallback's first RSS
    # reading is still meaningful.)
    t0 = time.perf_counter()
    mat = sess0.with_backend("numpy-batch").sweep(Space.grid(**axes))
    front_ids = _pareto_scan(np.stack(
        [np.asarray(mat.t_exe), np.asarray(mat.resource)], axis=1))
    top_rows = mat.top_k(k)
    base_stats = {
        "n_points": mat.n_points,
        "memory_bound_points": int(np.asarray(mat.memory_bound).sum()),
        "t_exe_min": float(np.min(mat.t_exe)),
    }
    dt_base = time.perf_counter() - t0
    base_rss = _peak_rss_mb()
    n = mat.n_points

    rows = []
    for b, rec in streamed.items():
        st = rec["stats"]
        agree = (
            rec["front_ids"] == front_ids.tolist()
            and _rows_close(rec["top_rows"], top_rows)
            and st["n_points"] == base_stats["n_points"]
            and st["memory_bound_points"] == base_stats["memory_bound_points"]
            and abs(st["t_exe_min"] - base_stats["t_exe_min"])
                <= 1e-6 * base_stats["t_exe_min"]
        )
        rows.append({
            "backend": b,
            "n_points": n,
            "chunk_size": chunk_size,
            "seconds": round(rec["seconds"], 3),
            "points_per_sec": round(n / rec["seconds"], 1),
            "peak_rss_mb": round(rec["peak_rss_mb"], 1),
            "speedup_vs_materialized": round(dt_base / rec["seconds"], 2),
            "agree_1e6": bool(agree),
        })
    rows.append({
        "backend": "materialized-baseline",
        "n_points": n,
        "chunk_size": 0,
        "seconds": round(dt_base, 3),
        "points_per_sec": round(n / dt_base, 1),
        "peak_rss_mb": round(base_rss, 1),
        "speedup_vs_materialized": 1.0,
        "agree_1e6": True,
    })
    return rows


def stream10_bench(*, chunk_size: int = 1 << 17, k: int = 10,
                   backends=("jax-jit", "numpy-batch"),
                   session: Session | None = None) -> list[dict]:
    """Device-pipeline scale benchmark: 10,240,000 points, no materialization.

    Streams :data:`STREAM10_AXES` through the device-resident jax-jit
    pipeline and the numpy-batch host fold (each in its own subprocess, for
    the same peak-RSS isolation reasons as ``stream_bench``).  The grid is
    10x too large to materialize as the agreement reference, so the two
    backends are checked against *each other*: ``agree_device_host`` on the
    jax-jit row requires Pareto-front membership to match the host fold
    exactly and top-k rows / ``t_exe_min`` to agree within rtol 1e-6 (the
    folds are bit-equal by contract — tests/test_device_stream.py — so the
    tolerance only absorbs jit fusion reassociation, e.g. FMA contraction).
    bench_gate.py fails the build unconditionally on a false flag.
    """
    sess0 = session or Session()
    hw_name = sess0.hardware.name if sess0.hardware is not None else "-"
    import repro.hw as hwreg

    if hw_name != "-":
        reconstructable = (_hw_registered(hw_name)
                           and sess0 == Session().with_hardware(
                               hwreg.get(hw_name)))
    else:
        reconstructable = sess0 == Session()
    axes = _stream_axes_for(sess0, "10m")

    streamed: dict[str, dict] = {}
    for b in backends:
        if reconstructable:
            streamed[b] = _run_stream_worker(b, chunk_size, k, hw_name,
                                             grid="10m")
        else:
            streamed[b] = _stream_once(sess0.with_backend(b), axes,
                                       chunk_size, k)

    # numpy-batch is the host reference every other backend must agree with.
    ref = streamed["numpy-batch"]
    rows = []
    for b, rec in streamed.items():
        st, rst = rec["stats"], ref["stats"]
        agree = (
            rec["front_ids"] == ref["front_ids"]
            and _rows_close(rec["top_rows"], ref["top_rows"])
            and st["n_points"] == rst["n_points"]
            and st["memory_bound_points"] == rst["memory_bound_points"]
            and abs(st["t_exe_min"] - rst["t_exe_min"])
                <= 1e-6 * abs(rst["t_exe_min"])
        )
        rows.append({
            "backend": b,
            "n_points": rec["n_points"],
            "chunk_size": chunk_size,
            "seconds": round(rec["seconds"], 3),
            "points_per_sec": round(rec["n_points"] / rec["seconds"], 1),
            "peak_rss_mb": round(rec["peak_rss_mb"], 1),
            "speedup_vs_host": round(ref["seconds"] / rec["seconds"], 2),
            "agree_device_host": bool(agree),
        })
    return rows


def optimize_1m(axes: dict | None = None, *, max_evals: int | None = None,
                seed: int = 0, chunk_size: int = 1 << 17, k: int = 10,
                session: Session | None = None) -> list[dict]:
    """``Session.optimize`` vs the exhaustive 1,024,000-point grid.

    Runs the full streaming sweep once (the ground truth: exact t_exe
    minimum + the (t_exe, resource) Pareto front), then the gradient-based
    optimizer in 2-objective mode, and reports whether the optimizer's
    best point *bit-matches* the grid optimum, what fraction of the
    reference front it recovered exactly, and how many model evaluations
    it paid — the telemetry behind the <1%-of-points claim the CI gate
    enforces.  Both paths score through the identical plan evaluator, so
    "match" means float64 bit-equality, not a tolerance.
    """
    from repro.core.stream import ParetoReducer, StatsReducer, default_reducers

    sess = (session or Session()).with_backend("numpy-batch")
    axes = dict(axes) if axes is not None else _stream_axes_for(sess)
    space = Space.grid(**axes)

    t0 = time.perf_counter()
    full = sess.sweep(space, chunk_size=chunk_size,
                      reducers=default_reducers(k))
    dt_full = time.perf_counter() - t0
    n = full.stats["n_points"]
    ref_min = full.stats["t_exe_min"]
    fr = full.pareto()
    ref_front = {(float(np.asarray(full.estimate.t_exe)[i]),
                  float(np.asarray(full.resource)[i])) for i in fr}

    t0 = time.perf_counter()
    rep = sess.optimize(space, objective=("t_exe", "resource"),
                        max_evals=max_evals, seed=seed)
    dt_opt = time.perf_counter() - t0

    got_front = {(float(rep.front["t_exe"][i]),
                  float(rep.front["resource"][i]))
                 for i in range(rep.n_front)}
    recall = len(ref_front & got_front) / max(1, len(ref_front))
    return [{
        "n_points": n,
        "n_evals": rep.n_evals,
        "n_grid_evals": rep.n_grid_evals,
        "n_relaxed_evals": rep.n_relaxed_evals,
        "evals_fraction": round(rep.evals_fraction, 6),
        "seconds": round(dt_opt, 3),
        "full_grid_seconds": round(dt_full, 3),
        "speedup_vs_full_grid": round(dt_full / dt_opt, 2),
        "matched_optimum": bool(rep.best.t_exe == ref_min),
        "front_recall": round(recall, 4),
        "ref_front_size": len(ref_front),
        "opt_front_size": rep.n_front,
    }]


def _hw_registered(name: str) -> bool:
    import repro.hw as hwreg

    return name in hwreg.names()


# -- distributed streaming (coordinator/worker process pool) ----------------

def _cpus() -> int:
    import os

    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:      # pragma: no cover — non-Linux
        return os.cpu_count() or 1


def _dist_once(sess: Session, axes: dict, workers: int, chunk_size: int,
               k: int) -> dict:
    """One warmed, timed ``executor='processes'`` sweep -> result record.

    The warmup sweeps a one-point grid through the same executor so the
    timed run excludes nothing but steady-state work (spawn + import cost
    per worker is real distributed overhead and *is* included — each timed
    sweep pays it, exactly as a fresh coordinator would)."""
    from repro.core.stream import default_reducers
    from repro.core.sweep import _as_list

    space = Space.grid(**axes)
    warmup = Space.grid(**{name: _as_list(v)[:1] for name, v in axes.items()})
    sess.sweep(warmup, chunk_size=chunk_size)   # score-path warmup only
    t0 = time.perf_counter()
    rep = sess.sweep(space, chunk_size=chunk_size,
                     reducers=default_reducers(k),
                     executor="processes", workers=workers)
    dt = time.perf_counter() - t0
    return {
        "n_points": rep.n_points,
        "seconds": dt,
        "front_ids": np.sort(
            np.asarray(rep.point_ids)[rep.pareto()]).tolist(),
        "top_rows": rep.top_k(k),
        "stats": {
            "n_points": rep.stats["n_points"],
            "memory_bound_points": rep.stats["memory_bound_points"],
            "t_exe_min": rep.stats["t_exe_min"],
        },
    }


def _dist_worker(workers: int, chunk_size: int, k: int,
                 hw_name: str) -> None:
    """Subprocess entry: one distributed sweep at ``workers``, print JSON."""
    import json

    sess = Session()
    if hw_name != "-":
        import repro.hw as hwreg

        sess = sess.with_hardware(hwreg.get(hw_name))
    rec = _dist_once(sess, _stream_axes_for(sess), workers, chunk_size, k)
    print(json.dumps(rec))


def _run_dist_worker(workers: int, chunk_size: int, k: int,
                     hw_name: str) -> dict:
    import json
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(root / "src"), env.get("PYTHONPATH")) if p)
    warn_args = [a for opt in sys.warnoptions for a in ("-W", opt)]
    out = subprocess.run(
        [sys.executable, *warn_args, "-m", "benchmarks.sweep_bench",
         "--dist-worker", str(workers), str(chunk_size), str(k), hw_name],
        capture_output=True, text=True, cwd=root, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"dist worker (workers={workers}) failed:\n"
                           f"{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def stream_dist(axes: dict | None = None, *, chunk_size: int = 1 << 17,
                workers_list=(1, 2, 4), k: int = 10,
                session: Session | None = None) -> list[dict]:
    """Distributed-sweep scaling: points/sec at 1/2/4 process workers.

    Each workers count runs the full >= 1M-point numpy-batch grid through
    ``executor="processes"`` in its *own coordinator subprocess* (so no
    measurement inherits another's page cache or import state), and every
    run's front ids / top-k rows / stats must agree with the in-process
    single-threaded streaming reference — the distributed path is bit-equal
    by construction, so ``agree`` failing means a real merge bug, and
    bench_gate.py fails the build on it.  ``cpus`` records the cores the
    coordinator could schedule on: scaling claims (and the bench_gate
    scaling invariant) only mean something when ``cpus >= workers``.
    """
    sess0 = (session or Session()).with_backend("numpy-batch")
    hw_name = sess0.hardware.name if sess0.hardware is not None else "-"
    import repro.hw as hwreg

    if hw_name != "-":
        reconstructable = (_hw_registered(hw_name)
                           and sess0 == Session().with_hardware(
                               hwreg.get(hw_name)).with_backend("numpy-batch"))
    else:
        reconstructable = sess0 == Session().with_backend("numpy-batch")
    isolate = axes is None and reconstructable
    axes = dict(axes) if axes is not None else _stream_axes_for(sess0)

    # In-process single-threaded streaming fold: the agreement reference.
    from repro.core.stream import default_reducers

    ref = sess0.sweep(Space.grid(**axes), chunk_size=chunk_size,
                      reducers=default_reducers(k), workers=1)
    ref_front = np.sort(np.asarray(ref.point_ids)[ref.pareto()]).tolist()
    ref_top = ref.top_k(k)
    ref_stats = {
        "n_points": ref.stats["n_points"],
        "memory_bound_points": ref.stats["memory_bound_points"],
        "t_exe_min": ref.stats["t_exe_min"],
    }

    rows = []
    base_pps = None
    for w in workers_list:
        if isolate:
            rec = _run_dist_worker(w, chunk_size, k, hw_name)
        else:
            rec = _dist_once(sess0, axes, w, chunk_size, k)
        agree = (rec["front_ids"] == ref_front
                 and rec["top_rows"] == ref_top      # bit-equal contract
                 and rec["stats"] == ref_stats)
        pps = rec["n_points"] / rec["seconds"]
        if base_pps is None:
            base_pps = pps
        rows.append({
            "backend": "numpy-batch",
            "executor": "processes",
            "workers": w,
            "n_points": rec["n_points"],
            "chunk_size": chunk_size,
            "seconds": round(rec["seconds"], 3),
            "points_per_sec": round(pps, 1),
            "speedup_vs_1worker": round(pps / base_pps, 2),
            "agree": bool(agree),
            "cpus": _cpus(),
        })
    return rows


def main() -> None:
    import sys

    argv = sys.argv[1:]
    if argv[:1] == ["--stream-worker"]:
        backend, chunk_size, k, hw_name = argv[1:5]
        grid = argv[5] if len(argv) > 5 else "1m"
        _stream_worker(backend, int(chunk_size), int(k), hw_name, grid)
        return
    if argv[:1] == ["--dist-worker"]:
        workers, chunk_size, k, hw_name = argv[1:5]
        _dist_worker(int(workers), int(chunk_size), int(k), hw_name)
        return
    rows = sweep_speedup()
    for row in rows:
        print(", ".join(f"{k}={v}" for k, v in row.items()))
    for row in stream_bench():
        print(", ".join(f"{k}={v}" for k, v in row.items()))
    for row in stream10_bench():
        print(", ".join(f"{k}={v}" for k, v in row.items()))
    for row in stream_dist():
        print(", ".join(f"{k}={v}" for k, v in row.items()))


if __name__ == "__main__":
    main()
