#!/usr/bin/env python3
"""Bring-up smoke run of the jax-jit estimator on a TPU.

Drives the main path once, in this one process, through ``Session``'s public
entry points, and checks every answer against the numpy-batch host path:

1. sweep     the 10,240,000-point ``stream_10m`` grid (chunk 2**17) must take
             the device-fused path and agree with the host fold of the grid;
2. serve     a few hundred ``estimate`` calls from several threads against
             ``Session.serve()`` must agree with serial host estimates;
3. optimize  on the 1,024,000-point ``optimize_1m`` space the descent phase
             must run and the result must be the grid optimum;
4. validate  all seven Pallas kernels at measurement shapes, compiled (never
             interpreted), with no failure rows;
5. model     ``codeqwen1.5-7b`` at its published widths on the ``tpu_v5e``
             preset (train and decode), depth and batch cut to one chip; the
             composed totals must equal the summed parts.

``--four-chips`` runs only the ``stream_10m`` sweep, on the fused step split
over every local chip, and its host comparison.

Agreement: equal bits pass.  Otherwise the largest difference in ulps is
printed per column, float columns must agree within a relative 1e-6, and
point ids (front membership, top-k order) must match exactly.

Usage, from the repository root::

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the fused sweep on four chips

Exits non-zero, printing no result, when the first jax device is not a TPU.
The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import threading
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

RTOL = 1e-6
CHUNK = 1 << 17
TOP_K = 10


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


#: Backend compile seconds and program count since the phase started, fed
#: by jax's monitoring events (persistent-cache hits compile nothing).
COMPILES = {"s": 0.0, "n": 0}


def _on_event(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES["s"] += secs
        COMPILES["n"] += 1


# ---------------------------------------------------------------------------
# comparison helpers
# ---------------------------------------------------------------------------

def max_ulps(a, b) -> float:
    """Largest |a - b| in units of the last place of the larger magnitude."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if a.size == 0:
        return 0.0
    diff = np.abs(a - b)
    scale = np.spacing(np.maximum(np.abs(a), np.abs(b)))
    return float(np.max(np.where(diff == 0, 0.0, diff / scale)))


def close(a, b) -> bool:
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    return bool(np.all(np.abs(a - b)
                       <= RTOL * np.maximum(np.abs(a), np.abs(b))))


def near_threshold(bound_ratio) -> np.ndarray:
    """Rows whose Eq. 3 ratio is within ``RTOL`` of the memory-bound
    threshold 1.0: the only rows whose ``memory_bound`` flag two backends
    that agree within ``RTOL`` may classify differently."""
    return np.abs(np.asarray(bound_ratio, dtype=np.float64) - 1.0) <= RTOL


def compare_columns(name: str, got: dict, want: dict,
                    exact: tuple = ()) -> dict:
    """Per-column agreement of two column dicts; raises on a mismatch.

    ``exact`` columns (ids, codes) must be equal; float columns may differ
    by ``RTOL`` and report their largest ulp distance; ``memory_bound`` may
    differ only on rows :func:`near_threshold` marks.
    """
    ulps = {}
    for col, w in want.items():
        g = np.asarray(got[col])
        w = np.asarray(w)
        check(g.shape == w.shape, f"{name}.{col}: shape {g.shape} != "
                                  f"{w.shape}")
        if col == "memory_bound" and "bound_ratio" in want:
            flips = g != w
            check(not np.any(flips & ~near_threshold(want["bound_ratio"])),
                  f"{name}.memory_bound: flips away from the threshold")
            if np.any(flips):
                log(f"  {name}.memory_bound: {int(flips.sum())} flips at "
                    f"the threshold")
            continue
        if col in exact or not np.issubdtype(w.dtype, np.floating):
            check(np.array_equal(g, w), f"{name}.{col}: values differ")
            continue
        ulps[col] = max_ulps(g, w)
        check(close(g, w), f"{name}.{col}: beyond rtol {RTOL} "
                           f"({ulps[col]:.0f} ulps)")
    return ulps


def report_agreement(name: str, ulps: dict) -> bool:
    bit_equal = all(u == 0.0 for u in ulps.values())
    worst = {c: u for c, u in ulps.items() if u}
    log(f"  {name}: bit-equal={bit_equal}"
        + ("" if bit_equal else f" max-ulps={json.dumps(worst)}"))
    return bit_equal


class NearThreshold:
    """A host-only reducer counting :func:`near_threshold` rows."""

    def __init__(self):
        self.n = 0

    def update(self, cols) -> None:
        self.n += int(np.count_nonzero(near_threshold(cols["bound_ratio"])))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_sweep(n_chips: int) -> dict:
    from benchmarks.sweep_bench import STREAM_GRIDS
    from repro import Session, Space
    from repro.core.stream import default_reducers

    axes = STREAM_GRIDS["10m"]
    space = Space.grid(**axes)
    expected = int(np.prod([len(v) for v in axes.values()]))
    dev = Session(backend="jax-jit")
    t0 = time.perf_counter()
    rep = dev.sweep(space, chunk_size=CHUNK, reducers=default_reducers(TOP_K),
                    profile=True)
    profiled_s = time.perf_counter() - t0
    prof = rep.profile
    log(f"  profile: {json.dumps(prof, default=str)}")
    check(prof["path"] == "device-fused",
          f"sweep took the {prof['path']!r} path "
          f"({prof.get('host_reason', '')}), not 'device-fused'")
    check(prof.get("devices") == n_chips,
          f"the sweep's ranges spread over {prof.get('devices')} devices, "
          f"not {n_chips}")
    t0 = time.perf_counter()
    rep = dev.sweep(space, chunk_size=CHUNK, reducers=default_reducers(TOP_K))
    warm_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    host = Session(backend="numpy-batch").sweep(
        space, chunk_size=CHUNK,
        reducers=default_reducers(TOP_K) + (NearThreshold(),))
    host_s = time.perf_counter() - t0
    (near,) = [r.n for r in host.reducers if isinstance(r, NearThreshold)]

    n = rep.n_points
    check(n == host.n_points == expected,
          f"n_points {n} / {host.n_points}, grid {expected}")
    ids = np.asarray(rep.point_ids)
    hids = np.asarray(host.point_ids)
    front = np.sort(ids[rep.pareto()])
    hfront = np.sort(hids[host.pareto()])
    check(np.array_equal(front, hfront),
          f"front ids differ: {front.tolist()} vs {hfront.tolist()}")

    def rows_cols(r):
        rows = r.top_k(TOP_K)
        return {c: np.asarray([row[c] for row in rows]) for c in rows[0]}

    def front_cols(r, r_ids):
        sel = r.pareto()
        order = np.argsort(r_ids[sel])
        return {"t_exe": np.asarray(r.estimate.t_exe)[sel][order],
                "resource": np.asarray(r.resource)[sel][order]}

    ulps = compare_columns("top_k", rows_cols(rep), rows_cols(host),
                           exact=("id",))
    ulps.update({"front_" + c: u for c, u in compare_columns(
        "front", front_cols(rep, ids), front_cols(host, hids)).items()})
    st, hst = rep.stats, host.stats
    for key, hv in hst.items():
        v = st[key]
        if key == "memory_bound_points":
            log(f"  memory_bound_points: {v} vs {hv}; {near} host rows lie "
                f"within rtol of the threshold")
            check(abs(v - hv) <= near, f"stats.{key}: {v} vs {hv} differ by "
                                       f"more than the {near} rows at the "
                                       f"threshold")
        elif isinstance(hv, float):
            ulps["stats." + key] = max_ulps(v, hv)
            check(close(v, hv), f"stats.{key}: {v!r} vs {hv!r}")
        else:
            check(v == hv, f"stats.{key}: {v!r} vs {hv!r}")
    bit_equal = report_agreement("sweep vs host fold", ulps)
    out = {
        "n_points": n, "chunk": CHUNK, "path": prof["path"],
        "front": int(len(front)),
        "profiled_s": profiled_s, "compile_s": prof.get("compile_s"),
        "warm_s": warm_s, "points_per_s": n / warm_s,
        "host_s": host_s, "bit_equal": bit_equal,
    }
    if n_chips > 1:
        out["devices"] = prof.get("devices")
    log(f"  {json.dumps(out)}")
    return out


def _designs(count: int, seed: int) -> list:
    from repro import Design
    from repro.core import LsuType
    from repro.hw import get as hw_get

    rng = np.random.default_rng(seed)
    types = [LsuType.BC_ALIGNED, LsuType.BC_NON_ALIGNED,
             LsuType.BC_WRITE_ACK, LsuType.ATOMIC_PIPELINED]
    drams = [hw_get("stratix10_ddr4_1866").dram_params(),
             hw_get("stratix10_ddr4_2666").dram_params()]
    out = []
    for _ in range(count):
        simd = int(rng.choice([1, 2, 4, 8, 16]))
        out.append(Design.microbench(
            types[int(rng.integers(len(types)))],
            n_ga=int(rng.integers(1, 11)), simd=simd,
            n_elems=simd << int(rng.integers(10, 20)),
            delta=int(rng.integers(1, 21)),
            elem_bytes=int(rng.choice([4, 8])),
            include_write=bool(rng.integers(2)),
            val_constant=bool(rng.integers(2)),
            dram=drams[int(rng.integers(2))]))
    return out


def phase_serve(n_requests: int = 384, n_threads: int = 8) -> dict:
    from repro import Session

    designs = _designs(n_requests, seed=0)
    serial = [Session().estimate(d) for d in designs]
    got = [None] * n_requests
    errors: list = []
    t0 = time.perf_counter()
    with Session(backend="jax-jit").serve(max_batch=64) as srv:
        def client(k: int) -> None:
            try:
                for i in range(k, n_requests, n_threads):
                    got[i] = srv.estimate(designs[i])
            except Exception as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = srv.stats()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    cols = ("t_exe", "t_ideal", "t_ovh", "bound_ratio", "total_bytes")
    want = {c: np.asarray([getattr(e, c) for e in serial]) for c in cols}
    have = {c: np.asarray([getattr(e, c) for e in got]) for c in cols}
    want["memory_bound"] = np.asarray([e.memory_bound for e in serial])
    have["memory_bound"] = np.asarray([e.memory_bound for e in got])
    ulps = compare_columns("serve", have, want)
    bit_equal = report_agreement("serve vs serial numpy-batch", ulps)
    out = {"requests": n_requests, "threads": n_threads, "wall_s": wall,
           "bit_equal": bit_equal,
           "latency_ms": stats.get("latency_ms"),
           "mean_batch": stats.get("mean_batch")}
    log(f"  {json.dumps(out, default=str)}")
    return out


def phase_optimize() -> dict:
    from benchmarks.sweep_bench import STREAM_GRIDS
    from repro import Session, Space
    from repro.core.stream import default_reducers

    space = Space.grid(**STREAM_GRIDS["1m"])
    t0 = time.perf_counter()
    full = Session(backend="numpy-batch").sweep(
        space, chunk_size=CHUNK, reducers=default_reducers(TOP_K))
    grid_s = time.perf_counter() - t0
    ref_id = int(full.stats["t_exe_min_id"])
    ref_min = float(full.stats["t_exe_min"])

    t0 = time.perf_counter()
    rep = Session(backend="jax-jit").optimize(
        space, objective=("t_exe", "resource"), seed=0)
    opt_s = time.perf_counter() - t0
    descend = [t for t in rep.trajectory if t["phase"] == "descend"]
    log(f"  trajectory: {json.dumps([dict(t) for t in rep.trajectory], default=str)}")
    check(len(descend) == 1, "no descend phase in the trajectory")
    check("skipped" not in descend[0],
          f"descent skipped: {descend[0].get('skipped')}")
    check(descend[0]["steps"] > 0, "descent took no steps")
    # the grid holds ties at the optimum (inert axes), so the value decides
    ulps = {"t_exe": max_ulps(rep.best.t_exe, ref_min)}
    check(close(rep.best.t_exe, ref_min),
          f"optimum t_exe {rep.best.t_exe!r} vs grid {ref_min!r}")
    bit_equal = report_agreement("optimum vs grid", ulps)
    out = {"n_points": rep.n_total, "n_evals": rep.n_evals,
           "descend_steps": descend[0]["steps"],
           "descend_lanes": descend[0]["lanes"],
           "best_id": int(rep.best_id), "grid_best_id": ref_id,
           "best_t_exe": rep.best.t_exe,
           "optimize_s": opt_s, "grid_s": grid_s, "bit_equal": bit_equal}
    log(f"  {json.dumps(out)}")
    return out


def phase_validate() -> dict:
    from repro import Session
    from repro.core.validate import default_cases

    t0 = time.perf_counter()
    rep = Session().validate(default_cases(small=False))
    wall = time.perf_counter() - t0
    for f in rep.failures:
        log(f"  FAILED {f['kernel']}: {f['error'][:2000]}")
    for r in rep.rows():
        log(f"  {r['kernel']:<18} measured={r['measured_ms']} ms "
            f"predicted={r['predicted_ms']} ms err={r['err_pct']}% "
            f"interpret={r['interpret']}")
    check(not rep.failures, f"{len(rep.failures)} kernels failed")
    check(len(rep.results) == 7, f"{len(rep.results)} kernels ran, not 7")
    check(not any(r.interpret for r in rep.results),
          "a kernel ran in interpret mode")
    check(all(r.bytes_moved > 0 and 0 < r.predicted_s < np.inf
              for r in rep.results),
          "a kernel moved no counted bytes or has no finite prediction")
    out = {"kernels": len(rep.results), "failures": len(rep.failures),
           "max_err_pct": rep.max_err_pct,
           "measured_bw_gbs": rep.measured_bw / 1e9,
           "calibration_factor": rep.calibration_factor, "wall_s": wall}
    log(f"  {json.dumps(out)}")
    return out


#: The published codeqwen1.5-7b widths are kept; only depth and batch are
#: cut, to what one 16 GB chip compiles comfortably.
MODEL = "codeqwen1.5-7b"
MODEL_SEQ = 4096
MODEL_BATCH = {"train": 1, "decode": 8}


def phase_model() -> dict:
    from repro import Session
    from repro.configs import ARCHS
    from repro.hw import get as hw_get

    full = ARCHS[MODEL]
    cfg = dataclasses.replace(full, n_layers=len(full.block_pattern))
    log(f"  cuts: n_layers {full.n_layers} -> {cfg.n_layers} (one block "
        f"period); batch train={MODEL_BATCH['train']} "
        f"decode={MODEL_BATCH['decode']}; seq_len {MODEL_SEQ}; widths "
        f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} kept")
    sess = Session(backend="jax-jit").with_hardware(hw_get("tpu_v5e"))
    host = Session().with_hardware(hw_get("tpu_v5e"))
    out: dict = {"model": MODEL, "n_layers": cfg.n_layers,
                 "seq_len": MODEL_SEQ}
    for phase, batch in MODEL_BATCH.items():
        t0 = time.perf_counter()
        rep = sess.estimate_model(cfg, phases=(phase,), batch=batch,
                                  seq_len=MODEL_SEQ)
        wall = time.perf_counter() - t0
        (ph,) = rep.phases
        parts = [op.t_exe for op in ph.ops]
        rescored = [e.t_exe for e in sess.estimate_many(
            [op.design for op in ph.ops])]
        on_host = [e.t_exe for e in host.estimate_many(
            [op.design for op in ph.ops])]
        check(len(parts) > 0, f"{phase}: no ops scored")
        check(ph.t_total == float(np.sum(parts)) or close(
            ph.t_total, np.sum(parts)),
              f"{phase}: total {ph.t_total!r} != summed parts")
        check(close(ph.t_total, np.sum(rescored)),
              f"{phase}: total {ph.t_total!r} != re-scored parts")
        ulps = {"t_exe": max_ulps(parts, on_host)}
        check(close(parts, on_host), f"{phase}: per-op t_exe differs from "
                                     f"the host backend")
        bit_equal = report_agreement(f"model {phase} ops vs host", ulps)
        row = {"batch": batch, "t_total_ms": ph.t_total * 1e3,
               "n_ops": ph.n_ops, "n_scored": len(ph.ops),
               "bytes_mb": ph.total_bytes / 1e6, "flops_g": ph.flops / 1e9,
               "bottleneck": ph.bottleneck, "wall_s": wall,
               "bit_equal": bit_equal}
        log(f"  {phase}: {json.dumps(row)}")
        out[phase] = row
    return out


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the stream_10m sweep on the fused step "
                         "over four local chips, and its host comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: the first jax device is {d0.platform!r} "
              f"({d0.device_kind}), not a TPU", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) != want:
        print(f"chip_smoke: this run needs {want} local TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    log(f"device: {json.dumps(device)}  jax {jax.__version__}")

    from repro import compat

    log(f"compilation cache: {compat.enable_compilation_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    phases = ([("sweep", lambda: phase_sweep(4))] if args.four_chips else [
        ("sweep", lambda: phase_sweep(1)),
        ("serve", phase_serve),
        ("optimize", phase_optimize),
        ("validate", phase_validate),
        ("model", phase_model),
    ])
    results, failed = {}, []
    t_all = time.perf_counter()
    for name, fn in phases:
        log(f"== {name}")
        COMPILES.update(s=0.0, n=0)
        t0 = time.perf_counter()
        try:
            results[name] = fn()
        except Exception as e:  # noqa: BLE001 — every phase is reported
            failed.append(name)
            log(f"  FAILED: {type(e).__name__}: {e}")
        log(f"  {name} wall {time.perf_counter() - t0:.3f} s, compiled "
            f"{COMPILES['n']} programs in {COMPILES['s']:.3f} s")
    log(f"total wall {time.perf_counter() - t_all:.3f} s; "
        f"failed phases: {failed or 'none'}")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
