#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; it names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``), whose ``kind`` picks the driver
(``bench/drivers/<kind>.py``).  Each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, configuration, mix or metric
adds files and entries; it edits none.

Set-up (``setup_s``, process start to the window) covers jax and device
start, the persistent compilation cache (always ``.jax_cache/`` in the
checkout),
building the traffic from ``--seed`` and compiling the cell's own shapes.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
same window under the profiler and reports its per-layer metrics, device
busy time and a breakdown.  Both then compare what the window produced
with the plain reference (``bench/reference.py``) and print each compared
number beside its limit: last on standard error, and last in the result.

Exits non-zero, printing no result, unless the first device is a TPU and
the device count is the cell's ``chips``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# The persistent compilation cache lives at one fixed path in the checkout,
# whatever the environment names (the program takes the variable's path).
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """The cell, its configuration and traffic, and its metric lists."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    (config,) = [c for c in bench["configs"] if c["name"] == cell["config"]]

    def listed(m):
        return name in m.get("workloads", [name])

    return {
        "cell": cell,
        "config": json.loads((ROOT / config["file"]).read_text()),
        "traffic": json.loads((BENCH / "traffic" /
                               f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
        "per_layer": [m for m in bench["per_layer"] if listed(m)],
    }


class Compiles:
    """Programs built, from jax's monitoring events: ``n`` counts every
    program jax had to obtain (compiled, or loaded from the persistent
    cache), ``hits`` those the persistent cache supplied."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.n, self.hits, self.s = 0, 0, 0.0

    def duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += secs

    def event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __str__(self) -> str:
        return (f"{self.n} programs ({self.n - self.hits} compiled, "
                f"{self.hits} from the persistent cache, {self.s:.3f} s)")


def device_info(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float | None = None) -> dict:
    """Set up, measure and check one cell on whatever devices jax has.

    Returns the result object (without printing it).  The device check is
    :func:`main`'s; tests drive this directly.
    """
    import jax

    from repro import compat

    t_start = T_START if t_start is None else t_start
    spec = load_cell(name)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles.duration)
    jax.monitoring.register_event_listener(compiles.event)
    log(f"compilation cache: {compat.enable_compilation_cache()}")
    t_jax = time.perf_counter()

    driver_mod = _load_module(BENCH / "drivers" /
                              f"{spec['traffic']['kind']}.py")
    driver = driver_mod.Driver(spec["config"], spec["traffic"], seed)
    driver.build(seconds)
    t_built = time.perf_counter()
    driver.warm_up()
    t_warm = time.perf_counter()
    log(f"set-up: {t_jax - t_start:.3f} s to jax and the devices, "
        f"{t_built - t_jax:.3f} s building the traffic, "
        f"{t_warm - t_built:.3f} s warming up; {compiles}")
    compiles.reset()
    # The traffic built ahead (for the advisor, every query's Design) stays
    # out of the collector's scans: a collection in the window then costs
    # what the program's own objects cost, as in a deployed process.
    gc.collect()
    gc.freeze()

    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda _name: contextlib.nullcontext()))
    setup_s = time.perf_counter() - t_start
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with annotate("bench.window"):
            out = driver.window(seconds, profile=trace, annotate=annotate)
    finally:
        if trace:
            jax.profiler.stop_trace()
    log(f"window: {compiles}")
    if out.get("log"):
        log(f"window: {json.dumps(out['log'])}")
    device = device_info(jax)
    driver.close()
    gc.unfreeze()

    reduced = None
    if trace:
        from bench import trace as _trace

        path = _trace.latest_xplane(str(TRACE_DIR))
        log(f"trace: {path} ({os.path.getsize(path)} bytes)")
        reduced = _trace.reduce(_trace.load(path))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    t0 = time.perf_counter()
    nums = driver.check()
    log(f"check: {time.perf_counter() - t0:.3f} s")

    metrics = {}
    if trace:
        for m in spec["per_layer"]:
            reader = _load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(out["layer"], reduced)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {**out["metrics"], "setup_s": setup_s}
        for m in spec["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result = {
        "correct": all(v["value"] <= v["limit"] for v in nums.values()),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if out.get("log"):
        result["window"] = out["log"]
    result["check"] = nums
    for k, v in nums.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    chips = load_cell(args.workload)["cell"]["chips"]
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: the first jax device is {devs[0].platform!r} "
            f"({devs[0].device_kind}), not a TPU")
        return 1
    if len(devs) != chips:
        log(f"bench: {args.workload} needs {chips} TPU devices, found "
            f"{len(devs)}")
        return 1
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
