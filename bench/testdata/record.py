#!/usr/bin/env python3
"""Record the small chip trace that ``bench/tests/test_trace.py`` reads.

Run on one TPU from the repository root::

    python3 bench/testdata/record.py <out_dir>

One fused sweep of a 1,280-point grid (one chunk step) runs inside the
``bench.window`` / ``bench.sweep`` host annotations the benchmark uses;
the ``.xplane.pb`` is copied to ``<out_dir>/sweep_trace.xplane.pb`` and the
planes, lines and a few events of each are printed.
"""
from __future__ import annotations

import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out_dir: str) -> int:
    import jax

    from bench import generator, trace
    from bench.drivers.sweep import Driver
    from bench.run import load_cell

    if jax.devices()[0].platform != "tpu":
        print("record.py: needs a TPU", file=sys.stderr)
        return 1
    spec = load_cell("explore_10m")
    drv = Driver(spec["config"], spec["traffic"], seed=7)
    lists = generator.sweep_lists(spec["config"], spec["traffic"], 7, 0)
    small = dict(lists, n_ga=lists["n_ga"][:1], n_elems=lists["n_elems"][:1],
                 delta=lists["delta"][:2])
    drv.warm_up()
    log_dir = ROOT / ".bench_trace" / "record"
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.sweep"):
            drv._sweep(small)
    jax.profiler.stop_trace()
    path = trace.latest_xplane(str(log_dir))
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out / "sweep_trace.xplane.pb")
    pd = trace.load(path)
    for pl in pd.planes:
        lines = list(pl.lines)
        print(f"plane {pl.name!r}: lines "
              f"{[(ln.name, len(list(ln.events))) for ln in lines]}")
        for ln in lines[:6]:
            for ev in list(ln.events)[:3]:
                try:
                    stats = {str(k): str(v)[:60] for k, v in ev.stats}
                except (TypeError, ValueError):
                    stats = "?"
                print(f"  {ln.name!r} {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={stats}")
    print(trace.reduce(pd))
    shutil.rmtree(log_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
