#!/usr/bin/env python3
"""How much of each traced sweep its program spans account for.

    python3 benchmarks/span_coverage.py --workload <cell> --seed <n> \\
        [--seconds 10]

Runs one cell of ``BENCHMARK.json`` like ``bench/run.py --trace 1`` (the
same window under the profiler) and reads the trace it records:

* for every ``repro.sweep`` span, the share of its duration covered by the
  union of its direct ``repro.*`` children on the same thread;
* for each of the window's ten longest device idle gaps, the name
  ``bench/trace.py`` gives it and the innermost ``repro.*`` span around
  the gap's middle (``null`` when the gap falls outside every span);
* each ``repro.*`` span name's summed duration over the window's grid
  points (the ``points`` arg of ``repro.sweep``), in ns a point.

Prints one JSON line: the cell's result and those readings.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, trace  # noqa: E402


def _repro_spans(pd) -> list[list]:
    """``repro.*`` events of each Python thread, one list per thread."""
    out = []
    for pl in pd.planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                evs = trace._events(ln)
                if trace._python_thread(evs):
                    out.append([e for e in evs if e[2].startswith("repro.")])
    return out


def _covered(children) -> float:
    busy = trace.merge(children, -float("inf"), float("inf"))
    return float((busy[:, 1] - busy[:, 0]).sum()) if len(busy) else 0.0


def _points(pd) -> int:
    return sum(int(dict(ev.stats).get("points", 0))
               for pl in pd.planes if pl.name.startswith("/host:")
               for ln in pl.lines for ev in ln.events
               if ev.name == "repro.sweep")


def coverage(pd) -> dict:
    shares, spans = [], []
    for evs in _repro_spans(pd):
        spans.extend(evs)
        for s0, s1, name in evs:
            if name != "repro.sweep":
                continue
            inner = [e for e in evs
                     if s0 <= e[0] and e[1] <= s1 and e[2] != name]
            direct = [e for e in inner if not any(
                o[0] <= e[0] and e[1] <= o[1] and o[1] - o[0] > e[1] - e[0]
                for o in inner)]
            shares.append(_covered(direct) / max(s1 - s0, 1.0))
    points = max(_points(pd), 1)
    per_point: dict = {}
    for s0, s1, name in spans:
        per_point[name] = per_point.get(name, 0.0) + (s1 - s0) / points
    return {"sweeps": len(shares), "points": points,
            "span_ns_per_point": per_point,
            "coverage_min": min(shares) if shares else None,
            "coverage_median": statistics.median(shares) if shares else None,
            "idle_gaps": idle_gaps(pd, spans)}


def idle_gaps(pd, spans) -> list:
    """The ten longest device idle gaps of the window: ``[name, innermost
    repro span, seconds]``."""
    devices, host = trace.planes(pd)
    lo, hi = next((s, e) for s, e, n in host if n == trace.WINDOW)
    gaps = []
    for ops in devices:
        gaps.extend(map(tuple, trace.gaps(trace.merge(ops, lo, hi), lo, hi)))
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:trace.TOP]
    named = []
    for g0, g1 in longest:
        mid = (g0 + g1) / 2
        around = [e for e in spans if e[0] <= mid <= e[1]]
        inner = min(around, key=lambda e: e[1] - e[0])[2] if around else None
        named.append([trace.name_gap(g0, g1, host), inner,
                      float(g1 - g0) / 1e9])
    return named


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    found = {}
    reduce = trace.reduce

    def reduce_and_cover(pd, window=trace.WINDOW):
        found.update(coverage(pd))
        return reduce(pd, window)

    trace.reduce = reduce_and_cover
    result = run.run_cell(args.workload, args.seed, args.seconds, True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "metrics": result["metrics"],
                      "correct": result["correct"], "spans": found}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
