#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell, to find the highest rate
the server sustains without a growing backlog.

    python3 bench/rate_sweep.py <workload> <seconds> <rate> [<rate> ...]

Each rate runs the cell's window once (set-up is paid once per rate, its
compiles hit the in-process cache) and prints one JSON line: the offered
rate, p50 and p99 latency, the generator's lag, the mean batch, refusals,
and ``drain_ms``, how long the last answers took after the last query was
sent (a backlog that grows through the window shows here first).
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv) -> int:
    import jax

    from bench import run

    if jax.devices()[0].platform != "tpu":
        print("rate_sweep.py: needs a TPU", file=sys.stderr)
        return 1
    name, seconds, rates = argv[0], float(argv[1]), [float(r) for r in
                                                       argv[2:]]
    load = run.load_cell
    for rate in rates:
        def patched(n, rate=rate):
            spec = load(n)
            spec["traffic"]["rate_per_s"] = rate
            return spec

        run.load_cell = patched
        t0 = time.perf_counter()
        res = run.run_cell(name, seed=int(rate), seconds=seconds, trace=False,
                           t_start=t0)
        print(json.dumps({"rate_per_s": rate, **res["metrics"],
                          "window": res.get("window"),
                          "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
