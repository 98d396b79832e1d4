#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits.json`` are set from.

    python3 bench/control.py <workload> <seconds> --sound <seed>... \
        --control <seed>...

In one process on the chip: a short window of the cell for each ``--sound``
seed as the program stands, then for each ``--control`` seed with the
program's float64 scoring turned off (``repro.compat.enable_x64`` scoping
float32 instead, the nearest precision below the one the configuration
states).  Each run prints one JSON line with its compared numbers.  The
benchmark's own runs never do this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def lower_precision(setattr_=setattr) -> None:
    """Score in float32: ``enable_x64`` turns 64-bit types off, and the
    program's compiled functions are dropped so they are traced again."""
    import jax

    from repro import api, compat
    from repro.core import device_stream

    setattr_(compat, "enable_x64", lambda: jax.enable_x64(False))
    setattr_(device_stream, "_STEP_CACHE", {})
    setattr_(api, "_JAX_FN", None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    ap.add_argument("seconds", type=float)
    ap.add_argument("--sound", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax

    from bench import run

    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 1
    plan = [("sound", s) for s in args.sound] + \
        [("control", s) for s in args.control]
    lowered = False
    for mode, seed in plan:
        if mode == "control" and not lowered:
            lower_precision()
            lowered = True
        try:
            res = run.run_cell(args.workload, seed=seed,
                               seconds=args.seconds, trace=False,
                               t_start=time.perf_counter())
        except Exception as e:  # noqa: BLE001 — a crash is a reading too
            print(json.dumps({"mode": mode, "seed": seed,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
            continue
        print(json.dumps({"mode": mode, "seed": seed,
                          "correct": res["correct"],
                          "metrics": res["metrics"],
                          "window": res.get("window"),
                          "check": res["check"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
