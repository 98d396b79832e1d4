"""The one traffic generator: every mix under ``bench/traffic/`` is data.

A mix is a JSON file with a ``kind``:

* ``sweep`` — back-to-back design-space sweeps.  ``draw`` gives, per drawn
  axis, how many distinct values each sweep takes from the configuration's
  range; every other axis is the configuration's fixed list.  Sweep ``i`` of
  seed ``s`` draws its values from ``(s, i)`` alone, sorted, so every sweep
  of a cell has the same shape (one compiled program) and different
  answers.
* ``open_loop`` — single-design queries at Poisson arrivals of
  ``rate_per_s``; a share ``app_share`` are Table IV applications at sizes
  drawn from ``app_log2_elems``, the rest microbenchmark points drawn
  uniformly from the configuration's ranges.  No design repeats.

Seeds are any non-negative whole number (more than 64 bits are folded).
"""
from __future__ import annotations

import numpy as np

_MASK = (1 << 64) - 1


def rng(seed: int, *stream) -> np.random.Generator:
    """Independent generator of one seed and stream tag."""
    words = [int(seed) & _MASK, int(seed) >> 64 & _MASK]
    return np.random.default_rng(words + [int(x) for x in stream])


def _drawn(r: np.random.Generator, lo: int, hi: int, count: int) -> list:
    if count > hi - lo + 1:
        raise ValueError(f"cannot draw {count} distinct values from "
                         f"[{lo}, {hi}]")
    return sorted(int(v) for v in r.choice(np.arange(lo, hi + 1),
                                           size=count, replace=False))


def sweep_lists(config: dict, traffic: dict, seed: int, index: int) -> dict:
    """Axis value lists of sweep ``index`` (names as in the configuration:
    ``lsu_type`` names, ``dram``/``bsp`` indices into its tables)."""
    r = rng(seed, 0, index)
    lists = {k: list(v) for k, v in config["axes"].items()}
    for axis, count in traffic["draw"].items():
        lo, hi = config["ranges"][axis]
        values = _drawn(r, lo, hi, count)
        if axis == "n_elems_log2":
            lists["n_elems"] = [1 << e for e in values]
        else:
            lists[axis] = values
    lists["dram"] = list(range(len(config["drams"])))
    lists["bsp"] = list(range(len(config["bsps"])))
    return lists


def grid_size(lists: dict) -> int:
    return int(np.prod([len(v) for v in lists.values()]))


def open_loop(config: dict, traffic: dict, seed: int,
              seconds: float) -> tuple[list[dict], np.ndarray]:
    """Query designs and their due times (seconds from the window start).

    A microbenchmark spec holds ``lsu_type``, ``n_ga``, ``simd``,
    ``n_elems``, ``delta``, ``elem_bytes``, ``include_write``,
    ``val_constant`` and ``dram``/``bsp`` indices; an application spec
    holds ``app`` (a row of the configuration's ``apps``), ``n_elems`` and
    ``dram``/``bsp``.
    """
    r = rng(seed, 1)
    rate = float(traffic["rate_per_s"])
    gaps = r.exponential(1.0 / rate, size=int(rate * seconds * 1.2) + 64)
    due = np.cumsum(gaps)
    while due[-1] < seconds:
        due = np.concatenate([due, due[-1] + np.cumsum(
            r.exponential(1.0 / rate, size=len(due)))])
    due = due[due < seconds]
    ranges, axes = config["ranges"], config["axes"]
    apps = config["apps"]
    lo_app, hi_app = traffic["app_log2_elems"]
    seen: set = set()
    specs: list[dict] = []
    while len(specs) < len(due):
        hw = {"dram": int(r.integers(len(config["drams"]))),
              "bsp": int(r.integers(len(config["bsps"])))}
        if r.random() < traffic["app_share"]:
            app = int(r.integers(len(apps)))
            spec = {"app": app, "n_elems": int(r.integers(
                1 << lo_app, (1 << hi_app) + 1)), **hw}
        else:
            spec = {
                "lsu_type": axes["lsu_type"][int(r.integers(
                    len(axes["lsu_type"])))],
                "n_ga": int(r.integers(ranges["n_ga"][0],
                                       ranges["n_ga"][1] + 1)),
                "simd": int(r.choice(axes["simd"])),
                "n_elems": 1 << int(r.integers(
                    ranges["n_elems_log2"][0],
                    ranges["n_elems_log2"][1] + 1)),
                "delta": int(r.integers(ranges["delta"][0],
                                        ranges["delta"][1] + 1)),
                "elem_bytes": int(r.choice(axes["elem_bytes"])),
                "include_write": bool(r.integers(2)),
                "val_constant": bool(r.integers(2)),
                **hw,
            }
        key = tuple(sorted(spec.items()))
        if key not in seen:
            seen.add(key)
            specs.append(spec)
    return specs, due


def max_groups(config: dict) -> int:
    """Most LSU groups one query can bring (one group per global LSU)."""
    micro = config["ranges"]["n_ga"][1] + max(config["axes"]["simd"])
    app = max(a["n_read"] + a["n_write"] for a in config["apps"])
    return max(micro, app)
