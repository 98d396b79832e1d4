"""The comparison that decides ``correct``: the program's answers against the
plain reference (:mod:`bench.reference`), as a few numbers, each with the
limit in ``bench/limits.json``.

Two numbers per kind of cell:

* ``rel_err``: the largest relative gap of any compared float, as
  ``|a - b| / max(|a|, |b|)``;
* ``count_err``: counts that must agree exactly, plus memory-bound flags
  that differ away from Eq. 3's threshold (a point within 1e-6 of ratio
  1.0 may land either side when float64 is emulated; one farther away may
  not).

Sweeps compare values, not point ids, so two designs whose times lie
within rounding of each other may trade places in the top-k or on the
front.  The held rows are also re-scored from the designs the program says
they are, which catches a right value on a wrong design.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from bench import reference

LIMITS = json.loads((pathlib.Path(__file__).parent / "limits.json")
                    .read_text())

#: Eq. 3 ratios within this of 1.0 may flip their memory-bound flag.
THRESHOLD_RTOL = 1e-6
_HUGE = 1e300


def rel_gap(a, b) -> float:
    """Largest ``|a - b| / max(|a|, |b|)`` (0 where both are 0)."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if a.shape != b.shape:
        return _HUGE
    if a.size == 0:
        return 0.0
    scale = np.maximum(np.abs(a), np.abs(b))
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.where(a == b, 0.0, np.abs(a - b) / scale)
    gap = np.where(np.isnan(gap), _HUGE, gap)
    return float(min(np.max(gap), _HUGE))


def near_threshold(bound_ratio) -> np.ndarray:
    return np.abs(np.asarray(bound_ratio, dtype=np.float64) - 1.0) \
        <= THRESHOLD_RTOL


def flips_off_threshold(got, want, ratio) -> int:
    """Memory-bound flags that differ on rows away from the threshold."""
    flips = np.asarray(got, dtype=bool) != np.asarray(want, dtype=bool)
    return int(np.count_nonzero(flips & ~near_threshold(ratio)))


def staircase(ft, fr, at) -> np.ndarray:
    """The front read as a function: least ``t_exe`` among front points of
    resource at most each of ``at`` (1e-9 of slack on the resource)."""
    order = np.argsort(fr, kind="stable")
    ft, fr = np.asarray(ft)[order], np.asarray(fr)[order]
    best = np.minimum.accumulate(ft) if len(ft) else ft
    pos = np.searchsorted(fr, np.asarray(at) * (1 + 1e-9), side="right")
    return np.where(pos > 0, best[np.maximum(pos - 1, 0)] if len(ft)
                    else np.inf, np.inf)


def numbers(kind: str, rel_err: float, count_err: int) -> dict:
    lim = LIMITS[kind]
    return {"rel_err": {"value": rel_err, "limit": lim["rel_err"]},
            "count_err": {"value": count_err, "limit": lim["count_err"]}}


def _held_points(rep) -> dict:
    """The designs of a sweep report's held rows, as reference columns."""
    P = rep.points
    p = {k: np.asarray(P[k]) for k in ("n_ga", "simd", "n_elems", "delta",
                                       "elem_bytes", "include_write",
                                       "val_constant")}
    p["type"] = np.asarray([reference.CODE[t.value] for t in P["lsu_type"]],
                           dtype=np.int64)
    for k in reference.DRAM_FIELDS:
        p[k] = np.asarray([getattr(d, k) for d in P["dram"]])
    for k in reference.BSP_FIELDS:
        p[k] = np.asarray([getattr(b, k) for b in P["bsp"]])
    return p


def sweep_numbers(rep, ref: dict) -> dict:
    """A streaming ``SweepReport`` against :func:`reference.sweep`."""
    st = rep.stats
    t = np.asarray(rep.estimate.t_exe, dtype=np.float64)
    res = np.asarray(rep.resource, dtype=np.float64)
    gaps = [rel_gap(st[k], ref[k]) for k in (
        "t_exe_min", "t_exe_sum", "total_bytes_sum", "t_exe_mean",
        "t_exe_var")]
    top = np.sort(t[np.asarray(rep.topk_idx, dtype=np.int64)])
    count_err = abs(int(st["n_points"]) - ref["n_points"])
    count_err += abs(len(top) - len(ref["topk"]))
    if len(top) == len(ref["topk"]):
        gaps.append(rel_gap(top, ref["topk"]))
    front = np.asarray(rep.pareto(), dtype=np.int64)
    at = np.concatenate([res[front], ref["front_r"]])
    gaps.append(rel_gap(staircase(t[front], res[front], at),
                        staircase(ref["front_t"], ref["front_r"], at)))
    count_err += max(0, abs(int(st["memory_bound_points"])
                            - ref["memory_bound_points"])
                     - ref["near_threshold"])
    if len(res):
        g, m = reference.microbench_groups(_held_points(rep))
        want = reference.score_groups(g, m)
        est = rep.estimate
        for k in reference.ESTIMATE:
            gaps.append(rel_gap(getattr(est, k), want[k]))
        gaps.append(rel_gap(res, want["resource"]))
        count_err += flips_off_threshold(est.memory_bound,
                                         want["memory_bound"],
                                         want["bound_ratio"])
    return numbers("sweep", max(gaps), count_err)


def design_reference(config: dict, specs: list[dict]) -> dict:
    """Reference estimates of advisor queries, in query order."""
    n = len(specs)
    out = {k: np.zeros(n) for k in reference.ESTIMATE}
    out["memory_bound"] = np.zeros(n, dtype=bool)
    micro = [i for i, s in enumerate(specs) if "app" not in s]
    apps = [i for i, s in enumerate(specs) if "app" in s]

    def hw(idx):
        return reference.hardware_columns(
            config["drams"], config["bsps"],
            np.asarray([specs[i]["dram"] for i in idx], dtype=np.int64),
            np.asarray([specs[i]["bsp"] for i in idx], dtype=np.int64))

    if micro:
        p = {k: np.asarray([specs[i][k] for i in micro])
             for k in ("n_ga", "simd", "n_elems", "delta", "elem_bytes",
                       "include_write", "val_constant")}
        p["type"] = np.asarray([reference.CODE[specs[i]["lsu_type"]]
                                for i in micro], dtype=np.int64)
        p.update(hw(micro))
        est = reference.score_groups(*reference.microbench_groups(p))
        for k in out:
            out[k][micro] = est[k]
    if apps:
        g, m = reference.app_groups(
            [config["apps"][specs[i]["app"]] for i in apps],
            [specs[i]["n_elems"] for i in apps], hw(apps))
        est = reference.score_groups(g, m)
        for k in out:
            out[k][apps] = est[k]
    return out


def advisor_numbers(config: dict, specs: list[dict], answers: dict,
                    unanswered: int) -> dict:
    """Every answered estimate against the reference of its design.

    ``answers`` maps query index to the program's ``Estimate``; a query
    that never got an answer counts in ``count_err``.
    """
    idx = sorted(answers)
    want = design_reference(config, [specs[i] for i in idx])
    gaps = [0.0]
    for k in reference.ESTIMATE:
        got = [getattr(answers[i], k) for i in idx]
        gaps.append(rel_gap(got, want[k]))
    flips = flips_off_threshold([answers[i].memory_bound for i in idx],
                                want["memory_bound"], want["bound_ratio"])
    return numbers("open_loop", max(gaps), flips + unanswered)

