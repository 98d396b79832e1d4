"""Reduction of a profiler trace to the benchmark's device numbers.

The traced run wraps its window in a host annotation ``bench.window`` and
each call into the program in one of its own (``bench.sweep``,
``bench.clients``).  From the ``.xplane.pb`` file the JAX profiler writes,
this module takes:

* each device's busy time: the union of its ``XLA Ops`` intervals, clipped
  to the window (summed over devices, and averaged over them);
* the device operations that took the most time (summed over devices);
* the longest idle gaps of any device, each named by what the host was
  doing: the innermost event covering the gap's middle on a thread that
  runs Python (one that holds a ``bench.*`` annotation or a jitted call),
  else the one overlapping the gap most, else ``untraced host time``.

A device is a plane with an ``XLA Ops`` line; operation names are HLO
instructions with layouts and operand names dropped.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW = "bench.window"
TOP = 10


def latest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line):
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in line.events]


def _python_thread(events) -> bool:
    return any(n.startswith(("bench.", "PjitFunction")) for _, _, n in events)


def planes(pd) -> tuple[list, list]:
    """(device op events per device, host events of Python threads) as
    ``(start_ns, end_ns, name)`` triples."""
    devices, host = [], []
    for pl in pd.planes:
        lines = list(pl.lines)
        if pl.name.startswith("/device:"):
            ops = [ln for ln in lines if ln.name == "XLA Ops"]
            if ops:
                devices.append([ev for ln in ops for ev in _events(ln)])
        elif pl.name.startswith("/host:"):
            for ln in lines:
                evs = _events(ln)
                if _python_thread(evs):
                    host.extend(evs)
    return devices, host


_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPERAND = re.compile(r" %[\w.\-]+")


def op_name(hlo: str) -> str:
    """``%fusion.6 = u32[131072]{0:T(1024)} fusion(u32[128]{...} %a, ...),
    kind=kCustom, ...`` -> ``fusion.6 = u32[131072] fusion(u32[128], ...)``."""
    text = hlo.lstrip("%")
    while True:
        shorter = _LAYOUT.sub("", text)
        if shorter == text:
            break
        text = shorter
    text = _OPERAND.sub("", text)
    head, eq, rest = text.partition(" = ")
    # skip a tuple result type, then keep through the op's operand list
    depth, start, cut = 0, 0, len(rest)
    if rest.startswith("("):
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                start = i + 1
                break
    for i in range(start, len(rest)):
        depth += (rest[i] == "(") - (rest[i] == ")")
        if rest[i] == ")" and depth == 0:
            cut = i + 1
            break
    text = head + eq + rest[:cut]
    return text


def merge(intervals, lo: float, hi: float) -> np.ndarray:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``, as an
    ``[n, 2]`` array of disjoint intervals in order."""
    iv = np.asarray([(max(s, lo), min(e, hi)) for s, e, *_ in intervals
                     if e > lo and s < hi], dtype=np.float64).reshape(-1, 2)
    if len(iv) == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    run_end = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), dtype=bool)
    new[1:] = iv[1:, 0] > run_end[:-1]
    starts = iv[new, 0]
    last = np.concatenate([np.flatnonzero(new)[1:] - 1, [len(iv) - 1]])
    return np.stack([starts, run_end[last]], axis=1)


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The idle intervals of ``[lo, hi]`` around merged busy intervals."""
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def name_gap(g0: float, g1: float, host: list) -> str:
    mid = (g0 + g1) / 2
    inner, over = None, None
    for s, e, name in host:
        if name == WINDOW or e <= g0 or s >= g1:
            continue
        if s <= mid <= e and (inner is None or e - s < inner[0]):
            inner = (e - s, name)
        o = min(e, g1) - max(s, g0)
        if over is None or o > over[0]:
            over = (o, name)
    if inner is not None:
        return inner[1]
    return over[1] if over is not None else "untraced host time"


def reduce(pd, window: str = WINDOW) -> dict:
    devices, host = planes(pd)
    spans = [(s, e) for s, e, name in host if name == window]
    if not spans:
        raise ValueError(f"no host span {window!r} in the trace")
    if not devices:
        raise ValueError("the trace holds no device plane")
    lo, hi = spans[0]
    busy_ns, all_gaps, per_op = [], [], {}
    for ops in devices:
        busy = merge(ops, lo, hi)
        busy_ns.append(float(np.sum(busy[:, 1] - busy[:, 0])))
        all_gaps.extend(map(tuple, gaps(busy, lo, hi)))
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                name = op_name(name)
                per_op[name] = per_op.get(name, 0.0) + d
    window_s = (hi - lo) / 1e9
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": window_s,
        "n_devices": len(devices),
        "busy_s": float(np.mean(busy_ns)) / 1e9,
        "busy_s_sum": float(np.sum(busy_ns)) / 1e9,
        "device_ops": [[n, d / 1e9] for n, d in top_ops],
        "idle_gaps": [[name_gap(g0, g1, host), float(g1 - g0) / 1e9]
                      for g0, g1 in longest],
    }
