"""Plain reference of the paper's model (arXiv:2003.13054, Eqs. 1-10).

Written for the benchmark alone: it imports nothing of the program and takes
nothing the program made.  Every design is a set of LSU *groups* (``count``
identical load/store units of one kernel); the equations below follow the
paper's text and Table I-III parameters directly, in float64, over numpy
arrays:

* Eq. 2   ``t_ideal = ls_acc * ls_bytes / (dq * 2 * f_mem)``
* Eq. 5   burst-coalesced transactions of ``2**burst_cnt * dq * bl`` bytes
* Eq. 6   ``T_row = T_RCD + T_RP`` (Eq. 9 adds ``T_WR`` for write-ACK,
          Eq. 10 doubles it and adds ``T_WR`` for atomics)
* Eq. 7-8 the non-aligned ``max_th`` knee
* Eq. 4   row-miss overhead ``n_bursts * T_row`` once two or more LSUs
          share the DRAM (a write-ACK pays its round-trip even alone, and
          wastes ``dq * bl - ls_bytes`` bytes of every burst)
* Eq. 1   ``t_exe = sum delta * (t_ideal + t_ovh)`` over the kernel's LSUs
* Eq. 3   memory bound when ``sum ls_width / (dq * bl * K_lsu) >= 1`` or
          any LSU is write-ACK or atomic (latency bound)

The microbenchmark family (paper SIV, Listings 3-5) expands to at most two
groups per design point; the Table IV applications to one or two.
"""
from __future__ import annotations

import numpy as np

#: LSU type names (paper Table I) and the codes used below.
TYPES = ("bc_aligned", "bc_non_aligned", "bc_cache", "bc_write_ack",
         "atomic_pipelined")
ALIGNED, NON_ALIGNED, CACHE, WRITE_ACK, ATOMIC = range(5)
CODE = {name: i for i, name in enumerate(TYPES)}

#: Columns every scored kernel reports.
ESTIMATE = ("t_exe", "t_ideal", "t_ovh", "bound_ratio", "total_bytes")

#: Grid axes in the order point ids count through them (first slowest).
GRID_AXES = ("lsu_type", "n_ga", "simd", "n_elems", "delta", "elem_bytes",
             "include_write", "val_constant", "dram", "bsp")

DRAM_FIELDS = ("dq", "bl", "f_mem", "t_rcd", "t_rp", "t_wr")
BSP_FIELDS = ("burst_cnt", "max_th")


def score_groups(g: dict, n_kernels: int) -> dict:
    """Eqs. 1-10 over LSU groups; ``g["kernel"]`` maps groups to kernels."""
    f64 = np.float64
    typ = g["type"]
    atomic, ack = typ == ATOMIC, typ == WRITE_ACK
    coalescing = (typ == ALIGNED) | (typ == NON_ALIGNED) | (typ == CACHE)
    count = g["count"].astype(f64)
    width, acc, nbytes = g["width"], g["acc"], g["bytes"]
    delta = g["delta"]
    dq, bl = g["dq"], g["bl"]

    bw = dq * 2.0 * g["f_mem"]
    min_burst = dq * bl
    max_txn = (2 ** g["burst_cnt"]) * min_burst
    total = acc * nbytes
    t_ideal = total / bw

    max_reqs = g["max_th"] * width / (delta + 1)
    burst = np.where(max_reqs <= max_txn, max_reqs / delta, width / delta)
    burst = np.where(typ == NON_ALIGNED, burst, 1.0 * max_txn)
    burst = np.where(atomic, 1.0 * min_burst, burst)
    n_bursts = total / burst
    t_row = g["t_rcd"] + g["t_rp"]
    t_row = np.where(ack, t_row + g["t_wr"],
                     np.where(atomic, 2.0 * t_row + g["t_wr"], t_row))

    n_lsu = np.bincount(g["kernel"], weights=count, minlength=n_kernels)
    single = n_lsu[g["kernel"]] < 2
    t_ovh = np.where(single, 0.0, n_bursts * t_row)
    t_ovh = t_ovh + np.where(ack, acc * np.maximum(min_burst - nbytes, 0)
                             / bw, 0.0)
    t_ovh = t_ovh + np.where(ack & single, n_bursts * t_row, 0.0)
    per_op = np.where(g["val_constant"], t_row / g["f"], t_row)
    t_ovh = np.where(atomic, acc * per_op, t_ovh)
    ratio = width / (min_burst * np.where(coalescing, 1.0 * delta, 1.0))

    def seg(x):
        return np.bincount(g["kernel"], weights=np.asarray(x, dtype=f64),
                           minlength=n_kernels)

    out = {
        "t_exe": seg(count * (delta * (t_ideal + t_ovh))),
        "t_ideal": seg(count * delta * t_ideal),
        "t_ovh": seg(count * delta * t_ovh),
        "bound_ratio": seg(count * ratio),
        "total_bytes": seg(count * total),
        "resource": seg(count * width),
    }
    latency = seg(count * (atomic | ack)) > 0
    out["memory_bound"] = (out["bound_ratio"] >= 1.0) | latency
    return out


def microbench_groups(p: dict) -> tuple[dict, int]:
    """The two LSU groups of each microbenchmark point (paper SIV).

    ``p`` holds per-point arrays: ``type`` codes, ``n_ga``, ``simd``,
    ``n_elems``, ``delta``, ``elem_bytes``, ``include_write``,
    ``val_constant`` and the DRAM/BSP fields.  Reads are ``n_ga`` LSUs of
    the point's type, ``simd`` lanes wide; the write is one more of the
    same type, except that a write-ACK store is ``simd`` scalar LSUs behind
    aligned reads and an atomic kernel is ``n_ga`` scalar atomic units.
    Stride only applies to coalescing types.
    """
    typ = np.asarray(p["type"], dtype=np.int64)
    n = len(typ)
    atomic, ack = typ == ATOMIC, typ == WRITE_ACK
    n_ga = np.asarray(p["n_ga"], dtype=np.int64)
    simd = np.asarray(p["simd"], dtype=np.int64)
    n_elems = np.asarray(p["n_elems"], dtype=np.int64)
    eb = np.asarray(p["elem_bytes"], dtype=np.int64)
    write = np.asarray(p["include_write"], dtype=bool) & ~atomic
    delta = np.where(atomic | ack, 1, np.asarray(p["delta"], dtype=np.int64))
    valc = np.asarray(p["val_constant"], dtype=bool) & atomic

    w1 = np.where(atomic, eb, simd * eb)
    both = np.concatenate
    g = {
        "kernel": both([np.arange(n), np.arange(n)]),
        "type": both([np.where(ack, ALIGNED, typ),
                      np.full(n, WRITE_ACK)]),
        "count": both([np.where(atomic | ack, n_ga, n_ga + write),
                       np.where(ack & write, simd, 0)]),
        "width": both([w1, eb]),
        "acc": both([np.where(atomic, n_elems, n_elems // simd),
                     n_elems // simd]),
        "bytes": both([w1, eb]),
        "delta": both([delta, np.ones(n, dtype=np.int64)]),
        "val_constant": both([valc, np.zeros(n, dtype=bool)]),
        "f": both([simd, simd]),
    }
    for k in DRAM_FIELDS + BSP_FIELDS:
        g[k] = both([np.asarray(p[k]), np.asarray(p[k])])
    return g, n


def app_groups(apps: list[dict], n_elems, hw: dict) -> tuple[dict, int]:
    """LSU groups of Table IV applications at the given input sizes.

    ``apps`` are the configuration's Table IV rows (``gmi``, ``n_read``,
    ``n_write``, ``delta``, ``simd``, ``elem_bytes``); a write-ACK app's
    LSUs are scalar and make ``n_elems`` accesses each, the others are
    ``simd`` lanes wide with ``n_elems // simd`` accesses.
    """
    n = len(apps)
    rows = {k: [] for k in ("kernel", "type", "count", "width", "acc",
                            "bytes", "delta", "f")}
    for i, (a, ne) in enumerate(zip(apps, n_elems)):
        code = CODE[a["gmi"]]
        if code == WRITE_ACK:
            w, acc, d = a["elem_bytes"], max(1, int(ne)), 1
        else:
            w = a["simd"] * a["elem_bytes"]
            acc, d = max(1, int(ne) // a["simd"]), a["delta"]
        rows["kernel"].append(i)
        rows["type"].append(code)
        rows["count"].append(a["n_read"] + a["n_write"])
        rows["width"].append(w)
        rows["acc"].append(acc)
        rows["bytes"].append(w)
        rows["delta"].append(d)
        rows["f"].append(a["simd"])
    g = {k: np.asarray(v, dtype=np.int64) for k, v in rows.items()}
    g["val_constant"] = np.zeros(n, dtype=bool)
    for k in DRAM_FIELDS + BSP_FIELDS:
        g[k] = np.asarray(hw[k])
    return g, n


def hardware_columns(drams: list[dict], bsps: list[dict], d_idx,
                     b_idx) -> dict:
    """Per-point DRAM/BSP fields gathered from the configuration's tables."""
    out = {k: np.asarray([d[k] for d in drams],
                         dtype=np.float64 if k.startswith(("f_", "t_"))
                         else np.int64)[d_idx] for k in DRAM_FIELDS}
    out.update({k: np.asarray([b[k] for b in bsps],
                              dtype=np.int64)[b_idx] for k in BSP_FIELDS})
    return out


def envelope_ok(p: dict, env: dict) -> np.ndarray:
    """Points a board can host: LSU ports, interconnect bytes, DRAM channels
    and burst-buffer bytes within the envelope (one port and ``ls_width``
    interconnect bytes per LSU, one max transaction of buffer per
    burst-coalesced LSU and one element per atomic unit)."""
    g, n = microbench_groups(p)
    c1, c2 = g["count"][:n].astype(np.float64), g["count"][n:].astype(
        np.float64)
    w1, eb = g["width"][:n], g["width"][n:]
    txn = (2.0 ** p["burst_cnt"]) * (p["dq"] * p["bl"])
    atomic = np.asarray(p["type"]) == ATOMIC
    ports = c1 + c2
    usage = {
        "lsu_ports": ports,
        "interconnect_bytes": c1 * w1 + c2 * eb,
        "buffer_bytes": c1 * np.where(atomic, w1, txn) + c2 * txn,
        "dram_channels": np.where(ports > 0, 1.0, 0.0),
    }
    ok = np.ones(n, dtype=bool)
    for k, cap in env.items():
        ok &= usage[k] <= cap
    return ok


def grid_points(lists: dict, ids: np.ndarray, drams, bsps) -> dict:
    """Per-point columns of grid ids (C order over :data:`GRID_AXES`)."""
    sizes = [len(lists[a]) for a in GRID_AXES]
    strides = np.cumprod([1] + sizes[::-1][:-1])[::-1]
    codes = {a: (ids // s) % m for a, s, m in zip(GRID_AXES, strides, sizes)}
    p = {a: np.asarray(lists[a])[codes[a]] for a in GRID_AXES
         if a not in ("lsu_type", "dram", "bsp")}
    p["type"] = np.asarray([CODE[t] for t in lists["lsu_type"]])[
        codes["lsu_type"]]
    p.update(hardware_columns(drams, bsps,
                              np.asarray(lists["dram"])[codes["dram"]],
                              np.asarray(lists["bsp"])[codes["bsp"]]))
    return p


def front_of(t: np.ndarray, r: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The (t_exe, resource) staircase: points no other point matches or
    beats in both, one per distinct pair, by increasing t_exe."""
    order = np.lexsort((r, t))
    t, r = t[order], r[order]
    prev = np.minimum.accumulate(np.concatenate([[np.inf], r[:-1]]))
    keep = r < prev
    return t[keep], r[keep]


def sweep(lists: dict, drams: list[dict], bsps: list[dict], *,
          envelope: dict | None = None, k: int = 10,
          block: int = 1 << 20) -> dict:
    """The whole grid scored and folded, block by block.

    Returns the exact point and memory-bound counts, the points within 1e-6
    of Eq. 3's threshold, the minimum, sums, mean and population variance
    of ``t_exe``, the ``k`` smallest ``t_exe`` values, and the front.
    """
    n = int(np.prod([len(lists[a]) for a in GRID_AXES]))
    t_all, tb_sum = [], 0.0
    count = mb = near = 0
    topk = np.empty(0)
    ft, fr = np.empty(0), np.empty(0)
    for lo in range(0, n, block):
        ids = np.arange(lo, min(lo + block, n), dtype=np.int64)
        p = grid_points(lists, ids, drams, bsps)
        if envelope is not None:
            ok = envelope_ok(p, envelope)
            p = {key: v[ok] for key, v in p.items()}
        if len(p["type"]) == 0:
            continue
        g, m = microbench_groups(p)
        est = score_groups(g, m)
        t = est["t_exe"]
        count += m
        mb += int(np.count_nonzero(est["memory_bound"]))
        near += int(np.count_nonzero(np.abs(est["bound_ratio"] - 1.0)
                                     <= 1e-6))
        t_all.append(t)
        tb_sum += float(np.sum(est["total_bytes"]))
        topk = np.sort(np.concatenate([topk, np.partition(
            t, min(k, m) - 1)[:k]]))[:k]
        ft, fr = front_of(np.concatenate([ft, t]),
                          np.concatenate([fr, est["resource"]]))
    t = np.concatenate(t_all) if t_all else np.empty(0)
    mean = float(np.sum(t) / count) if count else 0.0
    return {
        "n_points": count, "memory_bound_points": mb, "near_threshold": near,
        "t_exe_min": float(t.min()) if count else np.inf,
        "t_exe_sum": float(np.sum(t)), "total_bytes_sum": tb_sum,
        "t_exe_mean": mean,
        "t_exe_var": float(np.mean((t - mean) ** 2)) if count else 0.0,
        "topk": topk, "front_t": ft, "front_r": fr,
    }
