"""Vectorized design-space sweeps over the paper's analytical model.

The paper's pitch is *fast* exploration: the closed-form Eqs. 1-10 exist so
thousands of candidate designs can be scored without building any of them.
This module turns the array core (:mod:`repro.core.model_batch`) into that
workflow: describe a design space over the SIV microbenchmark knobs — LSU
type, number of global accesses, SIMD width, input size, stride, element
size, DRAM part, BSP variant — and score every point in one pass.

The public entry points are :class:`repro.Space` and
``repro.Session.sweep``:

    >>> from repro import Session, Space
    >>> res = Session().sweep(Space.grid(
    ...     lsu_type=[LsuType.BC_ALIGNED, LsuType.BC_WRITE_ACK],
    ...     n_ga=[1, 2, 4], simd=[1, 4, 16],
    ...     delta=[1, 2, 4], dram=[DDR4_1866, DDR4_2666]))
    >>> best = res.top_k(5)
    >>> front = res.pareto()          # time vs interconnect-width cost

Design points are described by integer codes end-to-end: every categorical
axis (LSU type, DRAM part, BSP variant, hardware spec) is factorized once
into a ``(table, codes)`` pair and per-point values are table gathers, so
the hot path never touches an object-dtype array.  The same scoring core
(:func:`_score`) backs both the materialized path below and the
bounded-memory streaming path (:mod:`repro.core.stream` +
``Space.grid(...).stream()``), which is how million-point spaces are swept.

Every design point maps to exactly the LSU list `apps.microbench` would
build, so batched results match the scalar estimate path element-wise
(tested to rtol 1e-6 in tests/test_sweep.py).
"""
from __future__ import annotations

import dataclasses
import numbers
from typing import Any, Callable, Mapping, Sequence

import numpy as np

from repro.core import model_batch as _mb
from repro.core.fpga import BspParams
from repro.core.lsu import LsuType
from repro.core.spans import span

#: Sweepable axes, in canonical order.  ``lsu_type``/``dram``/``bsp``/
#: ``hardware`` are categorical; the rest are numeric.  A ``hardware`` axis
#: value is a :class:`repro.hw.Hardware` spec (or ``None``): its DRAM/BSP
#: views and persisted calibration override the ``dram``/``bsp`` axes at
#: that point, so a single sweep fans out over (design x memory system).
AXES = ("lsu_type", "n_ga", "simd", "n_elems", "delta", "elem_bytes",
        "include_write", "val_constant", "dram", "bsp", "hardware")

_CATEGORICAL = {"lsu_type", "dram", "bsp", "hardware"}
_NUMERIC = tuple(a for a in AXES if a not in _CATEGORICAL)


def _as_list(v) -> list:
    if isinstance(v, (list, tuple)):
        return list(v)
    if isinstance(v, np.ndarray):
        return v.tolist()
    return [v]


def _object_array(values) -> np.ndarray:
    """1-D object array from a list (safe for dataclass/None elements)."""
    arr = np.empty(len(values), dtype=object)
    arr[:] = list(values)
    return arr


def _pareto_scan(vals: np.ndarray) -> np.ndarray:
    """Reference O(N·F) front: lexsort + per-candidate scan (any dimension).

    This was the only implementation before the streaming engine landed;
    it is kept both as the d != 2 fallback and as the measured baseline of
    ``benchmarks/sweep_bench.py`` (the "materialize everything, then scan"
    legacy cost).
    """
    n = len(vals)
    # Lexicographic order makes any dominator of row i appear before i, so a
    # single forward scan against the kept front is complete.
    order = np.lexsort(tuple(vals[:, d] for d in range(vals.shape[1] - 1, -1, -1)))
    # The front lives in a preallocated [n, d] buffer filled left to right;
    # each candidate is checked against the fv[:m] *view*, so keeping a point
    # is O(F) instead of a copy-the-front-per-point O(F^2).
    fv = np.empty_like(vals)
    m = 0
    keep: list[int] = []
    for idx in order:
        v = vals[idx]
        if m:
            front = fv[:m]
            if np.any((front <= v).all(axis=1) & (front < v).any(axis=1)):
                continue
        fv[m] = v
        m += 1
        keep.append(int(idx))
    return np.asarray(sorted(keep), dtype=np.int64)


def _pareto_2d(vals: np.ndarray) -> np.ndarray:
    """Fully vectorized 2-objective front, O(N log N), no Python loop.

    Sort by (v0, v1); a row is dominated iff some row in a strictly
    smaller v0 group has v1 <= its own (strict v0 makes the domination
    strict), or a row in its *own* v0 group has strictly smaller v1.
    Duplicated non-dominated rows all survive, exactly like the scan.
    """
    n = len(vals)
    order = np.lexsort((vals[:, 1], vals[:, 0]))
    v0 = vals[order, 0]
    v1 = vals[order, 1]
    new_group = np.empty(n, dtype=bool)
    new_group[0] = True
    new_group[1:] = v0[1:] != v0[:-1]
    start = np.maximum.accumulate(np.where(new_group, np.arange(n), 0))
    gmin = v1[start]                       # group min (v1 ascending in group)
    cm = np.minimum.accumulate(v1)         # min v1 over all earlier rows
    prev_end = start - 1                   # last row of the previous group
    m_strict = np.where(prev_end >= 0, cm[np.maximum(prev_end, 0)], np.inf)
    dominated = (m_strict <= v1) | (gmin < v1)
    return np.sort(order[~dominated]).astype(np.int64)


def pareto_front(values: np.ndarray) -> np.ndarray:
    """Indices of the Pareto-minimal rows of ``values`` [N, d].

    A row dominates another if it is <= in every objective and < in at least
    one.  Duplicated non-dominated rows are all kept.  The returned indices
    are sorted ascending, and the *set* of selected points is invariant under
    any permutation of the input rows.

    The 2-objective case (the default time-vs-resource trade-off) runs a
    fully vectorized O(N log N) pass — this is what lets the streaming
    reducers fold million-point sweeps without a per-point Python loop;
    higher dimensions fall back to the lexsort + scan reference.
    """
    vals = np.asarray(values, dtype=np.float64)
    if vals.ndim == 1:
        vals = vals[:, None]
    if len(vals) == 0:
        return np.empty(0, dtype=np.int64)
    if vals.shape[1] == 2:
        return _pareto_2d(vals)
    return _pareto_scan(vals)


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Scored design space: per-point config values + batched model output."""

    points: dict[str, np.ndarray]     # axis -> per-point values [N]
    estimate: _mb.BatchEstimate
    resource: np.ndarray              # total LSU interconnect width [B] per point

    @property
    def n_points(self) -> int:
        return int(len(self.resource))

    @property
    def t_exe(self) -> np.ndarray:
        return np.asarray(self.estimate.t_exe)

    @property
    def memory_bound(self) -> np.ndarray:
        return np.asarray(self.estimate.memory_bound)

    @property
    def effective_bandwidth(self) -> np.ndarray:
        return np.asarray(self.estimate.effective_bandwidth)

    def pareto(self, objectives: Sequence[Any] | None = None) -> np.ndarray:
        """Indices of the Pareto front, minimizing every objective.

        Default objectives: predicted time vs. total LSU width (the
        interconnect/resource cost of the design).  Pass an explicit list of
        arrays or names in (``t_exe``, ``resource``, ``bound_ratio``,
        ``total_bytes``) to change the trade-off.
        """
        if objectives is None:
            objectives = ["t_exe", "resource"]
        cols = []
        for obj in objectives:
            if isinstance(obj, str):
                if obj == "t_exe":
                    cols.append(self.t_exe)
                elif obj == "resource":
                    cols.append(self.resource)
                elif obj == "bound_ratio":
                    cols.append(np.asarray(self.estimate.bound_ratio))
                elif obj == "total_bytes":
                    cols.append(np.asarray(self.estimate.total_bytes))
                else:
                    raise KeyError(f"unknown objective {obj!r}")
            else:
                cols.append(np.asarray(obj, dtype=np.float64))
        return pareto_front(np.stack(cols, axis=1))

    def top_k(self, k: int = 10, key: str = "t_exe") -> list[dict]:
        """The ``k`` best rows by ``key`` (ascending), as config dicts."""
        vals = {"t_exe": self.t_exe, "resource": self.resource}[key] \
            if key in ("t_exe", "resource") else np.asarray(getattr(self.estimate, key))
        idx = np.argsort(vals, kind="stable")[:k]
        return self.rows(idx)

    def rows(self, indices: Sequence[int] | None = None) -> list[dict]:
        """CSV-ready dict rows for the selected (default: all held) points."""
        est = self.estimate
        ebw = self.effective_bandwidth
        if indices is None:
            indices = range(len(self.resource))
        out = []
        for i in indices:
            i = int(i)
            row = {}
            for name, vals in self.points.items():
                v = vals[i]
                if name == "lsu_type":
                    v = LsuType(v).value if not isinstance(v, LsuType) else v.value
                elif name == "bsp":
                    v = _bsp_name(v)
                elif name == "dram":
                    v = getattr(v, "name", repr(v))
                elif name == "hardware":
                    v = getattr(v, "name", "") if v is not None else ""
                elif isinstance(v, (np.integer, np.bool_)):
                    v = v.item()
                row[name] = v
            row.update(
                t_exe_ms=float(est.t_exe[i]) * 1e3,
                t_ovh_ms=float(est.t_ovh[i]) * 1e3,
                bound_ratio=float(est.bound_ratio[i]),
                memory_bound=bool(est.memory_bound[i]),
                eff_bw_gbs=float(ebw[i]) / 1e9,
                resource_bytes=float(self.resource[i]),
            )
            out.append(row)
        return out


def _bsp_name(b: BspParams) -> str:
    return f"bsp(burst_cnt={b.burst_cnt},max_th={b.max_th})"


def _factorize(objs) -> tuple[list, np.ndarray]:
    """(unique objects, per-row codes) — attribute extraction then runs per
    unique value instead of per design point (the batched-path hotspot)."""
    table: list = []
    index: dict[int, int] = {}
    codes = np.empty(len(objs), dtype=np.int64)
    for i, o in enumerate(objs):
        j = index.get(id(o))
        if j is None:
            j = index[id(o)] = len(table)
            table.append(o)
        codes[i] = j
    return table, codes


def _hardware_views(table: Sequence) -> tuple[list, list, np.ndarray, np.ndarray]:
    """Per-unique-spec (dram view, bsp view, host factor, is-None mask).

    Views are constructed once per unique spec — the dedup contract the old
    per-point loop kept via identity caching, now explicit in the table.
    ``None`` entries get placeholder views that are never gathered.
    """
    drams, bsps, hf, is_none = [], [], [], []
    for h in table:
        if h is None:
            drams.append(None)
            bsps.append(None)
            hf.append(1.0)
            is_none.append(True)
        else:
            drams.append(h.dram_params())
            bsps.append(h.bsp_params())
            hf.append(float(h.host_factor))
            is_none.append(False)
    return drams, bsps, np.asarray(hf), np.asarray(is_none, dtype=bool)


def _apply_hardware_axis(points: dict[str, np.ndarray], n: int,
                         ) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """Resolve the ``hardware`` axis into effective dram/bsp columns.

    Points whose hardware spec is not ``None`` get that spec's DRAM/BSP
    views in their ``dram``/``bsp`` columns (so reported configurations
    describe what was actually scored) and its persisted ``host_factor`` in
    the returned per-point scale array.  Fully vectorized: the hardware
    column is factorized once and the views are table gathers — no
    per-point Python loop.  Used by the scalar Session backend (the coded
    batched path resolves through :func:`_resolve_hardware_codes`); the two
    paths must resolve identically for backend equivalence to hold.
    """
    hw_col = points.get("hardware")
    scale = np.ones(n)
    if hw_col is None or all(h is None for h in hw_col):
        return points, scale
    table, codes = _factorize(hw_col)
    drams, bsps, hf, is_none = _hardware_views(table)
    own = is_none[codes]
    scale = np.where(own, 1.0, hf[codes])
    dram_col = np.where(own, np.asarray(points["dram"], dtype=object),
                        _object_array(drams)[codes])
    bsp_col = np.where(own, np.asarray(points["bsp"], dtype=object),
                       _object_array(bsps)[codes])
    return {**points, "dram": dram_col, "bsp": bsp_col}, scale


def _resolve_hardware_codes(cats: dict[str, tuple[list, np.ndarray]], n: int,
                            ) -> tuple[dict, np.ndarray, np.ndarray]:
    """Coded counterpart of :func:`_apply_hardware_axis`.

    Rewrites the ``dram``/``bsp`` ``(table, codes)`` pairs so points with a
    hardware spec index that spec's views (appended to the tables), and
    returns ``(cats, host-factor scale [n], own mask [n])`` where ``own``
    marks points running on the session's own hardware (spec is ``None``).
    No object-dtype column is ever built.
    """
    hw_table, hw_codes = cats["hardware"]
    if all(h is None for h in hw_table):
        return cats, np.ones(n), np.ones(n, dtype=bool)
    drams, bsps, hf, is_none = _hardware_views(hw_table)
    own = is_none[np.asarray(hw_codes)]
    scale = np.where(own, 1.0, hf[hw_codes])
    d_table, d_codes = cats["dram"]
    b_table, b_codes = cats["bsp"]
    new_d = (list(d_table) + drams,
             np.where(own, d_codes, len(d_table) + np.asarray(hw_codes)))
    new_b = (list(b_table) + bsps,
             np.where(own, b_codes, len(b_table) + np.asarray(hw_codes)))
    return {**cats, "dram": new_d, "bsp": new_b}, scale, own


def _normalize_inert_axes(points: dict[str, np.ndarray],
                          is_atomic: np.ndarray,
                          is_ack: np.ndarray) -> dict[str, np.ndarray]:
    """Normalize axes that are inert for a point's LSU type.

    Stride is inert for ACK/atomic, ``val_constant`` for non-atomics, and
    ``include_write`` for atomics (the atomic *is* the write), so reported
    configs describe exactly what was scored; grid products over inert axes
    thus show up as *visibly* identical rows rather than phantom distinct
    designs.  Shared by ``_score`` and the scalar Session backend — the two
    paths must normalize identically for backend equivalence to hold.
    """
    delta = np.where(is_atomic | is_ack, 1,
                     np.asarray(points["delta"], dtype=np.int64))
    val_constant = np.asarray(points["val_constant"], dtype=bool) & is_atomic
    include_write = (np.asarray(points["include_write"], dtype=bool)
                     & ~is_atomic)
    return {**points, "delta": delta, "val_constant": val_constant,
            "include_write": include_write}


def _score(numeric: dict[str, np.ndarray],
           cats: dict[str, tuple[list, np.ndarray]], n: int,
           estimator: Callable[[_mb.GroupBatch], _mb.BatchEstimate] | None = None,
           prof: dict | None = None,
           ) -> tuple[_mb.BatchEstimate, np.ndarray, dict, dict, np.ndarray]:
    """Score ``n`` design points given numeric columns + coded categoricals.

    This is the shared core of the materialized (:func:`_build`) and
    streaming (``Session.sweep(chunk_size=...)``) paths: per-point numeric
    arrays for the numeric axes, ``(table, codes)`` pairs for every
    categorical axis, no object arrays anywhere.  ``estimator`` maps the
    assembled :class:`model_batch.GroupBatch` to a
    :class:`model_batch.BatchEstimate`; it defaults to the NumPy array core
    and is how ``Session`` backends (jax-jit) plug into the same expansion.

    Each point expands to the LSU list ``apps.microbench`` would build,
    expressed as at most two homogeneous LSU *groups* per point:

    * burst-coalesced aligned/non-aligned/cache: one group of
      ``n_ga + include_write`` identical LSUs;
    * write-ACK: a group of ``n_ga`` aligned reads plus a group of ``simd``
      scalar ACK stores (the compiler replicates the store LSU);
    * atomic: a group of ``n_ga`` atomic units (stride is always 1).

    The expansion, up to the estimator call, is the ``chunk.pack`` span
    (:mod:`repro.core.spans`), added to ``prof``.

    Returns ``(estimate, resource, resolved cats, normalized numeric,
    own-hardware mask)``.
    """
    with span("chunk.pack", prof):
        batch, cats, numeric, hw_scale, own = _pack(numeric, cats, n)
    est = (estimator or _mb.estimate_batch)(batch)
    if np.any(hw_scale != 1.0):
        # apply each point's persisted hardware calibration (host_factor)
        est = dataclasses.replace(
            est, t_exe=np.asarray(est.t_exe) * hw_scale,
            t_ideal=np.asarray(est.t_ideal) * hw_scale,
            t_ovh=np.asarray(est.t_ovh) * hw_scale)
    resource = np.bincount(batch.kernel,
                           weights=np.asarray(batch.count * batch.ls_width,
                                              dtype=np.float64),
                           minlength=n)
    return est, resource, cats, numeric, own


def _pack(numeric: dict[str, np.ndarray],
          cats: dict[str, tuple[list, np.ndarray]], n: int) -> tuple:
    """:func:`_score`'s expansion of ``n`` points into a
    :class:`model_batch.GroupBatch`: ``(batch, resolved cats, normalized
    numeric, hardware scale, own-hardware mask)``."""
    cats, hw_scale, own = _resolve_hardware_codes(cats, n)

    type_table, type_idx = cats["lsu_type"]
    type_codes = np.asarray([_mb.TYPE_CODE[t] for t in type_table],
                            dtype=np.int64)[type_idx]
    n_ga = np.asarray(numeric["n_ga"], dtype=np.int64)
    simd = np.asarray(numeric["simd"], dtype=np.int64)
    n_elems = np.asarray(numeric["n_elems"], dtype=np.int64)
    elem_bytes = np.asarray(numeric["elem_bytes"], dtype=np.int64)
    dram_table, dram_idx = cats["dram"]
    bsp_table, bsp_idx = cats["bsp"]

    if np.any(n_ga < 1) or np.any(simd < 1) \
            or np.any(np.asarray(numeric["delta"], dtype=np.int64) < 1):
        raise ValueError("n_ga, simd and delta must be >= 1")
    if np.any(n_elems % simd):
        raise ValueError("n_elems must be divisible by simd at every point")

    is_atomic = type_codes == _mb.ATOMIC
    is_ack = type_codes == _mb.WRITE_ACK

    numeric = _normalize_inert_axes(numeric, is_atomic, is_ack)
    delta = numeric["delta"]
    val_constant = numeric["val_constant"]
    include_write = numeric["include_write"]

    # Group 1: the read side (plus the same-type write for plain BC types).
    g1_type = np.where(is_ack, _mb.ALIGNED, type_codes)
    g1_count = np.where(is_atomic | is_ack, n_ga, n_ga + include_write)
    g1_width = np.where(is_atomic, elem_bytes, simd * elem_bytes)
    g1_acc = np.where(is_atomic, n_elems, n_elems // simd)
    g1_delta = delta                      # already normalized above

    # Group 2: the replicated write-ACK store LSUs (count 0 elsewhere).
    g2_count = np.where(is_ack & include_write, simd, 0)

    kernel = np.concatenate([np.arange(n), np.arange(n)])
    vec = np.concatenate
    dram_f = {k: np.asarray([getattr(d, k) if d is not None else 0
                             for d in dram_table])[dram_idx]
              for k in ("dq", "bl", "f_mem", "t_rcd", "t_rp", "t_wr")}
    bsp_f = {k: np.asarray([getattr(b, k) if b is not None else 0
                            for b in bsp_table])[bsp_idx]
             for k in ("burst_cnt", "max_th")}

    batch = _mb.GroupBatch(
        kernel=kernel,
        n_kernels=n,
        count=vec([g1_count, g2_count]),
        lsu_type=vec([g1_type, np.full(n, _mb.WRITE_ACK, dtype=np.int64)]),
        ls_width=vec([g1_width, elem_bytes]),
        ls_acc=vec([g1_acc, n_elems // simd]),
        ls_bytes=vec([g1_width, elem_bytes]),
        delta=vec([g1_delta, np.ones(n, dtype=np.int64)]),
        val_constant=vec([val_constant, np.zeros(n, dtype=bool)]),
        f=vec([simd, simd]),
        **{k: vec([v, v]) for k, v in {**dram_f, **bsp_f}.items()},
    )
    return batch, cats, numeric, hw_scale, own


def _score_scalar(points: dict, n: int,
                  cats: dict[str, tuple[list, np.ndarray]]) -> SweepResult:
    """Reference scalar loop over the same points :func:`_score` would score.

    Each point expands through ``apps.microbench`` (the proven-equal scalar
    path) and is estimated by the readable per-LSU model
    (:func:`repro.core.model._estimate`); the hardware axis and inert axes
    are resolved exactly like ``_score`` so the reported configurations
    match across backends.  A free function of its inputs only — no
    session state — so :class:`repro.core.stream.SweepPlan` can rebuild
    the scalar backend in a fresh worker process.
    """
    from repro.core import apps as _apps
    from repro.core import model as _model

    points = {name: (points[name] if name in points
                     else _object_array(cats[name][0])[cats[name][1]])
              for name in AXES}   # canonical column order
    points, hw_scale = _apply_hardware_axis(points, n)
    lsu_types = [points["lsu_type"][i] for i in range(n)]
    is_atomic = np.array([t is LsuType.ATOMIC_PIPELINED
                          for t in lsu_types], dtype=bool)
    is_ack = np.array([t is LsuType.BC_WRITE_ACK for t in lsu_types],
                      dtype=bool)
    points = _normalize_inert_axes(points, is_atomic, is_ack)
    delta = points["delta"]
    val_constant = points["val_constant"]
    include_write = points["include_write"]

    cols = {k: np.empty(n) for k in
            ("t_exe", "t_ideal", "t_ovh", "bound_ratio", "total_bytes")}
    memory_bound = np.empty(n, dtype=bool)
    # float64 like the batched path, whose np.bincount segment sum promotes
    # the integer LSU counts — reducer states must agree across backends
    n_lsu = np.empty(n)
    resource = np.empty(n)
    for i in range(n):
        simd = int(points["simd"][i])
        lsus = _apps.microbench(
            lsu_types[i],
            n_ga=int(points["n_ga"][i]),
            simd=simd,
            n_elems=int(points["n_elems"][i]),
            delta=int(delta[i]),               # inert axes normalized above
            elem_bytes=int(points["elem_bytes"][i]),
            include_write=bool(include_write[i]),
            val_constant=bool(val_constant[i]))
        ke = _model._estimate(list(lsus), points["dram"][i], points["bsp"][i],
                              f=simd)
        cols["t_exe"][i] = ke.t_exe * hw_scale[i]
        cols["t_ideal"][i] = ke.t_ideal * hw_scale[i]
        cols["t_ovh"][i] = ke.t_ovh * hw_scale[i]
        cols["bound_ratio"][i] = ke.bound_ratio
        cols["total_bytes"][i] = ke.total_bytes
        memory_bound[i] = ke.memory_bound
        n_lsu[i] = len(ke.per_lsu)
        resource[i] = sum(l.ls_width for l in lsus if l.lsu_type.is_global)
    est = _mb.BatchEstimate(
        t_exe=cols["t_exe"], t_ideal=cols["t_ideal"],
        t_ovh=cols["t_ovh"], bound_ratio=cols["bound_ratio"],
        memory_bound=memory_bound, total_bytes=cols["total_bytes"],
        n_lsu=n_lsu, groups={})
    return SweepResult(points=points, estimate=est, resource=resource)


def _materialize_points(numeric: dict[str, np.ndarray],
                        cats: dict[str, tuple[list, np.ndarray]],
                        ) -> dict[str, np.ndarray]:
    """Per-point axis columns in canonical ``AXES`` order (object gathers
    for the categorical axes — the one place they are built)."""
    points: dict[str, np.ndarray] = {}
    for name in AXES:
        if name in _CATEGORICAL:
            table, codes = cats[name]
            points[name] = _object_array(table)[codes]
        else:
            points[name] = np.asarray(numeric[name])
    return points


def _build(points: dict[str, np.ndarray], n: int,
           cats: dict[str, tuple[list, np.ndarray]],
           estimator: Callable[[_mb.GroupBatch], _mb.BatchEstimate] | None = None,
           prof: dict | None = None) -> SweepResult:
    """Materialized scoring: every point's config + estimate held in memory.

    ``points`` carries the numeric per-point columns; ``cats`` must carry a
    ``(table, codes)`` pair for every categorical axis (``_grid_points`` /
    ``_random_points`` always do).  The returned ``SweepResult.points``
    holds the *resolved* configuration — hardware-axis dram/bsp overrides
    applied, inert axes normalized — exactly what was scored.
    """
    numeric = {k: points[k] for k in _NUMERIC}
    est, resource, cats, numeric, _ = _score(numeric, cats, n, estimator,
                                             prof)
    return SweepResult(points=_materialize_points(numeric, cats),
                       estimate=est, resource=resource)


def _normalize_axes(overrides: Mapping[str, Any]) -> dict[str, list]:
    from repro.hw import DEFAULT_BOARD, get as _hw_get

    board = _hw_get(DEFAULT_BOARD)
    defaults = {
        "lsu_type": LsuType.BC_ALIGNED,
        "n_ga": 1,
        "simd": 16,
        "n_elems": 1 << 22,
        "delta": 1,
        "elem_bytes": 4,
        "include_write": True,
        "val_constant": False,
        "dram": board.dram_params(),
        "bsp": board.bsp_params(),
        "hardware": None,
    }
    unknown = set(overrides) - set(AXES)
    if unknown:
        raise KeyError(f"unknown sweep axes: {sorted(unknown)}")
    return {k: _as_list(overrides.get(k, defaults[k])) for k in AXES}


def _grid_points(axes: Mapping[str, Any],
                 ) -> tuple[dict[str, np.ndarray], int,
                            dict[str, tuple[list, np.ndarray]]]:
    """Per-point axis arrays for the full Cartesian product of ``axes``.

    Point ids are decoded with mixed-radix index arithmetic (see
    :class:`repro.core.stream.GridEnumerator`) rather than ``np.meshgrid``,
    so this shares its enumeration — point ``i`` here is point ``i`` of the
    streaming path — while materializing only integer code arrays:
    ``points`` carries the numeric columns, the categorical axes live in
    ``cats`` as ``(table, codes)`` only (consumers that need per-point
    objects, like the scalar backend, gather them from ``cats``).
    """
    from repro.core.stream import GridEnumerator

    enum = GridEnumerator(_normalize_axes(axes))
    codes = enum.codes(np.arange(enum.n, dtype=np.int64))
    points: dict[str, np.ndarray] = {}
    cats: dict[str, tuple[list, np.ndarray]] = {}
    for name, vals in enum.lists.items():
        idx = codes[name]
        if name in _CATEGORICAL:
            cats[name] = (vals, idx)
        else:
            points[name] = np.asarray(vals)[idx]
    return points, enum.n, cats


def _is_numeric_range(v) -> bool:
    """True for a 2-tuple that means an inclusive integer range (lo, hi).

    *Both* elements must be plain numbers: a pair of categorical values —
    e.g. two :class:`LsuType` members, or booleans — is a 2-element value
    list to sample from, not a range, regardless of which element is which
    (checking only ``v[0]`` misclassified mixed pairs).
    """
    return (isinstance(v, tuple) and len(v) == 2
            and all(isinstance(x, numbers.Real)
                    and not isinstance(x, bool)
                    and not isinstance(x, LsuType) for x in v))


def _random_points(n: int, seed: int, axes: Mapping[str, Any],
                   constraints: tuple = (),
                   ) -> tuple[dict[str, np.ndarray], int,
                              dict[str, tuple[list, np.ndarray]]]:
    """Per-point axis arrays for ``n`` uniformly sampled design points.

    Numeric axes given as a 2-tuple ``(lo, hi)`` of numbers are sampled as
    integers in the inclusive range; any axis given as a list (or a tuple
    that is not a numeric pair — e.g. two ``LsuType`` values) is sampled
    uniformly from it; scalars are held fixed.  Each ``n_elems`` sample is
    rounded down to a multiple of *that point's own* ``simd`` (floored at
    ``simd``), so the sampled values stay inside the requested range
    whenever it contains any multiple of the point's simd — rounding to the
    global LCM of all sampled simd values could leave the range entirely.

    With ``constraints``, sampling is seeded rejection: draw a batch, keep
    the feasible rows (uniform over the feasible region, since rejection
    preserves the base distribution), repeat until ``n`` points or a
    bounded attempt budget runs out — then fail loudly instead of emitting
    infeasible points or spinning forever on an empty feasible region.
    """
    rng = np.random.default_rng(seed)
    tuples = {k: v for k, v in axes.items()
              if k not in _CATEGORICAL and _is_numeric_range(v)}
    lists = _normalize_axes({k: v for k, v in axes.items() if k not in tuples})

    def draw(m: int) -> tuple[dict[str, np.ndarray],
                              dict[str, tuple[list, np.ndarray]]]:
        points: dict[str, np.ndarray] = {}
        cats: dict[str, tuple[list, np.ndarray]] = {}
        for name in AXES:
            if name in tuples:
                lo, hi = tuples[name]
                points[name] = rng.integers(int(lo), int(hi) + 1, size=m)
            else:
                vals = lists[name]
                idx = rng.integers(0, len(vals), size=m)
                if name in _CATEGORICAL:
                    cats[name] = (vals, idx)
                else:
                    points[name] = np.asarray(vals)[idx]
        simd = np.asarray(points["simd"], dtype=np.int64)
        n_elems = np.asarray(points["n_elems"], dtype=np.int64)
        points["n_elems"] = np.maximum((n_elems // simd) * simd, simd)
        return points, cats

    if not constraints or n <= 0:
        points, cats = draw(n)
        return points, n, cats

    from repro.search.constraints import (
        columns_from_parts,
        feasibility_mask,
        normalize_constraints,
    )

    constraints = normalize_constraints(constraints)
    batch = max(int(n), 1024)
    budget = 256 * int(n) + 10_000          # total draws before giving up
    drawn = found = 0
    kept_points: list[dict[str, np.ndarray]] = []
    kept_codes: list[dict[str, np.ndarray]] = []
    tables: dict[str, list] = {}
    while found < n and drawn < budget:
        m = min(batch, budget - drawn)
        points, cats = draw(m)
        drawn += m
        mask = feasibility_mask(
            constraints, columns_from_parts(points, cats, m))
        if not mask.any():
            continue
        kept_points.append({k: v[mask] for k, v in points.items()})
        kept_codes.append({k: idx[mask] for k, (_, idx) in cats.items()})
        tables = {k: vals for k, (vals, _) in cats.items()}
        found += int(mask.sum())
    if found < n:
        region = ("appears empty" if found == 0
                  else f"yielded only {found} of {n} requested points")
        raise ValueError(
            f"constrained random sampling: the feasible region {region} "
            f"after {drawn} seeded draws; relax the constraints or widen "
            f"the axis ranges")
    points = {k: np.concatenate([p[k] for p in kept_points])[:n]
              for k in kept_points[0]}
    cats = {k: (tables[k], np.concatenate([c[k] for c in kept_codes])[:n])
            for k in kept_codes[0]}
    return points, n, cats
