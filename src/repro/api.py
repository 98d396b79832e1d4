"""Unified ``Design``/``Session`` API: one design description, one pipeline.

The paper's value is a single analytical flow — describe a memory
architecture once, get a fast prediction — but the repo historically grew
five disjoint entry points (``model.estimate``, ``model_batch.estimate_batch``,
``sweep.sweep_grid``/``sweep_random``, ``predictor.predict``,
``validate.validate``) that each re-invented how a design point, hardware
parameters and calibration were specified.  This module consolidates them:

* :class:`Design` — a frozen, self-contained description of one design
  point: the LSU groups (paper Table II), optional per-design DRAM/BSP
  overrides, the vectorization factor, and optional compute-side metadata
  when the design was read off a compiled artifact.  Builder-style
  ``with_*`` helpers derive variants; ``from_hlo``/``from_kernel`` read a
  design straight out of a compiled XLA executable (the transplant of
  reading the HLS early report), ``microbench``/``from_app`` build the
  paper's SIV/Table IV designs.
* :class:`Space` — a declarative design *space*: the Cartesian grid or a
  random sample over the microbenchmark axes of :mod:`repro.core.sweep`.
* :class:`Session` — the evaluation context: hardware parameters (DRAM +
  BSP for the faithful FPGA model, :class:`~repro.core.hbm.TpuParams` for
  the TPU transplant), a calibration factor, and a compute backend
  (``scalar`` | ``numpy-batch`` | ``jax-jit``).  Every pipeline stage is a
  method: ``estimate``, ``sweep``, ``autotune``, ``validate``,
  ``roofline``, ``predict`` — and ``serve`` turns the session into a
  long-lived concurrent query service (:class:`repro.core.serving.Server`:
  micro-batched scoring, content-hash LRU result cache, p50/p99 stats).
* :class:`Estimate` and the :class:`Report` family — one shared result
  vocabulary across all of those stages (``rows()`` / ``to_csv()`` /
  ``summary()``), instead of today's per-module dataclasses.

All three backends run the *same* equations (the array core in
:mod:`repro.core.model_batch`) and agree element-wise to 1e-6; the jax-jit
backend evaluates under ``jax.jit`` with x64 enabled so results are
bit-comparable with NumPy (tests/test_api.py).

    >>> from repro import Design, Session, Space
    >>> sess = Session()                       # DDR4-1866, numpy-batch
    >>> est = sess.estimate(Design.microbench(LsuType.BC_ALIGNED, n_ga=4))
    >>> res = sess.sweep(Space.grid(n_ga=[1, 2, 4], simd=[1, 4, 16]))
    >>> res.top_k(3)
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Callable, Iterable, Mapping, Sequence

import numpy as np

from repro.core import apps as _apps
from repro.core import model as _model
from repro.core import model_batch as _mb
from repro.core import sweep as _sweep
from repro.core.fpga import BspParams, DramParams
from repro.core.stream import SweepPlan
from repro.core.hbm import TpuParams
from repro.core.lsu import Lsu, LsuType, make_global_access
from repro.core.spans import count, span
from repro.hw import DEFAULT_BOARD, DEFAULT_CHIP, Hardware
from repro.hw import get as _hw_get
from repro.hw import preset_for_device_kind as _hw_preset_for

#: Supported Session compute backends, in increasing batch-friendliness.
BACKENDS = ("scalar", "numpy-batch", "jax-jit")

__all__ = [
    "BACKENDS", "EXECUTORS",
    "Design", "Space", "Session", "SweepPlan",
    "Estimate", "Report", "SweepReport", "AutotuneReport", "ValidateReport",
    "RooflineReport",
    # the serving layer (Session.serve) and its failure vocabulary
    "Server", "ServerClosed", "ServerOverloaded", "RequestTimeout",
]

#: Supported Session.sweep executors: the in-process chunk pipeline and the
#: coordinator/worker process pool (repro.core.distributed).
EXECUTORS = ("threads", "processes")

#: Per-process id of each ``Session.sweep``, an arg of its ``repro.sweep``
#: span.
_SWEEP_IDS = itertools.count(1)

#: LSU types whose stride axis is live (mirrors apps.microbench semantics).
_STRIDE_TYPES = (LsuType.BC_ALIGNED, LsuType.BC_NON_ALIGNED, LsuType.BC_CACHE)


# ---------------------------------------------------------------------------
# Design: one design point, described once
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Design:
    """A frozen description of one design point.

    ``lsus`` are the paper-Table-II load/store units the design instantiates
    (use the constructors below rather than writing them by hand).  ``dram``
    and ``bsp`` are optional per-design overrides of the session hardware;
    ``f`` is the vectorization factor entering Eq. 10.  ``flops`` is
    non-zero only for designs read off a compiled artifact
    (``from_hlo``/``from_kernel``) and feeds the compute term of
    ``Session.roofline``.
    """

    lsus: tuple[Lsu, ...]
    dram: DramParams | None = None
    bsp: BspParams | None = None
    f: int = 1
    name: str = ""
    flops: float = 0.0

    def __post_init__(self):
        object.__setattr__(self, "lsus", tuple(self.lsus))

    # -- constructors -------------------------------------------------------

    @classmethod
    def microbench(cls, lsu_type: LsuType, *, n_ga: int, simd: int = 16,
                   n_elems: int = 1 << 22, delta: int = 1,
                   elem_bytes: int = 4, include_write: bool = True,
                   val_constant: bool = False, name: str = "",
                   dram: DramParams | None = None,
                   bsp: BspParams | None = None) -> "Design":
        """The paper's SIV sum-reduction microbenchmark as a Design.

        The vectorization factor is the SIMD width, exactly as in the paper
        (``#ga`` reads + one write, write-ACK stores replicated ``simd``
        times, atomics one unit per GA).
        """
        lsus = _apps.microbench(
            lsu_type, n_ga=n_ga, simd=simd, n_elems=n_elems,
            delta=delta if lsu_type in _STRIDE_TYPES else 1,
            elem_bytes=elem_bytes, include_write=include_write,
            val_constant=val_constant)
        return cls(lsus=tuple(lsus), dram=dram, bsp=bsp, f=simd,
                   name=name or f"microbench-{lsu_type.value}-ga{n_ga}")

    @classmethod
    def from_app(cls, app: str, n_elems: int, *,
                 dram: DramParams | None = None,
                 bsp: BspParams | None = None) -> "Design":
        """One of the paper's Table IV applications (``repro.core.apps.APPS``)."""
        desc = _apps.APPS[app]
        return cls(lsus=tuple(desc.lsus(n_elems)), dram=dram, bsp=bsp,
                   f=desc.simd, name=app)

    @classmethod
    def from_classes(cls, bytes_by_class: Mapping[str, float], *,
                     access_bytes: int | None = None, flops: float = 0.0,
                     name: str = "") -> "Design":
        """Design from access-class byte totals (the HLO counter's output).

        Uses the same class -> LSU-type mapping the validation harness uses
        (stream -> aligned, strided -> non-aligned, gather/serialized ->
        write-ACK), preserving total traffic at ``access_bytes`` granularity.
        """
        from repro.core import validate as _validate

        lsus = _validate.lsus_from_classes(
            dict(bytes_by_class),
            access_bytes=access_bytes or _validate.ACCESS_BYTES)
        return cls(lsus=tuple(lsus), flops=flops, name=name)

    @classmethod
    def from_hlo(cls, hlo_text: str, *, access_bytes: int | None = None,
                 name: str = "") -> "Design":
        """Design read off compiled HLO text (``compiled.as_text()``).

        The transplant of reading the HLS early report: the trip-count-aware
        HLO counter classifies the executable's memory traffic, and each
        access class becomes one LSU group.
        """
        from repro.core import hlo_counter as _hc

        hc = _hc.analyze(hlo_text)
        return cls.from_classes(dict(hc.bytes_by_class),
                                access_bytes=access_bytes,
                                flops=float(hc.flops), name=name)

    @classmethod
    def from_kernel(cls, fn: Callable, *args, name: str = "",
                    access_bytes: int | None = None) -> "Design":
        """Design from a jax-jittable callable: lower + compile + analyze.

        ``fn`` may be a plain function (it is jitted here) or an already
        jitted/lowered one; ``args`` are example arguments or
        ``jax.ShapeDtypeStruct`` specs.  Requires jax.
        """
        import jax

        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        compiled = jitted.lower(*args).compile()
        return cls.from_hlo(compiled.as_text(), access_bytes=access_bytes,
                            name=name or getattr(fn, "__name__", "kernel"))

    # -- builder-style derivation ------------------------------------------

    def with_dram(self, dram: DramParams) -> "Design":
        return dataclasses.replace(self, dram=dram)

    def with_bsp(self, bsp: BspParams) -> "Design":
        return dataclasses.replace(self, bsp=bsp)

    def with_f(self, f: int) -> "Design":
        return dataclasses.replace(self, f=f)

    def with_name(self, name: str) -> "Design":
        return dataclasses.replace(self, name=name)

    def with_lsus(self, lsus: Iterable[Lsu]) -> "Design":
        """Replace the LSU list wholesale."""
        return dataclasses.replace(self, lsus=tuple(lsus))

    def with_access(self, lsu_type: LsuType, *, n_elems: int,
                    elem_bytes: int = 4, f: int | None = None,
                    delta: int = 1, is_write: bool = False,
                    val_constant: bool = False, name: str = "") -> "Design":
        """Append one source-level global access (expanded to its LSUs)."""
        extra = make_global_access(
            lsu_type, n_elems=n_elems, elem_bytes=elem_bytes,
            f=self.f if f is None else f, delta=delta, is_write=is_write,
            val_constant=val_constant, name=name)
        return dataclasses.replace(self, lsus=self.lsus + tuple(extra))

    # -- introspection ------------------------------------------------------

    @property
    def n_lsu(self) -> int:
        """Number of LSUs that issue DRAM traffic."""
        return sum(1 for l in self.lsus if l.lsu_type.is_global)

    @property
    def total_bytes(self) -> int:
        """Useful bytes the design moves (sum over global LSUs)."""
        return sum(l.total_bytes for l in self.lsus if l.lsu_type.is_global)

    @property
    def resource_bytes(self) -> int:
        """Total LSU interconnect width [B] — the sweep resource objective."""
        return sum(l.ls_width for l in self.lsus if l.lsu_type.is_global)


# ---------------------------------------------------------------------------
# Space: a declarative design space
# ---------------------------------------------------------------------------

#: Default streaming chunk: 64k points keeps the working set ~tens of MB
#: while amortizing per-chunk dispatch, and is one fixed jit shape.
DEFAULT_CHUNK = 1 << 16


@dataclasses.dataclass(frozen=True)
class Space:
    """A design space over the microbenchmark axes (``sweep.AXES``).

    ``Space.grid(**axes)`` is the full Cartesian product; ``Space.random(n,
    seed=..., **axes)`` samples ``n`` points (2-tuples of numbers =
    inclusive integer ranges).  Axes left unset default to the session's
    hardware and the sweep-engine defaults at evaluation time.

    ``Space.grid(...).stream()`` marks the space for bounded-memory
    streaming evaluation: points are enumerated lazily from integer ids and
    folded chunk-by-chunk into online reducers, so million-point grids
    sweep in O(chunk + front + k) memory (see ``Session.sweep``).
    """

    axes: Mapping[str, Any]
    n: int | None = None       # None -> full grid
    seed: int = 0
    chunk_size: int | None = None   # set by stream(); None -> materialize

    @classmethod
    def grid(cls, **axes) -> "Space":
        return cls(axes=dict(axes))

    @classmethod
    def random(cls, n: int, *, seed: int = 0, **axes) -> "Space":
        if n < 1:
            raise ValueError("a random space needs n >= 1 samples")
        return cls(axes=dict(axes), n=int(n), seed=int(seed))

    @property
    def is_grid(self) -> bool:
        return self.n is None

    def stream(self, chunk_size: int = DEFAULT_CHUNK) -> "Space":
        """This grid, marked for chunked streaming evaluation.

        Only grids stream: their points are pure index arithmetic on the
        point id, so no per-point state ever needs materializing.  (A
        random space would need all its draws held to be re-chunkable.)
        """
        if not self.is_grid:
            raise TypeError("streaming sweeps need a grid space; "
                            "Space.random materializes its draws")
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        return dataclasses.replace(self, chunk_size=int(chunk_size))

    def lists(self, *, dram: DramParams, bsp: BspParams) -> dict[str, list]:
        """Normalized per-axis value lists, defaulting the hardware axes."""
        axes = dict(self.axes)
        axes.setdefault("dram", dram)
        axes.setdefault("bsp", bsp)
        return _sweep._normalize_axes(axes)

    def points(self, *, dram: DramParams, bsp: BspParams, constraints=(),
               ) -> tuple[dict[str, np.ndarray], int, dict]:
        """Materialize per-point axis arrays, defaulting hardware axes.

        For a random space, ``constraints`` switches to seeded rejection
        sampling: every returned point is feasible, and an empty (or
        near-empty) feasible region raises instead of spinning or emitting
        infeasible points.  Grid spaces ignore ``constraints`` here — the
        sweep path masks the enumerated grid itself, so it can report the
        feasible/candidate split.
        """
        axes = dict(self.axes)
        axes.setdefault("dram", dram)
        axes.setdefault("bsp", bsp)
        if self.is_grid:
            return _sweep._grid_points(axes)
        return _sweep._random_points(self.n, self.seed, axes,
                                     constraints=tuple(constraints))


# ---------------------------------------------------------------------------
# The shared result family
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Estimate:
    """One design point's model output — the family's scalar member.

    The same fields come out of every backend; ``per_lsu`` carries the
    readable per-LSU breakdown when the scalar backend produced it.
    """

    t_exe: float                  # Eq. 1 [s]
    t_ideal: float                # bandwidth floor [s]
    t_ovh: float                  # row-miss/ACK/atomic overhead [s]
    bound_ratio: float            # LHS of Eq. 3
    memory_bound: bool
    total_bytes: float
    n_lsu: int
    backend: str = "scalar"
    design: "Design | None" = None
    per_lsu: tuple = ()
    cached: bool = False          # True when served from a Server's LRU

    @property
    def effective_bandwidth(self) -> float:
        """Useful bytes / predicted time [B/s]."""
        return self.total_bytes / self.t_exe if self.t_exe > 0 else math.inf

    def row(self) -> dict:
        return {
            "design": self.design.name if self.design else "",
            "t_exe_ms": self.t_exe * 1e3,
            "t_ideal_ms": self.t_ideal * 1e3,
            "t_ovh_ms": self.t_ovh * 1e3,
            "bound_ratio": self.bound_ratio,
            "memory_bound": bool(self.memory_bound),
            "eff_bw_gbs": self.effective_bandwidth / 1e9,
            "total_bytes": self.total_bytes,
            "backend": self.backend,
        }


def _estimate_row(est: "_mb.BatchEstimate", i: int, *, backend: str,
                  scale: float = 1.0,
                  design: "Design | None" = None) -> Estimate:
    """Row ``i`` of a BatchEstimate as an :class:`Estimate` (the one place
    that knows the field-by-field extraction)."""
    return Estimate(
        t_exe=float(np.asarray(est.t_exe)[i]) * scale,
        t_ideal=float(np.asarray(est.t_ideal)[i]) * scale,
        t_ovh=float(np.asarray(est.t_ovh)[i]) * scale,
        bound_ratio=float(np.asarray(est.bound_ratio)[i]),
        memory_bound=bool(np.asarray(est.memory_bound)[i]),
        total_bytes=float(np.asarray(est.total_bytes)[i]),
        n_lsu=int(np.asarray(est.n_lsu)[i]),
        backend=backend, design=design)


class Report:
    """Mixin of the shared report protocol: ``rows`` / ``to_csv`` / ``summary``.

    Every Session method that scores more than one thing returns a Report
    subclass, so downstream tooling (benchmarks, CI artifacts, notebooks)
    consumes one shape regardless of which pipeline stage produced it.
    """

    kind: str = "report"

    def rows(self) -> list[dict]:  # pragma: no cover — abstract
        raise NotImplementedError

    def to_csv(self) -> str:
        rows = self.rows()
        if not rows:
            return ""
        import csv
        import io

        fields = list(rows[0].keys())
        seen = set(fields)
        for r in rows[1:]:         # failure rows may carry extra keys
            fields += [k for k in r if k not in seen]
            seen.update(r)
        buf = io.StringIO()
        w = csv.DictWriter(buf, fieldnames=fields, restval="")
        w.writeheader()
        for r in rows:
            w.writerow(r)
        return buf.getvalue()

    def summary(self) -> dict:
        return {"kind": self.kind, "rows": len(self.rows())}


@dataclasses.dataclass(frozen=True)
class SweepReport(_sweep.SweepResult, Report):
    """Scored design space (a :class:`~repro.core.sweep.SweepResult` that is
    also a :class:`Report`), tagged with the backend that scored it.

    A *streaming* sweep returns the same class backed by reducer state: the
    held arrays (``points``/``estimate``/``resource``) cover only the
    surviving points (Pareto front + top-k), ``point_ids`` maps them back
    to global point ids, ``stats`` carries the exact whole-space summary,
    and ``pareto()`` / ``top_k()`` / ``rows()`` answer from that state —
    ``rows()`` is restricted to survivors by construction.
    """

    backend: str = "numpy-batch"
    # -- streaming state (None on a materialized sweep) --------------------
    n_total: int | None = None        # points swept (held arrays are fewer)
    stats: Mapping[str, Any] | None = None   # StatsReducer.summary()
    point_ids: np.ndarray | None = None      # global id of each held row
    front_idx: np.ndarray | None = None      # held-row indices of the front
    front_objectives: tuple | None = None    # the reducer's objective names
    topk_idx: np.ndarray | None = None       # held-row indices, best first
    topk_key: str | None = None
    reducers: tuple | None = None     # the folded reducer instances —
    # custom Reducer subclasses read their accumulated state back here
    # -- constraint telemetry (None on an unconstrained sweep) -------------
    n_candidates: int | None = None   # points enumerated before feasibility
    # -- per-stage timing (None unless swept with profile=True) ------------
    profile: Mapping[str, Any] | None = None
    kind = "sweep"

    @property
    def is_streaming(self) -> bool:
        return self.n_total is not None

    @property
    def n_points(self) -> int:
        """Points swept (for a streaming report: the whole space, not the
        survivors — ``len(report.resource)`` counts the held rows)."""
        return self.n_total if self.n_total is not None \
            else int(len(self.resource))

    def pareto(self, objectives: Sequence[Any] | None = None) -> np.ndarray:
        if self.is_streaming:
            if self.front_idx is None:
                raise ValueError(
                    "a streaming report holds only the reducer's front; "
                    "re-sweep with reducers=[ParetoReducer(objectives=...)]")
            # A non-default reducer front must be requested explicitly, the
            # same way top_k validates topk_key, so a custom-objective
            # front is never mistaken for the default t_exe/resource one.
            wanted = tuple(objectives) if objectives is not None \
                else ("t_exe", "resource")
            if wanted != self.front_objectives:
                raise ValueError(
                    f"streaming report holds the front over "
                    f"{self.front_objectives}; re-sweep with "
                    f"reducers=[ParetoReducer(objectives={wanted!r})] or "
                    f"call pareto({list(self.front_objectives)!r})")
            return np.asarray(self.front_idx, dtype=np.int64)
        return super().pareto(objectives)

    def top_k(self, k: int = 10, key: str = "t_exe") -> list[dict]:
        if self.is_streaming:
            if self.topk_idx is None or key != self.topk_key:
                raise ValueError(
                    f"streaming report kept top-k by {self.topk_key!r}; "
                    f"re-sweep with reducers=[TopKReducer(k, {key!r})]")
            # A reducer that kept the whole space answers any k, like the
            # materialized path; only a truncated selection caps k.
            if k > len(self.topk_idx) and len(self.topk_idx) < self.n_points:
                raise ValueError(
                    f"streaming report kept only the top {len(self.topk_idx)}"
                    f"; re-sweep with reducers=[TopKReducer(k={k})]")
            return self.rows(self.topk_idx[:k])
        return super().top_k(k, key)

    def estimates(self, indices: Sequence[int] | None = None,
                  ) -> list[Estimate]:
        """Per-point :class:`Estimate` objects (default: all held points)."""
        if indices is None:
            indices = range(len(self.resource))
        return [_estimate_row(self.estimate, int(i), backend=self.backend)
                for i in indices]

    def best(self) -> Estimate:
        """The fastest design point of the space.

        For a streaming report this is cross-checked against the exact
        whole-space minimum the stats reducer tracked: if the survivors the
        configured reducers kept do not include that point (e.g. a custom
        front with no ``t_exe`` objective and no top-k), this raises rather
        than returning a confidently wrong row.  The default reducers
        always keep it.
        """
        if self.n_points == 0:
            if self.n_candidates:
                raise ValueError(
                    f"constraints eliminated every point: 0 of "
                    f"{self.n_candidates} candidates feasible; relax the "
                    f"constraints or widen the space")
            raise ValueError("the swept space is empty (n_points == 0); "
                             "there is no best design point")
        if self.is_streaming and len(self.resource) == 0:
            raise ValueError(
                "streaming report holds no survivor rows (stats-only "
                f"reducers; t_exe_min={self.stats['t_exe_min']!r} at point "
                f"id {self.stats['t_exe_min_id']}); re-sweep with "
                "reducers=[TopKReducer(1), ...] to keep the best row")
        i = int(np.argmin(self.t_exe))
        if self.is_streaming and self.stats is not None \
                and float(np.asarray(self.t_exe)[i]) != self.stats["t_exe_min"]:
            raise ValueError(
                "streaming report's survivors do not include the fastest "
                f"point (held min {float(np.asarray(self.t_exe)[i])!r} vs "
                f"whole-space min {self.stats['t_exe_min']!r} at point id "
                f"{self.stats['t_exe_min_id']}); re-sweep with "
                "reducers=[TopKReducer(1), ...] to keep it")
        return self.estimates([i])[0]

    def summary(self) -> dict:
        if self.is_streaming:
            out = {
                "kind": self.kind, "backend": self.backend,
                "n_points": int(self.stats["n_points"]),
                "memory_bound_points": int(self.stats["memory_bound_points"]),
                "pareto_points": int(len(self.front_idx)
                                     if self.front_idx is not None else 0),
                "t_exe_min_ms": float(self.stats["t_exe_min"]) * 1e3,
            }
        else:
            out = {
                "kind": self.kind, "backend": self.backend,
                "n_points": self.n_points,
                "memory_bound_points": int(
                    np.asarray(self.memory_bound).sum()),
                "pareto_points": int(len(self.pareto())
                                     if self.n_points else 0),
                "t_exe_min_ms": (float(np.min(self.t_exe)) * 1e3
                                 if self.n_points else math.inf),
            }
        if self.n_candidates is not None:
            # the feasible/total split of a constrained sweep
            out["n_candidates"] = int(self.n_candidates)
            out["n_feasible"] = out["n_points"]
        if self.profile is not None:
            out["profile"] = dict(self.profile)
        return out


def _derive_legacy_keys(prof: dict) -> None:
    """The profile keys that predate the spans, as sums of span keys (see
    ``Session.sweep``)."""
    def total(*keys):
        return sum(prof.get(k, 0.0) for k in keys)

    if prof.get("path") in ("host-stream", "device-fused"):
        prof["enumerate_s"] = total("decode_s")
        prof["reduce_s"] = total("fold_s")
        prof["score_s"] = total("pack_s", "dispatch_s")
    if prof.get("path") != "distributed":
        prof["transfer_s"] = total("upload_s", "pull_s")


def _stream_report(reducers, plan: SweepPlan, *,
                   backend: str) -> SweepReport:
    """The SweepReport of ``reducers`` folded over the whole of ``plan``.

    Survivors = union of the Pareto reducer's front and the top-k rows,
    deduplicated by point id and held in ascending id order; the front and
    top-k index into those held rows.  For a constrained sweep the
    reducers only ever saw feasible rows, so the report's ``n_total`` is
    the stats reducer's exact feasible count and ``n_candidates`` the
    enumerated grid size.
    """
    from repro.core import stream as _stream

    tables = plan.tables()
    n_candidates = plan.n if plan.constraints else None

    front = next((r for r in reducers
                  if isinstance(r, _stream.ParetoReducer)), None)
    topk = next((r for r in reducers
                 if isinstance(r, _stream.TopKReducer)), None)
    stats = next(r for r in reducers
                 if isinstance(r, _stream.StatsReducer))

    pieces = [r.cols for r in (front, topk)
              if r is not None and r.cols is not None]
    if pieces:
        merged = {k: np.concatenate([p[k] for p in pieces])
                  for k in pieces[0]}
        ids, first = np.unique(np.asarray(merged["id"], dtype=np.int64),
                               return_index=True)
        merged = {k: np.asarray(v)[first] for k, v in merged.items()}
    else:   # stats-only reducers: nothing held beyond the summary
        ids = np.empty(0, dtype=np.int64)
        merged = {k: np.empty(0) for k in
                  (("id",) + _sweep.AXES + _stream.ESTIMATE_COLUMNS
                   + ("resource",))}

    points: dict[str, np.ndarray] = {}
    for name in _sweep.AXES:
        col = merged[name]
        if name in _sweep._CATEGORICAL:
            points[name] = _sweep._object_array(tables[name])[
                np.asarray(col, dtype=np.int64)] if len(col) \
                else _sweep._object_array([])
        else:
            points[name] = np.asarray(col)
    est = _mb.BatchEstimate(
        t_exe=np.asarray(merged["t_exe"], dtype=np.float64),
        t_ideal=np.asarray(merged["t_ideal"], dtype=np.float64),
        t_ovh=np.asarray(merged["t_ovh"], dtype=np.float64),
        bound_ratio=np.asarray(merged["bound_ratio"], dtype=np.float64),
        memory_bound=np.asarray(merged["memory_bound"], dtype=bool),
        total_bytes=np.asarray(merged["total_bytes"], dtype=np.float64),
        n_lsu=np.asarray(merged["n_lsu"], dtype=np.int64),
        groups={})
    return SweepReport(
        points=points, estimate=est,
        resource=np.asarray(merged["resource"], dtype=np.float64),
        backend=backend,
        n_total=(plan.n if n_candidates is None
                 else int(stats.n_points)),
        n_candidates=n_candidates, stats=stats.summary(),
        point_ids=ids,
        front_idx=(np.searchsorted(ids, front.ids)
                   if front is not None else None),
        front_objectives=front.objectives if front is not None else None,
        topk_idx=(np.searchsorted(ids, topk.ids)
                  if topk is not None else None),
        topk_key=topk.key if topk is not None else None,
        reducers=reducers)


class AutotuneReport(Report):
    """Ranked autotune results as a Report (wraps ``AutotuneResults``)."""

    kind = "autotune"

    def __init__(self, results):
        self.results = list(results)
        self.failures = list(getattr(results, "failures", []))

    def __iter__(self):
        return iter(self.results)

    def __len__(self) -> int:
        return len(self.results)

    def __getitem__(self, i):
        return self.results[i]

    @property
    def best(self):
        return self.results[0] if self.results else None

    def rows(self) -> list[dict]:
        return ([t.summary() for t in self.results]
                + [f.summary() for f in self.failures])

    def summary(self) -> dict:
        return {"kind": self.kind, "candidates": len(self.results),
                "failures": len(self.failures),
                "best": self.best.candidate.name if self.best else None}


class ValidateReport(Report):
    """Measured-vs-predicted validation as a Report.

    Wraps :class:`repro.core.validate.ValidationReport`, exposing its fields
    (``results``, ``failures``, ``dram``, ``measured_bw``,
    ``calibration_factor``) unchanged.
    """

    kind = "validate"

    def __init__(self, report):
        self.raw = report
        self.results = report.results
        self.failures = report.failures
        self.dram = report.dram
        self.measured_bw = report.measured_bw
        self.calibration_factor = report.calibration_factor

    @property
    def max_err_pct(self) -> float:
        return self.raw.max_err_pct

    def rows(self) -> list[dict]:
        return self.raw.rows()

    def summary(self) -> dict:
        return {"kind": self.kind, "kernels": len(self.results),
                "failures": len(self.failures),
                "measured_bw_gbs": self.measured_bw / 1e9,
                "calibration_factor": self.calibration_factor,
                "max_err_pct": self.max_err_pct}


@dataclasses.dataclass(frozen=True)
class RooflineReport(Report):
    """Roofline placement of one design: memory vs compute terms."""

    design: Design
    estimate: Estimate
    t_memory: float               # the Eqs. 1-10 memory time [s]
    t_compute: float              # flops / peak_flops (0 when flops unknown)
    ridge_flops_per_byte: float   # the hw ridge point
    arithmetic_intensity: float   # flops / useful bytes
    peak_bw: float                # hw peak memory bandwidth [B/s]
    kind = "roofline"

    @property
    def t_exe(self) -> float:
        """Roofline time: the slower of the two resources."""
        return max(self.t_memory, self.t_compute)

    @property
    def bottleneck(self) -> str:
        return "memory" if self.t_memory >= self.t_compute else "compute"

    @property
    def memory_bound(self) -> bool:
        return self.bottleneck == "memory"

    def rows(self) -> list[dict]:
        return [{
            "design": self.design.name,
            "t_memory_ms": self.t_memory * 1e3,
            "t_compute_ms": self.t_compute * 1e3,
            "bottleneck": self.bottleneck,
            "arithmetic_intensity": self.arithmetic_intensity,
            "ridge_flops_per_byte": self.ridge_flops_per_byte,
            "eff_bw_gbs": self.estimate.effective_bandwidth / 1e9,
            "peak_bw_gbs": self.peak_bw / 1e9,
            "bound_ratio": self.estimate.bound_ratio,
        }]


# ---------------------------------------------------------------------------
# Session: hardware + calibration + backend
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Session:
    """Evaluation context every pipeline stage runs in.

    * ``hardware`` — an optional :class:`repro.hw.Hardware` spec (usually
      ``repro.hw.get(name)``); when set, the three legacy views below and
      the calibration factor all derive from it (``with_hardware``);
    * ``dram``/``bsp`` — the faithful FPGA-model hardware (paper Table III),
      used unless a :class:`Design` carries its own override; default: the
      registry's ``stratix10_ddr4_1866`` board;
    * ``hw`` — the TPU-transplant parameters (autotune/predict/roofline
      compute term); default: the registry's ``tpu_v5e`` chip;
    * ``backend`` — how estimates are computed: ``scalar`` (readable
      reference loop), ``numpy-batch`` (vectorized array core, default) or
      ``jax-jit`` (the same core under ``jax.jit``, x64);
    * ``calibration_factor`` — a single measured/modeled scale fitted by
      ``validate`` (1.0 = uncalibrated); all estimated times are multiplied
      by it, so a session calibrated on a stream anchor predicts in
      host-measured seconds.
    """

    dram: DramParams | None = None
    bsp: BspParams | None = None
    hw: TpuParams | None = None
    backend: str = "numpy-batch"
    calibration_factor: float | None = None
    hardware: Hardware | None = None

    def __post_init__(self):
        spec = self.hardware
        if self.dram is None:
            object.__setattr__(self, "dram", spec.dram_params() if spec
                               else _hw_get(DEFAULT_BOARD).dram_params())
        if self.bsp is None:
            object.__setattr__(self, "bsp", spec.bsp_params() if spec
                               else _hw_get(DEFAULT_BOARD).bsp_params())
        if self.hw is None:
            object.__setattr__(self, "hw", spec.tpu_params() if spec
                               else _hw_get(DEFAULT_CHIP).tpu_params())
        if self.calibration_factor is None:
            object.__setattr__(self, "calibration_factor",
                               float(spec.host_factor) if spec else 1.0)
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; pick one of {BACKENDS}")
        if not (self.calibration_factor > 0
                and math.isfinite(self.calibration_factor)):
            raise ValueError("calibration_factor must be finite and > 0")

    # -- derivation ---------------------------------------------------------

    def with_backend(self, backend: str) -> "Session":
        return dataclasses.replace(self, backend=backend)

    def with_dram(self, dram: DramParams) -> "Session":
        # diverging from the spec: the hardware field no longer describes
        # this session, so drop it (autotune cache keys, simulator
        # interleave and future derivations must not read a stale spec).
        return dataclasses.replace(self, dram=dram, hardware=None)

    def with_hardware(self, hardware: Hardware) -> "Session":
        """Session re-anchored on one :class:`repro.hw.Hardware` spec.

        Every view the pipeline consumes — the FPGA-model ``dram``/``bsp``,
        the TPU-transplant ``hw``, and the calibration factor — derives from
        the spec, so all three backends score designs against the same
        serializable description: ``Session().with_hardware(hw.get("tpu_v4"))``.
        """
        return dataclasses.replace(
            self, hardware=hardware,
            dram=hardware.dram_params(), bsp=hardware.bsp_params(),
            hw=hardware.tpu_params(),
            calibration_factor=float(hardware.host_factor))

    def with_calibration(self, report: "ValidateReport") -> "Session":
        """Session re-anchored on a validation report's fitted bandwidth and
        host factor — subsequent estimates predict measured seconds.  Use
        ``with_hardware(Hardware.from_calibration(report))`` to make the
        same re-anchoring persistent (``to_json``)."""
        return dataclasses.replace(
            self, dram=report.dram, hardware=None,
            calibration_factor=float(report.calibration_factor))

    def _hw_for(self, design: Design) -> tuple[DramParams, BspParams]:
        return design.dram or self.dram, design.bsp or self.bsp

    # -- estimate -----------------------------------------------------------

    def estimate(self, design: Design) -> Estimate:
        """Eqs. 1-10 for one design, on this session's backend."""
        dram, bsp = self._hw_for(design)
        if self.backend == "scalar":
            ke = _model._estimate(list(design.lsus), dram, bsp, f=design.f)
            c = self.calibration_factor
            return Estimate(
                t_exe=ke.t_exe * c, t_ideal=ke.t_ideal * c,
                t_ovh=ke.t_ovh * c, bound_ratio=ke.bound_ratio,
                memory_bound=ke.memory_bound,
                total_bytes=float(ke.total_bytes), n_lsu=len(ke.per_lsu),
                backend=self.backend, design=design, per_lsu=ke.per_lsu)
        return self.estimate_many([design])[0]

    def estimate_many(self, designs: Sequence[Design]) -> list[Estimate]:
        """Score many heterogeneous designs in one batched pass."""
        if not designs:
            return []
        if self.backend == "scalar":
            return [self.estimate(d) for d in designs]
        est = _mb.batch_estimator(self.backend)(self._batch_for(designs))
        return self._rows_from(est, designs)

    def _batch_for(self, designs: Sequence[Design]) -> _mb.GroupBatch:
        """One GroupBatch over heterogeneous designs (session hw defaults
        applied) — shared by ``estimate_many`` and the serving batcher."""
        hw = [self._hw_for(d) for d in designs]
        return _mb.GroupBatch.from_kernels(
            [list(d.lsus) for d in designs],
            [h[0] for h in hw], [h[1] for h in hw],
            f=[d.f for d in designs])

    def _rows_from(self, est: _mb.BatchEstimate,
                   designs: Sequence[Design]) -> list[Estimate]:
        """Batch rows back out as calibrated per-design Estimates."""
        return [_estimate_row(est, i, backend=self.backend,
                              scale=self.calibration_factor,
                              design=designs[i])
                for i in range(len(designs))]

    # -- sweep --------------------------------------------------------------

    @staticmethod
    def _as_space(space: "Space | Mapping[str, Any] | None",
                  axes: Mapping[str, Any]) -> "Space":
        """Normalize the (space | mapping | keyword axes) calling forms."""
        if space is None:
            return Space.grid(**axes)
        if axes:
            raise TypeError("pass either a Space/mapping or keyword axes, "
                            "not both")
        if isinstance(space, Mapping):
            return Space.grid(**space)
        return space

    def plan(self, space: "Space | Mapping[str, Any] | None" = None, *,
             chunk_size: int | None = None, constraints=(),
             **axes) -> SweepPlan:
        """A frozen, picklable :class:`SweepPlan` for streaming this space.

        The plan is the data-only description of what ``sweep`` would
        stream — normalized axis lists (session hardware defaulted in),
        backend, calibration factor, chunk size and feasibility
        ``constraints`` — and rebuilds its chunk evaluator in any process
        (``plan.evaluator()``), which is how the ``executor="processes"``
        coordinator ships work to spawn-based workers.  ``plan.to_json()``
        round-trips it through text (custom callable constraints pickle
        but do not JSON-encode).  Only grid spaces plan: a random space
        materializes its draws.
        """
        space = self._as_space(space, axes)
        if not space.is_grid:
            raise TypeError("streaming sweeps need a grid space; "
                            "Space.random materializes its draws")
        chunk = chunk_size if chunk_size is not None else space.chunk_size
        chunk = int(chunk) if chunk is not None else DEFAULT_CHUNK
        return SweepPlan(
            lists=space.lists(dram=self.dram, bsp=self.bsp),
            backend=self.backend,
            calibration_factor=self.calibration_factor,
            chunk_size=chunk,
            constraints=constraints or ())

    def sweep(self, space: "Space | Mapping[str, Any] | None" = None, *,
              chunk_size: int | None = None, reducers=None,
              workers: int | None = None, executor: str = "threads",
              constraints=(), profile: bool = False,
              **axes) -> SweepReport:
        """Score a whole design space through this session's backend.

        Accepts a :class:`Space`, a plain axes mapping (treated as a grid),
        or keyword axes directly: ``sess.sweep(n_ga=[1, 2], simd=[4, 16])``.

        Passing ``chunk_size`` (or a ``Space.grid(...).stream()`` space, or
        explicit ``reducers``) switches to **bounded-memory streaming**:
        points are enumerated lazily, evaluated in fixed-shape chunks (the
        jax-jit backend's fused step compiles once per chunk shape and,
        with several local chips, splits the grid's ids over them), and folded
        into online reducers — by default a running Pareto front, a
        ``top_k(10)`` selection and exact summary stats — so a 10M-point
        grid sweeps in O(chunk + front + k) memory.  ``reducers`` takes
        :mod:`repro.core.stream` reducer instances to change what is kept.

        ``executor`` picks how streaming chunks are driven:

        * ``"threads"`` (default) — the in-process pipeline; ``workers``
          sizes the chunk thread pool on the numpy-batch backend (the
          jax-jit backend's fused step already uses every local chip, and the
          scalar reference loop is GIL-bound — both reject ``workers > 1``
          here);
        * ``"processes"`` — the coordinator/worker process pool
          (:mod:`repro.core.distributed`): the grid is partitioned into
          chunk-aligned id ranges, ``workers`` spawn-based processes each
          rebuild the evaluator from the picklable :class:`SweepPlan`,
          stragglers are re-issued, and the merged report is bit-equal to
          the single-process run on every backend.

        ``constraints`` (a :class:`repro.search.Constraint`, a
        :class:`repro.search.ResourceEnvelope`, a ``callable(cols) ->
        bool mask``, or a sequence of those) restricts the sweep to the
        feasible region: grid points are feasibility-masked *before*
        scoring (on the streaming path, chunk by chunk — infeasible
        points are never evaluated), random spaces rejection-sample, and
        the report's ``summary()`` carries the feasible/candidate split.
        Results are bit-equal to post-filtering the unconstrained sweep.

        Every sweep runs inside program spans (:mod:`repro.core.spans`):
        ``repro.sweep`` (its trace args: a per-process sweep ``id``,
        ``path``, ``points``, ``chunk`` and the counters below) and, as
        its children, ``repro.sweep.plan`` then, on the fused device path,
        ``repro.sweep.open`` / ``.compile`` (the first step call, inside
        ``.dispatch``) / ``.dispatch`` / ``.wait`` / ``.close``; on the
        host stream ``repro.chunk.mask`` / ``.decode`` / ``.pack`` /
        ``.upload`` / ``.dispatch`` / ``.pull`` / ``.fold`` per chunk,
        then ``repro.sweep.close``; materialized, ``repro.sweep.enumerate``
        / ``.mask`` / ``.score``.  A ``jax.profiler`` trace holds them on
        the device's clock.

        ``profile=True`` returns their sums on ``report.profile`` (and in
        ``report.summary()["profile"]``): ``<span>_s`` host seconds per
        span name (``plan_s``, ``open_s``, ``pull_s``, ...), the counters
        ``chunks``, ``lanes`` (points scored, padding included),
        ``feasible`` (points kept by the constraints), ``uploads`` /
        ``upload_bytes`` / ``pulls`` / ``pull_bytes`` (arrays moved each
        way), ``device_calls`` (jitted calls) and, fused, ``lookups`` /
        ``lookup_gathers`` (the step's table reads by one-hot lookup and by
        gather, times the steps run), and ``path``, ``devices`` and
        ``host_reason``.
        The older keys are sums of spans: ``enumerate_s`` = ``decode_s``
        (materialized: the enumeration), ``reduce_s`` = ``fold_s``,
        ``transfer_s`` = ``upload_s + pull_s``, ``score_s`` = ``pack_s +
        dispatch_s`` on the host stream and ``dispatch_s`` fused,
        ``compile_s`` the first fused step's call, ``total_s`` the whole
        sweep.  Profiling adds no synchronization: a span is host time as
        the host experiences it (a pull includes the device's wait), and
        device time is the trace's.  A profiled sweep runs the same
        program as an unprofiled one.
        """
        space = self._as_space(space, axes)
        if constraints:
            from repro.search.constraints import normalize_constraints

            constraints = normalize_constraints(constraints)
        else:
            constraints = ()
        if executor not in EXECUTORS:
            raise ValueError(
                f"unknown executor {executor!r}: pick 'threads' (in-process "
                f"chunk pipeline) or 'processes' (coordinator/worker "
                f"process pool)")
        if workers is not None and workers < 1:
            raise ValueError("workers must be >= 1")
        if executor == "threads" and workers is not None and workers > 1:
            if self.backend == "jax-jit":
                raise ValueError(
                    "workers > 1 under executor='threads' does not apply to "
                    "the jax-jit backend (its fused step already uses every "
                    "local chip); use executor='processes' to fan out "
                    "across process workers")
            if self.backend == "scalar":
                raise ValueError(
                    "workers > 1 under executor='threads' cannot speed up "
                    "the scalar backend (the reference loop is GIL-bound); "
                    "use executor='processes' to fan out across process "
                    "workers")
        if executor == "processes" and self.backend == "jax-jit":
            import jax

            if jax.default_backend() == "tpu":
                raise ValueError(
                    "executor='processes' with the jax-jit backend would "
                    "start worker processes that each open this host's "
                    "TPU, and a TPU chip serves one process at a time; use "
                    "executor='threads' (one process drives every local "
                    "chip) or a host backend for the process pool")
        chunk = chunk_size if chunk_size is not None else space.chunk_size
        if chunk is None and (reducers is not None or workers is not None
                              or executor == "processes"):
            chunk = DEFAULT_CHUNK      # these options all imply streaming
        if chunk is not None and not space.is_grid:
            raise TypeError("streaming sweeps need a grid space; "
                            "Space.random materializes its draws")
        prof: dict = {}
        with span("sweep", prof, id=next(_SWEEP_IDS)) as sp:
            if chunk is not None:
                report = self._sweep_stream(space, int(chunk), reducers,
                                            workers, executor, constraints,
                                            prof)
            else:
                report = self._sweep_materialized(space, constraints, prof)
            _derive_legacy_keys(prof)
            sp.annotate(points=(report.n_points if report.n_candidates is None
                                else report.n_candidates),
                        **{k: v for k, v in prof.items()
                           if not k.endswith("_s")})
        prof["total_s"] = prof.pop("sweep_s")
        return dataclasses.replace(report, profile=prof if profile else None)

    def _sweep_materialized(self, space: "Space", constraints: tuple,
                            prof: dict) -> SweepReport:
        prof["path"] = "materialized"
        with span("sweep.enumerate", prof):
            points, n, cats = space.points(dram=self.dram, bsp=self.bsp,
                                           constraints=constraints)
        n_candidates = None
        if constraints and space.is_grid:
            # Mask the enumerated grid before anything is scored; scoring
            # is per-point independent, so this is bit-equal to scoring
            # everything and filtering after.
            from repro.search.constraints import (
                columns_from_parts,
                feasibility_mask,
            )

            with span("sweep.mask", prof):
                mask = feasibility_mask(
                    constraints, columns_from_parts(points, cats, n))
                n_candidates = n
                points = {k: np.asarray(v)[mask] for k, v in points.items()}
                cats = {k: (t, np.asarray(idx)[mask])
                        for k, (t, idx) in cats.items()}
                n = int(np.count_nonzero(mask))
            if n == 0:
                return self._empty_report(cats, n_candidates)
        count(prof, "chunks")
        count(prof, "lanes", n)
        count(prof, "feasible", n)
        with span("sweep.score", prof):
            if self.backend == "scalar":
                result = self._sweep_scalar(points, n, cats)
            else:
                result = _sweep._build(
                    points, n, cats,
                    estimator=_mb.batch_estimator(self.backend, prof),
                    prof=prof)
        est = result.estimate
        if self.calibration_factor != 1.0:
            # The session factor belongs to the *session's* hardware; points
            # fully overridden by a hardware-axis spec already carry that
            # spec's own persisted host_factor and must not be scaled twice.
            hw_col = result.points.get("hardware")
            own = (np.ones(result.n_points, dtype=bool) if hw_col is None
                   else np.asarray([h is None for h in hw_col]))
            c = np.where(own, self.calibration_factor, 1.0)
            est = dataclasses.replace(
                est, t_exe=np.asarray(est.t_exe) * c,
                t_ideal=np.asarray(est.t_ideal) * c,
                t_ovh=np.asarray(est.t_ovh) * c)
        return SweepReport(points=result.points, estimate=est,
                           resource=result.resource, backend=self.backend,
                           n_candidates=n_candidates)

    def _empty_report(self, cats: dict,
                      n_candidates: int | None) -> SweepReport:
        """A zero-row materialized report (constraints ate every point)."""
        points = {name: (_sweep._object_array([])
                         if name in _sweep._CATEGORICAL else np.empty(0))
                  for name in _sweep.AXES}
        est = _mb.BatchEstimate(
            t_exe=np.empty(0), t_ideal=np.empty(0), t_ovh=np.empty(0),
            bound_ratio=np.empty(0),
            memory_bound=np.empty(0, dtype=bool),
            total_bytes=np.empty(0), n_lsu=np.empty(0, dtype=np.int64),
            groups={})
        return SweepReport(points=points, estimate=est,
                           resource=np.empty(0), backend=self.backend,
                           n_candidates=n_candidates)

    def _sweep_scalar(self, points: dict, n: int, cats: dict,
                      ) -> _sweep.SweepResult:
        """Reference scalar loop (moved to ``sweep._score_scalar`` so the
        picklable :class:`SweepPlan` can rebuild it without a session)."""
        return _sweep._score_scalar(points, n, cats)

    # -- streaming sweep ----------------------------------------------------

    def _sweep_stream(self, space: "Space", chunk_size: int, reducers,
                      workers: int | None, executor: str,
                      constraints: tuple, prof: dict) -> SweepReport:
        """Chunked, reducer-folded evaluation of a grid space.

        A thin consumer of :class:`SweepPlan`: it plans, folds ``[0, n)``
        into the reducers — in this process through
        :func:`repro.core.device_stream.make_range_folder`, which picks the
        fused device step or the host stream and records the path taken, or
        across the coordinator/worker pool (``processes``) — and builds the
        report.  Peak memory is O(chunk + front + k); survivor rows (front +
        top-k) are the only points materialized.
        """
        import copy

        from repro.core import stream as _stream

        with span("sweep.plan", prof):
            plan = self.plan(space, chunk_size=chunk_size,
                             constraints=constraints)
            prof["chunk"] = plan.chunk_size
            if reducers is None:
                reducers = _stream.default_reducers()
            else:
                # Reducers accumulate state in place; folding a second
                # sweep into instances that already hold the first one's
                # points would silently mix the spaces, so each sweep
                # folds into copies.
                reducers = tuple(copy.deepcopy(r) for r in reducers)
            if not any(isinstance(r, _stream.StatsReducer)
                       for r in reducers):
                reducers += (_stream.StatsReducer(),)
            if executor != "processes":
                from repro.core import device_stream as _dev

                if workers is None and self.backend == "numpy-batch":
                    import os

                    workers = min(4, os.cpu_count() or 1)
                fold = _dev.make_range_folder(plan, profile=prof,
                                              workers=workers)

        if executor == "processes":
            from repro.core import distributed as _dist

            # per-stage spans live in the worker processes
            prof["path"] = "distributed"
            _dist.run_distributed(plan, reducers, workers=workers)
        else:
            fold(0, plan.n, reducers)
        with span("sweep.close", prof):
            return _stream_report(reducers, plan, backend=self.backend)

    # -- optimizer-driven search -------------------------------------------

    def optimize(self, space: "Space | Mapping[str, Any] | None" = None, *,
                 objective="t_exe", constraints=(), seed: int = 0,
                 max_evals: int | None = None, n_starts: int = 2,
                 steps: int = 16, screen: int | None = None,
                 chunk_size: int | None = None, **axes):
        """Search a grid space for the best design *without* enumerating it.

        ``objective`` is an estimate/resource column to minimize (default
        ``"t_exe"``), or a pair of columns — e.g. ``("t_exe",
        "resource")`` — to approximate the 2-objective Pareto front.
        ``constraints`` restricts the search to the feasible region
        (same forms as ``sweep``); ``max_evals`` bounds how many grid
        points may be scored (default ``max(1024, n // 128)`` — under 1%
        of any large grid).

        The strategy leans on the model being differentiable end to end:
        a seeded feasible screen picks starting points; the integer axes
        are relaxed to continuous and multi-start AdamW descends through
        the jax-differentiable estimator (one lane per categorical
        combination, envelope caps as smooth penalties); each continuous
        optimum is then refined on its *discrete* neighborhood — and, in
        Pareto mode, a Pareto local search walks ±1-step neighbors of the
        running front — all through the same streaming evaluator a full
        sweep would use, so every reported number is bit-comparable to
        the exhaustive grid.

        Returns an :class:`repro.search.OptimizeReport` carrying the best
        point, the evaluated front, per-phase trajectory and the
        evals-used telemetry backing the <1%-of-points claim.
        """
        from repro.search.optimize import run_optimize

        space = self._as_space(space, axes)
        return run_optimize(
            self, space, objective=objective, constraints=constraints,
            seed=seed, max_evals=max_evals, n_starts=n_starts,
            steps=steps, screen=screen, chunk_size=chunk_size)

    # -- the rest of the pipeline ------------------------------------------

    def autotune(self, cfg, shape, mesh, candidates=None, *,
                 cache=True, gather_row_bytes: float = 512.0,
                 ) -> AutotuneReport:
        """Model-guided candidate ranking (lower+compile on CPU, no TPU).

        The session's hardware spec is part of every on-disk cache key, so
        rankings produced under one memory system are never silently reused
        under another.
        """
        from repro.core import autotune as _at

        return AutotuneReport(_at._autotune(
            cfg, shape, mesh, candidates, self.hardware or self.hw,
            cache=cache, gather_row_bytes=gather_row_bytes))

    def validate(self, cases=None, *, iters: int = 3, warmup: int = 1,
                 calibrate: bool = True) -> ValidateReport:
        """Measured-vs-predicted loop over the Pallas kernels.

        With ``calibrate=True`` (default) the stream anchor fits the
        effective bandwidth and a host factor, the paper's methodology.
        With ``calibrate=False`` predictions come from this session's own
        ``dram`` parameters alone — no measured wall-clock enters the
        prediction side, so repeated runs predict identically.

        On a TPU backend a session without its own ``hardware`` predicts
        with the preset of the chip it runs on
        (:func:`repro.hw.preset_for_device_kind`; an unknown chip raises).
        """
        import jax

        from repro.core import validate as _validate

        dram = self.dram
        if self.hardware is None and jax.default_backend() == "tpu":
            kind = jax.devices()[0].device_kind
            dram = _hw_get(_hw_preset_for(kind)).dram_params()
        rep = _validate._validate(
            cases, iters=iters, warmup=warmup,
            dram=None if calibrate else dram, base=dram,
            fit_host_factor=calibrate)
        return ValidateReport(rep)

    def roofline(self, design: Design) -> RooflineReport:
        """Place one design on the roofline: Eqs. 1-10 memory time vs the
        compute floor (``flops / hw.peak_flops``; 0 when flops unknown)."""
        est = self.estimate(design)
        t_compute = design.flops / self.hw.peak_flops
        ai = (design.flops / est.total_bytes if est.total_bytes
              else math.inf if design.flops else 0.0)
        dram, _ = self._hw_for(design)
        return RooflineReport(
            design=design, estimate=est,
            t_memory=est.t_exe, t_compute=t_compute,
            ridge_flops_per_byte=self.hw.ridge_flops_per_byte,
            arithmetic_intensity=ai, peak_bw=dram.bw_mem)

    def predict(self, hlo_text: str, cost: dict | None = None, *,
                gather_row_bytes: float = 512.0):
        """TPU-transplant step prediction from compiled HLO text
        (:func:`repro.core.predictor.predict_step` under this session's hw)."""
        from repro.core import predictor as _pred

        return _pred.predict_step(hlo_text, cost, self.hw,
                                  gather_row_bytes=gather_row_bytes)

    # -- whole-model estimation (repro.workload) ----------------------------

    def _model_hlo_texts(self, model, args, *, phases, batch,
                         seq_len) -> tuple[str, dict[str, str]]:
        """(model name, phase -> compiled HLO text) for every input form
        ``estimate_model``/``plan_model`` accept: HLO text, a mapping of
        phase name -> HLO text, a model-zoo config (lowered via
        ``workload.steps``), or a jittable callable + example args."""
        if isinstance(model, str):
            return "hlo", {"step": model}
        if isinstance(model, Mapping):
            return "hlo", {str(k): str(v) for k, v in model.items()}
        from repro import compat as _compat

        _compat.enable_compilation_cache()      # the lowerings below compile
        if hasattr(model, "block_pattern"):     # models.config.ModelConfig
            from repro.workload import steps as _steps

            return model.name, {
                p: _steps.phase_hlo(model, p, batch=batch, seq_len=seq_len)
                for p in phases}
        if callable(model):
            import jax

            jitted = model if hasattr(model, "lower") else jax.jit(model)
            text = jitted.lower(*args).compile().as_text()
            return getattr(model, "__name__", "model"), {"step": text}
        raise TypeError(
            f"estimate_model wants HLO text, a mapping of phase -> HLO "
            f"text, a ModelConfig, or a jittable callable; got "
            f"{type(model).__name__}")

    def estimate_model(self, model, *args, phases=("train", "decode"),
                       batch: int = 1, seq_len: int = 128, name: str = "",
                       access_bytes: int | None = None,
                       fused: bool = True) -> "_workload.ModelReport":
        """End-to-end estimate of a whole compiled model step.

        Walks every materialized op of each phase's module
        (:func:`repro.workload.walk_module`), maps each op's access-class
        traffic onto LSU groups, scores all ops in **one** batched Eqs.
        1-10 pass on this session's backend, and composes a
        :class:`~repro.workload.ModelReport` — per-phase totals (defined
        as the sum of the per-op estimates), per-layer and per-op-class
        breakdowns, and the aggregate roofline position.

        ``model`` may be compiled HLO text, a ``{phase: hlo_text}``
        mapping, a model-zoo :class:`~repro.models.config.ModelConfig`
        (its ``phases`` are lowered here at ``batch`` x ``seq_len``; needs
        jax), or a jittable callable with example ``*args``.
        """
        from repro import workload as _wl

        mname, texts = self._model_hlo_texts(
            model, args, phases=phases, batch=batch, seq_len=seq_len)
        records = {p: _wl.walk_module(t, fused=fused)
                   for p, t in texts.items()}
        return _wl.compose_model(self, name or mname, records,
                                 access_bytes=access_bytes)

    def plan_model(self, model, *, phases=("decode",), batch=(1,),
                   seq_len=(128,), shards=(1,), hardware=(None,),
                   chunk_size: int = 256, access_bytes: int | None = None,
                   fused: bool = True,
                   name: str = "") -> "_workload.ModelSweepPlan":
        """A frozen, picklable whole-model sweep plan.

        Every distinct ``(phase, batch, seq_len)`` combination is lowered
        and walked **once here** (the only step that needs jax or the
        model code); the returned :class:`~repro.workload.ModelSweepPlan`
        is pure data — JSON/pickle it to any process and stream it there.
        ``hardware`` axis values may be specs, preset names, or ``None``
        (= this session's hardware).
        """
        from repro import workload as _wl
        from repro.core import validate as _validate

        phases = tuple(phases)
        batch = tuple(int(b) for b in batch)
        seq_len = tuple(int(s) for s in seq_len)
        tables: dict[str, tuple] = {}
        mname = name
        for b in batch:
            for s in seq_len:
                pname, texts = self._model_hlo_texts(
                    model, (), phases=phases, batch=b, seq_len=s)
                mname = mname or pname
                for p in phases:
                    if p not in texts:
                        raise ValueError(
                            f"phase {p!r} not in walked phases "
                            f"{list(texts)}")
                    recs = _wl.walk_module(texts[p], fused=fused)
                    tables[f"{p}|{b}|{s}"] = tuple(
                        {"classes": dict(r.bytes_by_class),
                         "flops": r.flops}
                        for r in recs if r.total_bytes > 0)
        pbytes = 0.0
        if hasattr(model, "block_pattern"):
            from repro.workload import steps as _steps

            pbytes = _steps.param_bytes(model)
        return _wl.ModelSweepPlan(
            model=mname or "model",
            lists={"phase": phases, "batch": batch, "seq_len": seq_len,
                   "shards": tuple(shards), "hardware": tuple(hardware)},
            tables=tables, param_bytes=pbytes,
            dram=self.dram, bsp=self.bsp, backend=self.backend,
            calibration_factor=float(self.calibration_factor),
            chunk_size=chunk_size,
            access_bytes=access_bytes or _validate.ACCESS_BYTES)

    def sweep_model(self, model=None, *, plan=None, phases=("decode",),
                    batch=(1,), seq_len=(128,), shards=(1,),
                    hardware=(None,), chunk_size: int | None = None,
                    reducers=None, k: int = 10,
                    access_bytes: int | None = None, fused: bool = True,
                    ) -> "_workload.ModelSweepReport":
        """Sweep model shape x sharding x hardware through the streaming
        engine.

        With ``chunk_size=None`` (default — model grids are small) the
        whole grid is evaluated in one materialized pass and the report
        holds every point; with a ``chunk_size`` the grid streams through
        ``run_stream`` into Pareto/top-k/stats reducers and the report
        holds the survivors — per-point values are bit-equal either way
        (tested).  Pass a prebuilt ``plan`` to skip lowering.
        """
        from repro import workload as _wl
        from repro.core import stream as _stream

        if plan is None:
            if model is None:
                raise ValueError("sweep_model needs a model or a plan")
            plan = self.plan_model(
                model, phases=phases, batch=batch, seq_len=seq_len,
                shards=shards, hardware=hardware,
                chunk_size=chunk_size or 256, access_bytes=access_bytes,
                fused=fused)
        elif chunk_size is not None:
            plan = dataclasses.replace(plan, chunk_size=chunk_size)

        if chunk_size is None:
            cols = plan.materialize()
            stats = _stream.StatsReducer()
            if len(cols["id"]):
                stats.update(cols)
            return _wl.ModelSweepReport(
                plan, cols, n_total=plan.n, stats=stats.summary(),
                streaming=False)

        reducers = tuple(reducers) if reducers is not None \
            else _stream.default_reducers(k)
        outcome = plan.run(reducers)
        front = next((r for r in outcome.reducers
                      if isinstance(r, _stream.ParetoReducer)), None)
        topk = next((r for r in outcome.reducers
                     if isinstance(r, _stream.TopKReducer)), None)
        stats = next((r for r in outcome.reducers
                      if isinstance(r, _stream.StatsReducer)), None)
        pieces = [r.cols for r in (front, topk)
                  if r is not None and r.cols is not None]
        if pieces:
            merged = {kk: np.concatenate([p[kk] for p in pieces])
                      for kk in pieces[0]}
            _, first = np.unique(
                np.asarray(merged["id"], dtype=np.int64),
                return_index=True)
            merged = {kk: np.asarray(v)[first] for kk, v in merged.items()}
        else:
            merged = {kk: np.empty(0) for kk in _wl.sweep.MODEL_COLUMNS}
        return _wl.ModelSweepReport(
            plan, merged, n_total=outcome.n_points,
            stats=stats.summary() if stats is not None else None,
            streaming=True, reducers=outcome.reducers)

    # -- serving ------------------------------------------------------------

    def serve(self, *, max_batch: int = 64, max_wait_ms: float = 1.0,
              cache_size: int = 4096, max_queue: int = 1024,
              timeout_ms: float | None = None) -> "Server":
        """This session as a long-lived concurrent query service.

        Returns a :class:`Server` whose ``estimate``/``submit``/``predict``
        calls are safe from any number of threads: a background batcher
        collects up to ``max_batch`` concurrent requests (lingering at most
        ``max_wait_ms`` for a partial batch), scores them in one batched
        pass — padded to fixed shapes on the jax-jit backend so the core
        compiles once per shape — and scatters results back to per-request
        futures, bit-equal to serial ``estimate`` calls.  A content-hash
        LRU of ``cache_size`` results sits in front (hits return
        immediately with ``Estimate.cached`` set); ``max_queue`` bounds the
        backlog (beyond it submissions fast-fail with
        :class:`ServerOverloaded`); ``timeout_ms`` is the default
        per-request deadline.  Close with ``server.close()`` or use it as a
        context manager; see ``server.stats()`` for hit/miss/latency
        telemetry and ``benchmarks/serve_bench.py`` for the p50/p99 bench.
        """
        from repro.core.serving import Server

        return Server(self, max_batch=max_batch, max_wait_ms=max_wait_ms,
                      cache_size=cache_size, max_queue=max_queue,
                      timeout_ms=timeout_ms)


#: The jax-jit estimator and its jit cache live in
#: :mod:`repro.core.model_batch` (``_JAX_FN`` there).  ``bench/control.py``
#: and the program reset of ``bench/tests`` still set this name; jax.jit
#: retraces on its own when the x64 scope changes.
_JAX_FN = None


# ---------------------------------------------------------------------------
# serving layer (implementation in repro.core.serving; surface is
# Session.serve — imported last because serving's type hints point back here)
# ---------------------------------------------------------------------------

from repro.core.serving import (  # noqa: E402
    RequestTimeout,
    Server,
    ServerClosed,
    ServerOverloaded,
)
