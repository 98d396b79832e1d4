"""Array-based core of the paper's analytical model (Eqs. 1-10).

This is the same math as :mod:`repro.core.model`, restated over arrays so a
whole design space can be scored in one vectorized pass.  The layout is a
structure-of-arrays over *LSU groups*:

* a **group** is ``count`` identical LSUs belonging to one kernel (one design
  point) — e.g. the paper's ``z[id] = x1[id] + ... + xn[id]`` microbenchmark
  with ``#ga = 4`` is a single group with ``count = 5`` (4 reads + 1 write);
* every per-group field (``lsu_type`` code, ``ls_width``, ``ls_acc``,
  ``ls_bytes``, ``delta``, …) and every per-kernel hardware field (DRAM
  timings, BSP parameters, vectorization factor ``f``) is an array
  broadcastable to a common shape ``[M]``;
* ``kernel`` maps each group to its kernel id in ``[0, n_kernels)``; Eq. 1's
  sum over LSUs becomes a segment-sum weighted by ``count``.

All arithmetic mirrors the scalar reference (`model.lsu_timing`) operation
for operation, so batched and scalar results agree to float64 round-off.
The math uses only ops that exist in both NumPy and ``jax.numpy``; pass
``xp=jax.numpy`` (and jax arrays) to run the core under ``jit``/``vmap``.
:func:`batch_estimator` is the one place a backend name picks its batch
scorer: this NumPy core, or the same core under ``jax.jit`` (jax is
imported inside functions, so importing this module stays numpy-only).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import numpy as np

from repro.core.fpga import BspParams, DramParams
from repro.core.lsu import Lsu, LsuType
from repro.core import spans as _spans

# Integer codes for the GMI LSU types (the only ones that touch DRAM).
ALIGNED, NON_ALIGNED, CACHE, WRITE_ACK, ATOMIC = 0, 1, 2, 3, 4

TYPE_CODE = {
    LsuType.BC_ALIGNED: ALIGNED,
    LsuType.BC_NON_ALIGNED: NON_ALIGNED,
    LsuType.BC_CACHE: CACHE,
    LsuType.BC_WRITE_ACK: WRITE_ACK,
    LsuType.ATOMIC_PIPELINED: ATOMIC,
    # The high-end BSP compiles prefetching LSUs as burst-coalesced aligned
    # (lsu.py Table I note), so they share the aligned timing.
    LsuType.PREFETCHING: ALIGNED,
}
CODE_TYPE = {ALIGNED: LsuType.BC_ALIGNED, NON_ALIGNED: LsuType.BC_NON_ALIGNED,
             CACHE: LsuType.BC_CACHE, WRITE_ACK: LsuType.BC_WRITE_ACK,
             ATOMIC: LsuType.ATOMIC_PIPELINED}


class _ScalarNamespace:
    """Array-namespace shim over plain Python scalars.

    Lets the scalar `model.estimate` wrapper run `group_timing` per LSU with
    no array-construction overhead (a length-1 ndarray pipeline costs ~100x
    a float op) while keeping a single source of truth for the math.
    """

    @staticmethod
    def asarray(x):
        return x

    @staticmethod
    def where(cond, a, b):
        return a if cond else b

    @staticmethod
    def maximum(a, b):
        return a if a >= b else b


SCALAR_XP = _ScalarNamespace()


def _segment_sum(data, segment_ids, num_segments: int, xp=np):
    if xp is np:
        return np.bincount(segment_ids, weights=np.asarray(data, dtype=np.float64),
                           minlength=num_segments)
    import jax
    return jax.ops.segment_sum(data, segment_ids, num_segments)


def group_timing(
    *,
    lsu_type,
    ls_width,
    ls_acc,
    ls_bytes,
    delta,
    val_constant,
    n_lsu,
    f,
    dq,
    bl,
    f_mem,
    t_rcd,
    t_rp,
    t_wr,
    burst_cnt,
    max_th,
    xp=np,
) -> dict[str, Any]:
    """Eqs. 2 and 4-10 for a batch of LSU groups.

    All arguments are arrays (or scalars) broadcastable to a common shape.
    Returns per-single-LSU terms: multiply ``t_total`` by the group ``count``
    to get the group's Eq. 1 contribution.
    """
    lsu_type = xp.asarray(lsu_type)
    is_atomic = lsu_type == ATOMIC
    is_ack = lsu_type == WRITE_ACK
    is_nonaligned = lsu_type == NON_ALIGNED
    coalescing = (lsu_type == ALIGNED) | is_nonaligned | (lsu_type == CACHE)

    bw_mem = dq * 2.0 * f_mem                       # Eq. 2 denominator
    min_burst = dq * bl                              # dq * bl [B]
    max_txn = (2 ** xp.asarray(burst_cnt)) * min_burst  # Eq. 5 upper bound

    total_bytes = ls_acc * ls_bytes
    t_ideal = total_bytes / bw_mem                   # Eq. 2

    # Effective transaction size (Eq. 5 / Eqs. 7-8 / min-burst for atomics).
    max_reqs = max_th * ls_width / (delta + 1)       # Eq. 7
    bsz_nonaligned = xp.where(max_reqs <= max_txn,   # Eq. 8 knee
                              max_reqs / delta, ls_width / delta)
    bsz = xp.where(is_nonaligned, bsz_nonaligned, 1.0 * max_txn)
    bsz = xp.where(is_atomic, 1.0 * min_burst, bsz)

    n_bursts_bc = total_bytes / bsz
    t_row_bc = t_rcd + t_rp                          # Eq. 6
    t_row = xp.where(is_ack, t_row_bc + t_wr, t_row_bc)          # Eq. 9
    t_row = xp.where(is_atomic, 2.0 * t_row_bc + t_wr, t_row)    # Eq. 10

    # Atomic-pipelined (Eq. 10): per-operation overhead, merged across the
    # vectorization factor when the summed value is loop-constant.
    per_op = xp.where(xp.asarray(val_constant), t_row / f, t_row)
    t_ovh_atomic = ls_acc * per_op

    # Burst-coalesced family (Eq. 4): a single stream never thrashes rows.
    single = n_lsu < 2
    t_ovh_bc = xp.where(single, 0.0, n_bursts_bc * t_row)
    # Write-ACK wasted-burst transfer inflation (SIII-A3): each dq*bl burst
    # carries only ls_bytes useful bytes.
    waste = xp.maximum(min_burst - ls_bytes, 0)
    t_ovh_bc = t_ovh_bc + xp.where(is_ack, ls_acc * waste / bw_mem, 0.0)
    # The ACK round-trip itself is never hidden by bank interleaving.
    t_ovh_bc = t_ovh_bc + xp.where(is_ack & single, n_bursts_bc * t_row, 0.0)

    t_ovh = xp.where(is_atomic, t_ovh_atomic, t_ovh_bc)
    n_bursts = xp.where(is_atomic, 1.0 * ls_acc, n_bursts_bc)

    # Eq. 3 per-LSU term with K_lsu = delta for coalescing LSUs, 1 otherwise.
    k = xp.where(coalescing, 1.0 * delta, 1.0)
    ratio_term = ls_width / (min_burst * k)

    return {
        "burst_size": bsz,
        "n_bursts": n_bursts,
        "t_ideal": t_ideal,
        "t_ovh": t_ovh,
        "t_total": delta * (t_ideal + t_ovh),        # Eq. 1 summand
        "ratio_term": ratio_term,
        "total_bytes": total_bytes,
        "latency_bound": is_ack | is_atomic,
    }


@dataclasses.dataclass(frozen=True)
class GroupBatch:
    """Structure-of-arrays over LSU groups for ``n_kernels`` design points."""

    kernel: Any          # int [M] — kernel id per group
    n_kernels: int
    count: Any           # int [M] — identical LSUs this group represents
    lsu_type: Any        # int codes [M]
    ls_width: Any
    ls_acc: Any
    ls_bytes: Any
    delta: Any
    val_constant: Any    # bool [M]
    f: Any               # per-kernel vectorization factor, broadcast to [M]
    dq: Any
    bl: Any
    f_mem: Any
    t_rcd: Any
    t_rp: Any
    t_wr: Any
    burst_cnt: Any
    max_th: Any

    @classmethod
    def from_kernels(
        cls,
        kernels: Sequence[Sequence[Lsu]],
        dram: DramParams | Sequence[DramParams],
        bsp: BspParams | Sequence[BspParams] | None = None,
        *,
        f: int | Sequence[int] = 1,
    ) -> "GroupBatch":
        """Build a batch from per-kernel LSU lists (one group per global LSU).

        ``dram``/``bsp``/``f`` may be single values (shared by every kernel)
        or per-kernel sequences.  Non-global (on-chip) LSUs are ignored, like
        in the scalar ``estimate``.
        """
        if bsp is None:
            from repro.core.model import _default_bsp

            bsp = _default_bsp()
        n = len(kernels)
        drams = list(dram) if isinstance(dram, (list, tuple)) else [dram] * n
        bsps = list(bsp) if isinstance(bsp, (list, tuple)) else [bsp] * n
        fs = list(f) if isinstance(f, (list, tuple)) else [f] * n
        if not (len(drams) == len(bsps) == len(fs) == n):
            raise ValueError("per-kernel dram/bsp/f lengths must match kernels")

        cols: dict[str, list] = {k: [] for k in (
            "kernel", "lsu_type", "ls_width", "ls_acc", "ls_bytes", "delta",
            "val_constant", "f", "dq", "bl", "f_mem", "t_rcd", "t_rp", "t_wr",
            "burst_cnt", "max_th")}
        for ki, lsus in enumerate(kernels):
            d, b, fk = drams[ki], bsps[ki], fs[ki]
            for lsu in lsus:
                if not lsu.lsu_type.is_global:
                    continue
                cols["kernel"].append(ki)
                cols["lsu_type"].append(TYPE_CODE[lsu.lsu_type])
                cols["ls_width"].append(lsu.ls_width)
                cols["ls_acc"].append(lsu.ls_acc)
                cols["ls_bytes"].append(lsu.ls_bytes)
                cols["delta"].append(lsu.delta)
                cols["val_constant"].append(lsu.val_constant)
                cols["f"].append(fk)
                cols["dq"].append(d.dq)
                cols["bl"].append(d.bl)
                cols["f_mem"].append(d.f_mem)
                cols["t_rcd"].append(d.t_rcd)
                cols["t_rp"].append(d.t_rp)
                cols["t_wr"].append(d.t_wr)
                cols["burst_cnt"].append(b.burst_cnt)
                cols["max_th"].append(b.max_th)

        m = len(cols["kernel"])
        return cls(
            kernel=np.asarray(cols["kernel"], dtype=np.int64),
            n_kernels=n,
            count=np.ones(m, dtype=np.int64),
            lsu_type=np.asarray(cols["lsu_type"], dtype=np.int64),
            ls_width=np.asarray(cols["ls_width"], dtype=np.int64),
            ls_acc=np.asarray(cols["ls_acc"], dtype=np.int64),
            ls_bytes=np.asarray(cols["ls_bytes"], dtype=np.int64),
            delta=np.asarray(cols["delta"], dtype=np.int64),
            val_constant=np.asarray(cols["val_constant"], dtype=bool),
            f=np.asarray(cols["f"], dtype=np.int64),
            dq=np.asarray(cols["dq"], dtype=np.int64),
            bl=np.asarray(cols["bl"], dtype=np.int64),
            f_mem=np.asarray(cols["f_mem"], dtype=np.float64),
            t_rcd=np.asarray(cols["t_rcd"], dtype=np.float64),
            t_rp=np.asarray(cols["t_rp"], dtype=np.float64),
            t_wr=np.asarray(cols["t_wr"], dtype=np.float64),
            burst_cnt=np.asarray(cols["burst_cnt"], dtype=np.int64),
            max_th=np.asarray(cols["max_th"], dtype=np.int64),
        )


_JAX_REGISTERED = False


def enable_jax() -> bool:
    """Register GroupBatch as a jax pytree (idempotent; False without jax).

    Deliberately not done at import time: the numpy-only paths (sweep,
    scalar estimate, benchmarks) must not pay the jax import on startup.
    Call this before passing a GroupBatch through ``jax.jit``/``vmap``;
    ``estimate_batch`` also calls it whenever ``xp`` is not numpy.
    """
    global _JAX_REGISTERED
    if _JAX_REGISTERED:
        return True
    try:
        from jax import tree_util as _jtu
    except ImportError:
        return False
    fields = tuple(f.name for f in dataclasses.fields(GroupBatch)
                   if f.name != "n_kernels")
    try:
        _jtu.register_pytree_node(
            GroupBatch,
            lambda b: (tuple(getattr(b, n) for n in fields), b.n_kernels),
            lambda aux, ch: GroupBatch(n_kernels=aux, **dict(zip(fields, ch))),
        )
    except ValueError:  # pragma: no cover — already registered (reload)
        pass
    _JAX_REGISTERED = True
    return True


@dataclasses.dataclass(frozen=True)
class BatchEstimate:
    """Model output for a batch of kernels — array analogue of KernelEstimate."""

    t_exe: Any           # [n_kernels] Eq. 1 [s]
    t_ideal: Any         # [n_kernels] sum of delta * T_ideal
    t_ovh: Any           # [n_kernels] sum of delta * T_ovh
    bound_ratio: Any     # [n_kernels] LHS of Eq. 3
    memory_bound: Any    # bool [n_kernels]
    total_bytes: Any     # [n_kernels] useful bytes moved
    n_lsu: Any           # [n_kernels] number of global LSUs
    groups: dict         # per-group timing arrays (group_timing output)

    @property
    def effective_bandwidth(self) -> Any:
        """Useful bytes / predicted time [B/s] (inf where t_exe == 0)."""
        with np.errstate(divide="ignore", invalid="ignore"):
            out = np.where(np.asarray(self.t_exe) > 0,
                           self.total_bytes / np.maximum(self.t_exe, 1e-300),
                           np.inf)
        return out


def estimate_batch(batch: GroupBatch, xp=np,
                   paired_kernel: bool = False) -> BatchEstimate:
    """Eq. 3 classification + Eq. 1 execution time for every kernel at once.

    ``paired_kernel=True`` asserts ``batch.kernel`` is exactly
    ``concat([arange(n), arange(n)])`` (two groups per kernel, as the sweep
    scorer builds) and replaces every segment reduction with the split add
    ``data[:n] + data[n:]``.  This is bit-equal to the scatter-based
    segment sum — each segment receives exactly two contributions, and for
    two terms IEEE addition is order-independent (``0 + a == a`` and
    ``a + b == b + a`` are exact) — but avoids the serialized scatter,
    which dominates the fused device step's runtime on CPU.
    """
    if xp is not np:
        enable_jax()
    n = batch.n_kernels
    count = xp.asarray(batch.count)
    if paired_kernel:
        seg = lambda data: data[:n] + data[n:]  # noqa: E731
        n_lsu = xp.concatenate([seg(count)] * 2)
    else:
        seg = lambda data: _segment_sum(data, batch.kernel, n, xp)  # noqa: E731
        n_lsu = seg(count)[batch.kernel]
    g = group_timing(
        lsu_type=batch.lsu_type,
        ls_width=batch.ls_width,
        ls_acc=batch.ls_acc,
        ls_bytes=batch.ls_bytes,
        delta=batch.delta,
        val_constant=batch.val_constant,
        n_lsu=n_lsu,
        f=batch.f,
        dq=batch.dq,
        bl=batch.bl,
        f_mem=batch.f_mem,
        t_rcd=batch.t_rcd,
        t_rp=batch.t_rp,
        t_wr=batch.t_wr,
        burst_cnt=batch.burst_cnt,
        max_th=batch.max_th,
        xp=xp,
    )
    t_exe = seg(count * g["t_total"])
    t_ideal = seg(count * batch.delta * g["t_ideal"])
    t_ovh = seg(count * batch.delta * g["t_ovh"])
    ratio = seg(count * g["ratio_term"])
    total_bytes = seg(count * g["total_bytes"])
    latency_bound = seg(count * g["latency_bound"]) > 0
    return BatchEstimate(
        t_exe=t_exe,
        t_ideal=t_ideal,
        t_ovh=t_ovh,
        bound_ratio=ratio,
        memory_bound=(ratio >= 1.0) | latency_bound,
        total_bytes=total_bytes,
        n_lsu=seg(count),
        groups=g,
    )


# ---------------------------------------------------------------------------
# Backends: which batch estimator scores a GroupBatch
# ---------------------------------------------------------------------------

_JAX_FN = None


def _jax_estimator_fn():
    """The jit-compiled batched estimator core (built once per process):
    ``GroupBatch`` of device arrays -> dict of estimate columns."""
    global _JAX_FN
    if _JAX_FN is None:
        import jax
        import jax.numpy as jnp

        from repro import compat as _compat

        enable_jax()
        _compat.enable_compilation_cache()

        def estimate_core(b):
            with jax.named_scope("score"):
                est = estimate_batch(b, xp=jnp)
            return {"t_exe": est.t_exe, "t_ideal": est.t_ideal,
                    "t_ovh": est.t_ovh, "bound_ratio": est.bound_ratio,
                    "memory_bound": est.memory_bound,
                    "total_bytes": est.total_bytes, "n_lsu": est.n_lsu,
                    "groups": est.groups}
        # the trace's XLA Modules line names it jit_estimate_core
        _JAX_FN = jax.jit(estimate_core)
    return _JAX_FN


def _jax_estimate_batch(batch: GroupBatch,
                        stage_times: dict | None = None) -> BatchEstimate:
    """The array core under ``jax.jit`` with x64 on the default device —
    numerically equal to the NumPy path (same ops, same dtype), returned as
    NumPy arrays.  It compiles once per input shape, so fixed-shape
    streaming chunks reuse a single executable for the whole sweep.

    Three spans (:mod:`repro.core.spans`) go to ``stage_times`` when it
    is a dict: ``chunk.upload`` (the batch's arrays to the device),
    ``chunk.dispatch`` (the jitted call, which returns once it is
    enqueued) and ``chunk.pull`` (the columns back: host time blocked on
    them, the device's wait included).  So do the counters ``uploads``,
    ``upload_bytes``, ``pulls``, ``pull_bytes`` and ``device_calls``, and
    ``devices``, the number of devices the batch spans.  Nothing waits
    for the device beyond what the pull itself needs.
    """
    import jax
    import jax.numpy as jnp

    from repro import compat as _compat

    fn = _jax_estimator_fn()
    prof = stage_times
    with _compat.enable_x64():
        with _spans.span("chunk.upload", prof):
            jb = GroupBatch(**{
                f.name: (batch.n_kernels if f.name == "n_kernels"
                         else jnp.asarray(getattr(batch, f.name)))
                for f in dataclasses.fields(GroupBatch)})
        with _spans.span("chunk.dispatch", prof):
            dev = fn(jb)
        with _spans.span("chunk.pull", prof):
            out = jax.tree_util.tree_map(np.asarray, dev)
    if prof is not None:
        sent = jax.tree_util.tree_leaves(jb)
        got = jax.tree_util.tree_leaves(out)
        for key, n in (("uploads", len(sent)),
                       ("upload_bytes", sum(x.nbytes for x in sent)),
                       ("device_calls", 1),
                       ("pulls", len(got)),
                       ("pull_bytes", sum(x.nbytes for x in got))):
            _spans.count(prof, key, n)
        prof["devices"] = len(jb.count.sharding.device_set)
    groups = out.pop("groups")
    return BatchEstimate(**out, groups=groups)


def batch_estimator(backend: str, stage_times: dict | None = None):
    """The batch estimator (``GroupBatch -> BatchEstimate``) of a session
    or plan ``backend``: the jit core on jax-jit, else the NumPy core.
    ``stage_times`` receives its spans and counters (the NumPy core's call
    is ``chunk.dispatch``; see :func:`_jax_estimate_batch`)."""
    if backend == "jax-jit":
        return lambda b: _jax_estimate_batch(b, stage_times=stage_times)

    def estimate(b: GroupBatch) -> BatchEstimate:
        with _spans.span("chunk.dispatch", stage_times):
            return estimate_batch(b)
    return estimate
