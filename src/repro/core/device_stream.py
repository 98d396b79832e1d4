"""Device-resident streaming sweep: the jax-jit fast path.

The host streaming loop (:func:`repro.core.stream.run_stream`) enumerates
every chunk on the host, ships the decoded axis arrays to the device,
scores them, ships *every* estimate column back, and folds reducers in
NumPy — four host<->device boundary crossings per chunk.  This module
fuses the whole chunk step into one jit-compiled function so only a
``(start)`` scalar crosses per chunk and one reducer state crosses at the
very end:

* **In-jit enumeration** — the mixed-radix point-id -> axis decode
  (``(ids // stride) % mod``) runs on device from a chunk-start scalar;
  axis *value tables* (a few hundred numbers) live on device for the whole
  sweep.  The padded-tail rule reproduces :func:`stream._chunk_ids`
  exactly: ``ids = min(start + iota, n - 1)``.
* **In-jit feasibility mask** — a plan constrained by envelopes, bounds
  on the columns the device can read and conjunctions of those is masked
  on device (:func:`_feasible`, the usage model of
  :func:`repro.search.envelope.usage_from_axes` with ``xp=jnp``), and the
  kept lanes are moved to the front of the chunk in id order before
  scoring: exactly the compacted chunk ``plan.evaluator()`` scores on the
  host, so every fold below is unchanged and bit-equal.  Caps and bounds
  are traced data; only which columns and which comparisons join the
  step's key.
* **In-jit scoring** — the same two-group expansion as
  :func:`repro.core.sweep._score` (hardware-axis resolution, inert-axis
  normalization, Eqs. 1-10 via :func:`model_batch.estimate_batch` with
  ``xp=jnp``), producing the identical chunk-column dict the host
  evaluator would, on device.
* **On-device reducer folds** — lax-based, fixed-shape carries for
  :class:`stream.StatsReducer` (Shewchuk exact-sum partials + Chan
  moments, replicated operation for operation), :class:`stream.TopKReducer`
  and the 2-objective :class:`stream.ParetoReducer`.  Chunk sums go
  through the shared position-deterministic tree sum
  (:func:`stream._tree_sum`), which is what makes the fixed-shape
  zero-masked device fold *bit-equal* to the host fold under any chunk
  partition.  The selection reducers sort nothing: top-k takes ``k``
  rounds of a masked lexicographic minimum over (value, id), and the
  Pareto front is a staircase walk of the same minimum, one round per
  front point; only the selected rows' columns are gathered.  On the TPU
  every float64 op is emulated and the compiler spends minutes on a
  float64 sort (and tens of seconds on each copy of the Eqs. 1-10 graph,
  which is therefore traced once per step); masked reductions in a loop
  compile in seconds.
* **Overlapped dispatch** — the chunk loop enqueues step N+1 while N
  computes (jax async dispatch; the carry is donated off-CPU so state
  ping-pongs between two buffers), and the step executable is keyed only
  on (chunk size, reducer config, table bucket shapes) with every grid
  quantity passed as traced data — a warm-up sweep over a 1-point grid
  compiles the very executable the million-point sweep runs, and
  :func:`repro.compat.enable_compilation_cache` persists it across
  processes.
* **Every chip of the host** — on several local devices the id range is
  split into one contiguous, chunk-aligned range per chip
  (:meth:`DeviceSweep.split`), and one program for the mesh
  (:func:`_get_mesh_step`, the same step body under ``shard_map``) steps
  every chip through its range in lockstep, one call a round, with the
  carry stacked over the chips and the tables replicated.  The chips'
  carries are pulled once and merged into the reducers in chip order,
  as the process pool merges its ranges.

Fixed-shape carries mean two *capacity* limits the host fold does not
have: the Pareto front cap (:data:`FRONT_CAP`) and the exact-sum partial
count (:data:`N_PARTIALS`).  Both are tracked with on-device overflow
flags checked before any reducer is touched; an overflow raises
:class:`DeviceFoldOverflow` and the caller refolds the same range on the
host path — never a silently truncated result.

Everything jax lives inside functions: importing this module is
numpy-only.  :meth:`DeviceSweep.build` raises :class:`DeviceIneligible`,
naming the reason, when the plan carries a constraint the device cannot
evaluate (a callable, or a bound on a categorical column), or the plan's
axis values fall outside the integer/bool domain the device tables mirror
bit-exactly; callers record that reason where the path taken is reported.
"""
from __future__ import annotations

import contextvars

import numpy as np

from repro.core import model_batch as _mb
from repro.core import stream as _stream
from repro.core import sweep as _sweep
from repro.core.spans import count, span
from repro.search.envelope import (
    USAGE_COLUMNS,
    max_transaction_bytes,
    usage_from_axes,
)

#: Pareto front capacity of the fixed-shape device carry.  A front larger
#: than this overflows to the host path (flagged, never truncated).
FRONT_CAP = 4096

#: Shewchuk partial slots of the on-device exact sum.  Real sweeps use 2-4;
#: adversarial magnitude spreads overflow to the host path.
N_PARTIALS = 16

#: Axis value tables are padded (edge-replicated) to multiples of this, so
#: every grid whose axes fit one bucket shares a single compiled step.  A
#: table of at most one bucket is read by a one-hot lookup (:func:`_lookup`).
_TABLE_BUCKET = 128

_NUM_AXES = tuple(a for a in _sweep.AXES if a not in _sweep._CATEGORICAL)
_DRAM_FIELDS = ("dq", "bl", "f_mem", "t_rcd", "t_rp", "t_wr")
_BSP_FIELDS = ("burst_cnt", "max_th")

#: Chunk-column order (must cover everything the host evaluator emits).
COLUMNS = ("id",) + _sweep.AXES + _stream.ESTIMATE_COLUMNS + ("resource",)

#: The columns a feasibility mask evaluated on device may read.
MASK_COLUMNS = _NUM_AXES + ("lsu_type_code",) + USAGE_COLUMNS

#: The axes the usage model reads (the hardware axis resolves dram/bsp).
_USAGE_AXES = ("lsu_type", "n_ga", "simd", "elem_bytes", "include_write",
               "dram", "bsp", "hardware")

_STEP_CACHE: dict = {}

#: The table reads of one step, counted as it is traced, by (chunk size,
#: reducer config, table shapes): ``{"lookups": one-hot reads,
#: "lookup_gathers": reads that fell back to a gather}``.
_STEP_LOOKUPS: dict = {}

#: The tally the step being traced counts its table reads into.
_TALLY: contextvars.ContextVar = contextvars.ContextVar("lookup_tally",
                                                        default=None)


class DeviceFoldOverflow(RuntimeError):
    """A fixed-shape device carry ran out of capacity; refold on the host."""


class DeviceIneligible(ValueError):
    """The plan cannot take the fused device path; the message says why."""


# ---------------------------------------------------------------------------
# traced helpers (called at trace time only; jax imported lazily)
# ---------------------------------------------------------------------------

def _tree_sum_dev(x, chunk: int):
    """Traced twin of :func:`stream._tree_sum` over a zero-masked chunk."""
    import jax.numpy as jnp

    size = 1 << (chunk - 1).bit_length()
    if size != chunk:
        x = jnp.concatenate([x, jnp.zeros(size - chunk, dtype=x.dtype)])
    while size > 1:
        x = x[0::2] + x[1::2]
        size //= 2
    return x[0]


def _exact_add(parts, cnt, x):
    """Traced twin of :meth:`stream._ExactSum.add` (grow-expansion).

    ``parts`` holds ``cnt`` non-overlapping partials in slots ``[0, cnt)``;
    the unrolled loop reads the *original* slots (like the host iterating
    the list it mutates behind the read cursor) and compacts surviving
    ``lo`` terms left, appending the final ``hi``.  Returns the new
    ``(parts, cnt, overflowed)``.
    """
    import jax.numpy as jnp

    n_slots = parts.shape[0]
    idx = jnp.arange(n_slots, dtype=jnp.int32)
    new_parts = jnp.zeros_like(parts)
    i = jnp.int32(0)
    for j in range(n_slots):
        active = j < cnt
        y = parts[j]
        swap = jnp.abs(x) < jnp.abs(y)
        big = jnp.where(swap, y, x)
        small = jnp.where(swap, x, y)
        hi = big + small
        lo = small - (hi - big)
        keep = active & (lo != 0.0)
        new_parts = jnp.where((idx == i) & keep, lo, new_parts)
        i = jnp.where(keep, i + jnp.int32(1), i)
        x = jnp.where(active, hi, x)
    overflow = i >= n_slots
    new_parts = jnp.where(idx == i, x, new_parts)
    return new_parts, jnp.minimum(i + jnp.int32(1), n_slots), overflow


def _lexmin(live, keys):
    """Index of the lexicographically smallest ``live`` entry by ``keys``
    (a tuple of arrays; the first index breaks a full tie), and whether any
    entry is live.  Plain masked reductions: no sort, no bitcast."""
    import jax.numpy as jnp

    cand = live
    for k in keys:
        fill = (jnp.inf if jnp.issubdtype(k.dtype, jnp.floating)
                else jnp.iinfo(k.dtype).max)
        cand = cand & (k == jnp.min(jnp.where(cand, k, fill)))
    return jnp.argmax(cand), jnp.any(cand)


def _lookup(table, code):
    """``table[code]`` for a vector of codes into the true prefix of
    ``table``, bit for bit.

    A table of at most :data:`_TABLE_BUCKET` entries is read by a one-hot
    select over its static length, which fuses into elementwise work: the
    TPU runs a per-lane gather as one gather of 32-bit words per lane and
    word, the longest op of the step.  Exactly one entry is hit, so the
    reduce is exact: a sum for integers, ``any`` for bools, and for floats
    a reduce that keeps the value of the hit entry by select, so every bit
    stays (an arithmetic max would flush subnormals on the CPU).  A longer
    table is gathered.  The step being traced counts each read
    (:data:`_TALLY`).
    """
    import jax.numpy as jnp
    from jax import lax

    m = table.shape[0]
    tally = _TALLY.get()
    if m > _TABLE_BUCKET:
        if tally is not None:
            tally["lookup_gathers"] += 1
        return table[code]
    if tally is not None:
        tally["lookups"] += 1
    hit = code[:, None] == jnp.arange(m, dtype=code.dtype)[None, :]
    if table.dtype == jnp.bool_:
        return jnp.any(hit & table[None, :], axis=1)
    if jnp.issubdtype(table.dtype, jnp.floating):
        def keep_hit(a, b):
            return a[0] | b[0], jnp.where(a[0], a[1], b[1])

        return lax.reduce((hit, jnp.broadcast_to(table[None, :], hit.shape)),
                          (False, jnp.zeros((), table.dtype)), keep_hit,
                          (1,))[1]
    return jnp.sum(jnp.where(hit, table[None, :], 0), axis=1,
                   dtype=table.dtype)


def _table_shapes(tables) -> tuple:
    """The shapes of ``tables`` (host arrays or tracers), by name."""
    return tuple(sorted((k, tuple(np.shape(v))) for k, v in tables.items()))


def _decode(tables, ids, axes=_sweep.AXES):
    """Axis codes and numeric axis values of ``ids`` on ``axes``, from the
    tables."""
    strides, mods = tables["strides"], tables["mods"]
    # decode in the tables' integer width: int32 whenever the grid fits it
    # (see DeviceSweep.build), since 64-bit division is emulated on the TPU
    dec = ids.astype(strides.dtype)
    code = {name: (dec // strides[i]) % mods[i]
            for i, name in enumerate(_sweep.AXES) if name in axes}
    num = {k: _lookup(tables["num_" + k], code[k])
           for k in _NUM_AXES if k in code}
    return code, num


def _mask_axes(mask_sig: tuple) -> tuple:
    """The axes the comparisons of ``mask_sig`` read."""
    need = set()
    for col, _ in mask_sig:
        if col in USAGE_COLUMNS:
            need.update(_USAGE_AXES)
        elif col == "lsu_type_code":
            need.add("lsu_type")
        else:
            need.add(col)
    return tuple(a for a in _sweep.AXES if a in need)


def _feasible(tables, code, num, mask_sig: tuple):
    """The traced twin of the plan's feasibility mask.

    ``mask_sig`` lists ``(column, op)`` comparisons, ANDed; their bounds are
    ``tables["mask_bounds"]``.  Columns read as
    :class:`repro.search.constraints.GridColumns` serves them: raw axis
    values, the LSU type code, and the usage columns of
    :func:`repro.search.envelope.usage_from_axes` against each point's
    effective DRAM/BSP (the hardware axis resolved as in
    :func:`_score_ids`), with the burst-buffer size from a host-built
    table.  Every comparison is in float64, as on the host.
    """
    import jax.numpy as jnp

    cols = dict(num)
    need = {col for col, _ in mask_sig}
    if need & {"lsu_type_code", *USAGE_COLUMNS}:
        cols["lsu_type_code"] = _lookup(tables["lsu_code"],
                                        code["lsu_type"])
    if need & set(USAGE_COLUMNS):
        own = _lookup(tables["hw_own"], code["hardware"])
        d_code = jnp.where(own, code["dram"],
                           tables["len_d"] + code["hardware"])
        b_code = jnp.where(own, code["bsp"],
                           tables["len_b"] + code["hardware"])
        cols.update(usage_from_axes(
            type_codes=cols["lsu_type_code"], n_ga=num["n_ga"],
            simd=num["simd"], elem_bytes=num["elem_bytes"],
            include_write=num["include_write"],
            max_txn=_lookup(tables["max_txn"],
                            d_code * tables["len_bx"] + b_code),
            xp=jnp))
    keep = None
    for i, (col, op) in enumerate(mask_sig):
        v = cols[col].astype(jnp.float64)
        bound = tables["mask_bounds"][i]
        ok = v <= bound if op == "<=" else v >= bound
        keep = ok if keep is None else keep & ok
    return keep


def _compact(tables, start, chunk: int, mask_sig: tuple):
    """The chunk's lanes that the plan keeps, first and in lane order, then
    lanes past the chunk (which read as an in-range id); and their count.

    This is the chunk ``plan.evaluator()`` scores on the host: its feasible
    ids, re-padded.  Folding it under the tail rule ``iota < count`` adds
    the kept rows at the positions the host's fold gives them, which the
    position-paired :func:`_tree_sum_dev` needs for equal bits.
    """
    import jax.numpy as jnp

    n = tables["n"]
    iota = jnp.arange(chunk, dtype=jnp.int64)
    ids = jnp.minimum(start + iota, n - 1)
    valid = jnp.minimum(jnp.int64(chunk), n - start)
    code, num = _decode(tables, ids, _mask_axes(mask_sig))
    keep = _feasible(tables, code, num, mask_sig) & (iota < valid)
    lane = jnp.arange(chunk, dtype=jnp.int32)
    lanes = jnp.sort(jnp.where(keep, lane, jnp.int32(chunk)))
    return lanes.astype(jnp.int64), jnp.sum(keep.astype(jnp.int64))


def _score_ids(tables, ids, code, num):
    """The in-jit twin of ``plan.evaluator()``'s ``score_ids``.

    Takes an arbitrary id vector with its axis codes and values
    (:func:`_decode`), replicates :func:`sweep._score`'s two-group
    construction and hardware resolution, and runs
    :func:`model_batch.estimate_batch` with ``xp=jnp`` (``paired_kernel``
    replaces each scatter-based segment sum with its bit-equal two-term
    split add) — so every column is bit-equal to the host evaluator's for
    the same ids.
    """
    import jax.numpy as jnp

    chunk = ids.shape[0]
    iota = jnp.arange(chunk, dtype=jnp.int64)

    type_codes = _lookup(tables["lsu_code"], code["lsu_type"])
    own = _lookup(tables["hw_own"], code["hardware"])
    hw_scale = jnp.where(own, 1.0, _lookup(tables["hw_hf"], code["hardware"]))
    d_code = jnp.where(own, code["dram"], tables["len_d"] + code["hardware"])
    b_code = jnp.where(own, code["bsp"], tables["len_b"] + code["hardware"])

    n_ga, simd = num["n_ga"], num["simd"]
    n_elems, elem_bytes = num["n_elems"], num["elem_bytes"]
    is_atomic = type_codes == _mb.ATOMIC
    is_ack = type_codes == _mb.WRITE_ACK

    # _normalize_inert_axes, traced
    delta = jnp.where(is_atomic | is_ack, 1, num["delta"])
    val_constant = num["val_constant"] & is_atomic
    include_write = num["include_write"] & ~is_atomic

    g1_type = jnp.where(is_ack, _mb.ALIGNED, type_codes)
    g1_count = jnp.where(is_atomic | is_ack, n_ga, n_ga + include_write)
    g1_width = jnp.where(is_atomic, elem_bytes, simd * elem_bytes)
    # n_elems // simd from a host-built table: 64-bit division is emulated
    # on the TPU
    per_simd = _lookup(tables["acc_per_simd"],
                       code["n_elems"] * tables["len_simd"] + code["simd"])
    g1_acc = jnp.where(is_atomic, n_elems, per_simd)
    g2_count = jnp.where(is_ack & include_write, simd, 0)

    vec = lambda a, b: jnp.concatenate([a, b])  # noqa: E731
    dram_f = {k: _lookup(tables["dram_" + k], d_code) for k in _DRAM_FIELDS}
    bsp_f = {k: _lookup(tables["bsp_" + k], b_code) for k in _BSP_FIELDS}
    batch = _mb.GroupBatch(
        kernel=vec(iota, iota),
        n_kernels=chunk,
        count=vec(g1_count, g2_count),
        lsu_type=vec(g1_type, jnp.full(chunk, _mb.WRITE_ACK,
                                       dtype=jnp.int64)),
        ls_width=vec(g1_width, elem_bytes),
        ls_acc=vec(g1_acc, per_simd),
        ls_bytes=vec(g1_width, elem_bytes),
        delta=vec(delta, jnp.ones(chunk, dtype=jnp.int64)),
        val_constant=vec(val_constant, jnp.zeros(chunk, dtype=bool)),
        f=vec(simd, simd),
        **{k: vec(v, v) for k, v in {**dram_f, **bsp_f}.items()},
    )
    est = _mb.estimate_batch(batch, xp=jnp, paired_kernel=True)

    # hardware host_factor then session calibration — the same two
    # multiplies, in the same order, as _score + evaluator() (a 1.0 scale
    # is an exact multiplicative identity, so applying them
    # unconditionally matches the host's conditional skips bit-for-bit).
    cal = jnp.where(own, tables["calib"], 1.0)
    w = (batch.count * batch.ls_width).astype(jnp.float64)

    cols = {
        "id": ids,
        "lsu_type": code["lsu_type"].astype(jnp.int64),
        "n_ga": n_ga, "simd": simd, "n_elems": n_elems, "delta": delta,
        "elem_bytes": elem_bytes,
        "include_write": include_write, "val_constant": val_constant,
        "dram": d_code.astype(jnp.int64), "bsp": b_code.astype(jnp.int64),
        "hardware": code["hardware"].astype(jnp.int64),
    }
    for name in _stream.ESTIMATE_COLUMNS:
        v = getattr(est, name)
        if name in ("t_exe", "t_ideal", "t_ovh"):
            v = (v * hw_scale) * cal
        if name in ("total_bytes", "n_lsu"):
            # the host's np.bincount segment sum promotes these to float64;
            # the paired split add keeps int64 — cast to match the host
            # column dtype exactly (values are small integers, lossless)
            v = v.astype(jnp.float64)
        cols[name] = v
    # np.bincount folds (0 + w1) + w2 per point; 0 + w1 == w1 exactly.
    cols["resource"] = w[:chunk] + w[chunk:]
    return cols


def _score_chunk(tables, start, chunk: int):
    """Chunk-shaped :func:`_score_ids`: decode ids from a start scalar.

    The padded-tail rule reproduces :func:`stream._chunk_ids` exactly:
    ``ids = min(start + iota, n - 1)``.  Returns ``(cols, valid, mask)``.
    """
    import jax
    import jax.numpy as jnp

    n = tables["n"]
    with jax.named_scope("decode"):
        iota = jnp.arange(chunk, dtype=jnp.int64)
        ids = jnp.minimum(start + iota, n - 1)
        valid = jnp.minimum(jnp.int64(chunk), n - start)
        mask = iota < valid
        code, num = _decode(tables, ids)
    with jax.named_scope("score"):
        cols = _score_ids(tables, ids, code, num)
    return cols, valid, mask


def _score_kept(tables, start, chunk: int, mask_sig: tuple):
    """:func:`_score_chunk` under a feasibility mask: the chunk's kept ids,
    compacted (:func:`_compact`), scored; ``valid`` is their count."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("mask"):
        lanes, valid = _compact(tables, start, chunk, mask_sig)
    with jax.named_scope("decode"):
        iota = jnp.arange(chunk, dtype=jnp.int64)
        ids = jnp.minimum(start + lanes, tables["n"] - 1)
        mask = iota < valid
        code, num = _decode(tables, ids)
    with jax.named_scope("score"):
        cols = _score_ids(tables, ids, code, num)
    return cols, valid, mask


def _fold_stats(st, cols, valid, mask, chunk: int):
    """Traced twin of :meth:`stream.StatsReducer.update` for one chunk."""
    import jax.numpy as jnp

    t = cols["t_exe"]                                   # already float64
    tz = jnp.where(mask, t, 0.0)
    s = _tree_sum_dev(tz, chunk)
    tb = jnp.where(mask, cols["total_bytes"].astype(jnp.float64), 0.0)
    mb = jnp.sum(jnp.where(mask, cols["memory_bound"],
                           False).astype(jnp.int64))

    te_parts, te_cnt, ovf1 = _exact_add(st["te_parts"], st["te_cnt"], s)
    tb_parts, tb_cnt, ovf2 = _exact_add(st["tb_parts"], st["tb_cnt"],
                                        _tree_sum_dev(tb, chunk))

    mf = valid.astype(jnp.float64)
    cmean = s / mf
    cm2 = _tree_sum_dev(jnp.where(mask, (t - cmean) ** 2, 0.0), chunk)
    # _chan_merge(n_points, mean, m2, valid, cmean, cm2), same op order.
    # XLA:CPU contracts a product feeding an add into one FMA, rounding
    # once where the host rounds twice; a select between them (always
    # true: every step folds at least one point) keeps the two roundings.
    n_new = st["n"] + valid
    nf = n_new.astype(jnp.float64)
    d = cmean - st["mean"]
    live = n_new > 0
    mean = st["mean"] + jnp.where(live, d * (mf / nf), 0.0)
    m2 = st["m2"] + cm2 + jnp.where(
        live, d * d * (st["n"].astype(jnp.float64) / nf * mf), 0.0)

    vals = jnp.where(mask, t, jnp.inf)
    i = jnp.argmin(vals)                     # first occurrence, like numpy
    v = vals[i]
    pid = cols["id"][i]
    better = (v < st["vmin"]) | ((v == st["vmin"]) & (pid < st["vid"]))
    return {
        "n": n_new, "mb": st["mb"] + mb,
        "vmin": jnp.where(better, v, st["vmin"]),
        "vid": jnp.where(better, pid, st["vid"]),
        "te_parts": te_parts, "te_cnt": te_cnt,
        "tb_parts": tb_parts, "tb_cnt": tb_cnt,
        "mean": mean, "m2": m2,
        "ovf": st["ovf"] | ovf1 | ovf2,
    }


def _fold_topk(st, cols, valid, mask, k: int, key: str):
    """Traced twin of :meth:`stream.TopKReducer.update` for one chunk.

    The held rows and the chunk's live lanes form one candidate set; ``k``
    rounds of :func:`_lexmin` over its (value, id) pairs, as float64 like
    the host's keys, pick the next row each — exactly the host's stable
    (value, id) order.  The picked rows' columns are gathered in rank
    order.  No sort: the TPU's compiler spends minutes on a float64 sort
    comparator, and ``k`` masked reductions cost next to nothing.
    """
    import jax
    import jax.numpy as jnp

    held = jnp.minimum(st["n_seen"], k)
    vals = jnp.concatenate([st["cols"][key], cols[key]]).astype(jnp.float64)
    ids = jnp.concatenate([st["cols"]["id"], cols["id"]])
    live = jnp.concatenate([jnp.arange(k) < held, mask])
    pos = jnp.arange(live.shape[0])

    def pick(j, carry):
        live, picks = carry
        p, found = _lexmin(live, (vals, ids))
        return (live & ~(found & (pos == p)),
                picks.at[j].set(jnp.where(found, p, 0)))

    _, picks = jax.lax.fori_loop(0, k, pick,
                                 (live, jnp.zeros(k, dtype=pos.dtype)))
    return {"cols": {c: jnp.concatenate([st["cols"][c], cols[c]])[picks]
                     for c in COLUMNS},
            "n_seen": st["n_seen"] + valid}


def _fold_pareto(st, cols, valid, mask, cap: int, objectives):
    """Traced twin of :meth:`stream.ParetoReducer.update` (2 objectives).

    The held front and the chunk's live lanes form one candidate set,
    keyed by the objectives as float64 like the host's.  A staircase walk
    finds its front: each round takes the live rows at the lexicographic
    minimum (v0, v1) — non-dominated, and kept with all their duplicates,
    as :func:`sweep._pareto_2d` keeps them — and retires every row with
    v1 >= that minimum, which it dominates.  Rounds equal the number of
    distinct front points.  Survivors keep their position order (held
    rows first, then lanes by ascending id), the host's held order, and
    are compacted into the ``cap`` slots with a prefix count.  More than
    ``cap`` survivors sets the overflow flag.  No sort: see
    :func:`_fold_topk`.
    """
    import jax
    import jax.numpy as jnp

    o0, o1 = objectives
    v0 = jnp.concatenate([st["cols"][o0], cols[o0]]).astype(jnp.float64)
    v1 = jnp.concatenate([st["cols"][o1], cols[o1]]).astype(jnp.float64)
    live = jnp.concatenate([jnp.arange(cap) < st["count"], mask])
    m = live.shape[0]

    def more(carry):
        alive, _, rounds = carry
        return jnp.any(alive) & (rounds <= cap)

    def walk(carry):
        alive, front, rounds = carry
        p, _ = _lexmin(alive, (v0, v1))
        at_min = alive & (v0 == v0[p]) & (v1 == v1[p])
        return alive & (v1 < v1[p]), front | at_min, rounds + 1

    alive, front, _ = jax.lax.while_loop(
        more, walk, (live, jnp.zeros(m, dtype=bool), jnp.int32(0)))
    count = jnp.sum(front.astype(jnp.int32))
    slot = jnp.cumsum(front.astype(jnp.int32)) - 1
    slot = jnp.where(front & (slot < cap), slot, cap)
    perm = jnp.zeros(cap + 1, dtype=jnp.int32).at[slot].set(
        jnp.arange(m, dtype=jnp.int32))[:cap]
    return {
        "cols": {c: jnp.concatenate([st["cols"][c], cols[c]])[perm]
                 for c in COLUMNS},
        "count": jnp.minimum(count, cap).astype(jnp.int64),
        "ovf": st["ovf"] | (count > cap) | jnp.any(alive),
    }


def _step_body(chunk: int, sig: tuple):
    """The fused chunk step's body, ``sweep_step(carry, tables, start) ->
    carry``, for (chunk size, reducer config).

    Everything else (grid geometry, axis tables, calibration, caps and
    bounds) is traced data, so one executable serves every grid whose
    tables fit the same padded buckets.  A feasibility mask is the last
    entry of ``sig``, ``("mask", ((column, op), ...))``: its structure is
    part of the key, and its carry is the count of points kept.  Tracing
    the body records its table reads in :data:`_STEP_LOOKUPS`.
    """
    import jax
    import jax.numpy as jnp

    mask_sig = next((spec[1] for spec in sig if spec[0] == "mask"), ())

    def sweep_step(carry, tables, start):
        tally = {"lookups": 0, "lookup_gathers": 0}
        _STEP_LOOKUPS[(chunk, sig, _table_shapes(tables))] = tally
        token = _TALLY.set(tally)
        try:
            if mask_sig:
                cols, valid, mask = _score_kept(tables, start, chunk,
                                                mask_sig)
            else:
                cols, valid, mask = _score_chunk(tables, start, chunk)
        finally:
            _TALLY.reset(token)
        out = []
        with jax.named_scope("fold"):
            for spec, st in zip(sig, carry):
                if spec[0] == "stats":
                    new = _fold_stats(st, cols, valid, mask, chunk)
                elif spec[0] == "topk":
                    new = _fold_topk(st, cols, valid, mask, spec[1], spec[2])
                elif spec[0] == "pareto":
                    new = _fold_pareto(st, cols, valid, mask, spec[1],
                                       spec[2])
                else:                       # the mask's count of kept points
                    new = st + valid
                if mask_sig:
                    # a chunk that keeps nothing folds nothing, as on the
                    # host (the stats fold would divide by its zero count)
                    new = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(valid > 0, a, b), new, st)
                out.append(new)
        return tuple(out)

    return sweep_step


def _get_step(chunk: int, sig: tuple):
    """The jit-compiled fused chunk step of one chip, ``step(carry, tables,
    start) -> carry`` (:func:`_step_body`).  The carry is donated off-CPU
    (CPU donation is a no-op that warns)."""
    import jax

    key = (chunk, sig, jax.default_backend())
    step = _STEP_CACHE.get(key)
    if step is not None:
        return step
    # the trace's XLA Modules line names it jit_sweep_step
    donate = (0,) if jax.default_backend() != "cpu" else ()
    step = jax.jit(_step_body(chunk, sig), donate_argnums=donate)
    _STEP_CACHE[key] = step
    return step


def _mesh_shardings(devices: tuple):
    """The shardings on the chip mesh of ``devices``: stacked over the
    chips (a leading axis split over them), and replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro import compat as _compat

    mesh = _compat.chip_mesh(devices)
    return (NamedSharding(mesh, PartitionSpec(_compat.CHIP_AXIS)),
            NamedSharding(mesh, PartitionSpec()))


def _get_mesh_step(chunk: int, sig: tuple, devices: tuple):
    """The fused chunk step of several chips: one program for the mesh of
    ``devices``, ``step(carry, tables, starts) -> carry``.

    Every leaf of ``carry`` is stacked over the chips (sharded on the chip
    axis), ``tables`` are replicated, and ``starts`` is ``int64[chips]``:
    each chip runs :func:`_step_body` on its own chunk and carry.  A chip
    whose range is used up steps at ``start = n``, a chunk of padding
    only, and keeps its carry.
    """
    import jax
    import jax.numpy as jnp

    from repro import compat as _compat

    key = (chunk, sig, jax.default_backend(), tuple(d.id for d in devices))
    step = _STEP_CACHE.get(key)
    if step is not None:
        return step
    body = _step_body(chunk, sig)

    def chip_step(carry, tables, starts):
        start = starts[0]
        st = jax.tree_util.tree_map(lambda a: a[0], carry)
        new = body(st, tables, start)
        valid = jnp.minimum(jnp.int64(chunk), tables["n"] - start)
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(valid > 0, a, b)[None], new, st)

    on_chips, replicated = _mesh_shardings(devices)
    mapped = _compat.shard_map(
        chip_step, on_chips.mesh,
        in_specs=(on_chips.spec, replicated.spec, on_chips.spec),
        out_specs=on_chips.spec)

    def sweep_step(carry, tables, starts):
        return mapped(carry, tables, starts)

    donate = (0,) if jax.default_backend() != "cpu" else ()
    step = jax.jit(sweep_step, donate_argnums=donate,
                   in_shardings=(on_chips, replicated, on_chips),
                   out_shardings=on_chips)
    _STEP_CACHE[key] = step
    return step


# ---------------------------------------------------------------------------
# DeviceSweep: host-side driver
# ---------------------------------------------------------------------------

def _pad_table(arr: np.ndarray) -> np.ndarray:
    """Edge-replicate to the next :data:`_TABLE_BUCKET` multiple (padding
    is never gathered — codes only index the true prefix)."""
    size = -(-len(arr) // _TABLE_BUCKET) * _TABLE_BUCKET
    if size == len(arr):
        return arr
    return np.concatenate([arr, np.repeat(arr[-1:], size - len(arr),
                                          axis=0)])


def _mask_terms(constraints) -> list:
    """The plan's constraints as ``(column, op, bound)`` comparisons, ANDed.

    Raises :class:`DeviceIneligible`, naming the constraint, for any the
    device cannot evaluate.
    """
    from repro.search.constraints import (
        AllOf,
        BoundConstraint,
        EnvelopeConstraint,
    )

    terms: list = []

    def visit(c) -> None:
        if type(c) is AllOf:
            for p in c.parts:
                visit(p)
        elif type(c) is EnvelopeConstraint:
            terms.extend((col, "<=", cap)
                         for col, cap in c.envelope.caps().items())
        elif type(c) is BoundConstraint and c.column in MASK_COLUMNS:
            terms.append((c.column, c.op, float(c.bound)))
        elif type(c) is BoundConstraint:
            raise DeviceIneligible(f"constrained plan: a bound on column "
                                   f"{c.column!r} is evaluated on the host")
        else:
            raise DeviceIneligible(f"constrained plan: a "
                                   f"{type(c).__name__} is evaluated on "
                                   f"the host")

    for c in constraints:
        visit(c)
    return terms


_COL_DTYPES = {
    **{a: np.int64 for a in ("id", "lsu_type", "n_ga", "simd", "n_elems",
                             "delta", "elem_bytes", "dram", "bsp",
                             "hardware")},
    # total_bytes / n_lsu are float64 on the host too: its np.bincount
    # segment sum promotes the integer inputs
    **{a: np.float64 for a in ("t_exe", "t_ideal", "t_ovh", "bound_ratio",
                               "resource", "total_bytes", "n_lsu")},
    **{a: np.bool_ for a in ("include_write", "val_constant",
                             "memory_bound")},
}


class DeviceSweep:
    """One plan's device-resident fold driver (build via :meth:`build`)."""

    def __init__(self, plan: "_stream.SweepPlan", tables: dict,
                 mask_sig: tuple, devices: tuple):
        self.plan = plan
        self.n = plan.enumerator().n
        self.chunk = plan.chunk_size
        self.front_cap = FRONT_CAP
        #: ``(column, op)`` of each comparison of the feasibility mask
        self.mask_sig = mask_sig
        #: the chips a fold splits its range over, in order
        self.devices = devices
        self._tables_host = tables
        self._tables_dev = None

    # -- eligibility --------------------------------------------------------

    @classmethod
    def build(cls, plan: "_stream.SweepPlan") -> "DeviceSweep":
        """A driver for ``plan`` on every local device: a fold splits its
        range over them (:meth:`fold_range`).

        Raises :class:`DeviceIneligible`, naming the reason, when the host
        path must run instead: non-jax backend, a constraint the device
        cannot evaluate (a callable, or a bound on a column outside
        :data:`MASK_COLUMNS`; envelopes, such bounds and their
        conjunctions are masked on device), an empty grid,
        non-integer/bool numeric axis values (the device tables mirror the
        host's gathered dtypes exactly), or axis values the host evaluator
        itself would reject.
        """
        import jax

        from repro import compat as _compat

        if plan.backend != "jax-jit":
            raise DeviceIneligible(f"backend {plan.backend!r}")
        terms = _mask_terms(plan.constraints)
        mask_sig = tuple((col, op) for col, op, _ in terms)
        lists = {k: list(v) for k, v in plan.lists.items()}
        enum = _stream.GridEnumerator(lists)
        if enum.n == 0:
            raise DeviceIneligible("empty grid")

        idt = np.int32 if enum.n < 2 ** 31 else np.int64
        tables: dict = {
            "strides": enum.strides.astype(idt),
            "mods": enum._mod.astype(idt),
            "n": np.int64(enum.n),
            "calib": np.float64(plan.calibration_factor),
        }
        for k in _NUM_AXES:
            arr = np.asarray(lists[k])
            want = np.bool_ if k in ("include_write",
                                     "val_constant") else np.int64
            if arr.dtype == object or not (
                    np.issubdtype(arr.dtype, np.integer)
                    or np.issubdtype(arr.dtype, np.bool_)):
                raise DeviceIneligible(f"non-integer values on axis {k!r}")
            tables["num_" + k] = _pad_table(arr.astype(want))
        for k in ("n_ga", "simd", "delta"):
            if tables["num_" + k][:len(lists[k])].min(initial=1) < 1:
                raise DeviceIneligible(f"axis {k!r} has values < 1")
        ne = np.asarray(lists["n_elems"], dtype=np.int64)
        sd = np.asarray(lists["simd"], dtype=np.int64)
        if np.any(ne[:, None] % sd[None, :]):
            raise DeviceIneligible("a simd value does not divide n_elems")
        tables["acc_per_simd"] = _pad_table((ne[:, None] // sd[None, :])
                                            .ravel())
        tables["len_simd"] = idt(len(sd))

        try:
            lsu_codes = np.asarray([_mb.TYPE_CODE[t]
                                    for t in lists["lsu_type"]],
                                   dtype=np.int64)
        except (KeyError, TypeError):
            raise DeviceIneligible("unknown lsu_type value") from None
        tables["lsu_code"] = _pad_table(lsu_codes)

        hw_table = lists["hardware"]
        try:
            drams_v, bsps_v, hf, is_none = _sweep._hardware_views(hw_table)
            all_own = bool(is_none.all())
            # Mirror _resolve_hardware_codes: the dram/bsp tables are
            # extended with the per-hardware views only when any spec is
            # set; all-None leaves them (and the codes) untouched.
            d_table = lists["dram"] + ([] if all_own else drams_v)
            b_table = lists["bsp"] + ([] if all_own else bsps_v)
            for k in _DRAM_FIELDS:
                tables["dram_" + k] = _pad_table(np.asarray(
                    [getattr(d, k) if d is not None else 0
                     for d in d_table]))
            for k in _BSP_FIELDS:
                tables["bsp_" + k] = _pad_table(np.asarray(
                    [getattr(b, k) if b is not None else 0
                     for b in b_table]))
            if any(col in USAGE_COLUMNS for col, _ in mask_sig):
                # max_transaction_bytes over every (dram, bsp) pair, built
                # here as the host mask builds it: the TPU emulates float64,
                # and a pow there could land an ulp off at a cap
                gather = lambda table, attr: np.asarray(  # noqa: E731
                    [getattr(o, attr) if o is not None else 0
                     for o in table], dtype=np.float64)
                tables["max_txn"] = _pad_table(max_transaction_bytes(
                    gather(d_table, "dq")[:, None],
                    gather(d_table, "bl")[:, None],
                    gather(b_table, "burst_cnt")[None, :]).ravel())
                tables["len_bx"] = np.int64(len(b_table))
        except (AttributeError, TypeError):
            raise DeviceIneligible("unreadable dram/bsp/hardware axis "
                                   "values") from None
        tables["hw_own"] = _pad_table(np.asarray(is_none, dtype=bool))
        tables["hw_hf"] = _pad_table(np.asarray(hf, dtype=np.float64))
        tables["len_d"] = np.int64(len(lists["dram"]))
        tables["len_b"] = np.int64(len(lists["bsp"]))
        if terms:
            tables["mask_bounds"] = np.asarray([b for _, _, b in terms],
                                               dtype=np.float64)

        _compat.enable_compilation_cache()
        return cls(plan, tables, mask_sig, tuple(jax.local_devices()))

    def supports(self, reducers) -> bool:
        return self._sig(reducers) is not None

    def _sig(self, reducers) -> tuple | None:
        sig = []
        for r in reducers:
            if type(r) is _stream.StatsReducer:
                sig.append(("stats",))
            elif type(r) is _stream.TopKReducer and r.key in COLUMNS:
                sig.append(("topk", r.k, r.key))
            elif (type(r) is _stream.ParetoReducer
                    and len(r.objectives) == 2
                    and all(o in COLUMNS for o in r.objectives)):
                sig.append(("pareto", self.front_cap, tuple(r.objectives)))
            else:
                return None
        if self.mask_sig:
            sig.append(("mask", self.mask_sig))
        return tuple(sig)

    # -- carries ------------------------------------------------------------

    def _init_carry(self, sig: tuple):
        """The empty carry, on the host: one ``device_put`` of its leaves
        costs a few milliseconds, where creating each on the device ran a
        program per leaf."""
        carry = []
        for spec in sig:
            if spec[0] == "stats":
                carry.append({
                    "n": np.int64(0), "mb": np.int64(0),
                    "vmin": np.float64(np.inf), "vid": np.int64(-1),
                    "te_parts": np.zeros(N_PARTIALS, dtype=np.float64),
                    "te_cnt": np.int32(0),
                    "tb_parts": np.zeros(N_PARTIALS, dtype=np.float64),
                    "tb_cnt": np.int32(0),
                    "mean": np.float64(0.0), "m2": np.float64(0.0),
                    "ovf": np.bool_(False),
                })
            elif spec[0] == "topk":
                carry.append({
                    "cols": {c: np.zeros(spec[1], dtype=_COL_DTYPES[c])
                             for c in COLUMNS},
                    "n_seen": np.int64(0),
                })
            elif spec[0] == "pareto":
                carry.append({
                    "cols": {c: np.zeros(spec[1], dtype=_COL_DTYPES[c])
                             for c in COLUMNS},
                    "count": np.int64(0),
                    "ovf": np.bool_(False),
                })
            else:
                carry.append(np.int64(0))           # points the mask kept
        return tuple(carry)

    # -- the fold -----------------------------------------------------------

    def split(self, lo: int, hi: int) -> list:
        """Chunk-aligned ``[lo, hi)`` as one contiguous ``(lo, hi)`` range
        per device, in device order: sizes differ by at most one chunk, the
        last range ends at ``hi`` and a device with no chunk gets an empty
        range.  Each is a range :meth:`SweepPlan.run_range` folds."""
        chunk, ndev = self.chunk, len(self.devices)
        base, extra = divmod(-(-(hi - lo) // chunk), ndev)
        out, c = [], 0
        for i in range(ndev):
            first, c = c, c + base + (i < extra)
            out.append((min(lo + first * chunk, hi), min(lo + c * chunk, hi)))
        return out

    def fold_range(self, lo: int, hi: int, reducers,
                   profile: dict | None = None) -> None:
        """Fold chunk-aligned ``[lo, hi)`` into ``reducers`` on device.

        Same alignment contract as :meth:`SweepPlan.run_range`.  The loop
        enqueues every chunk step without a host sync (jax async
        dispatch); reducer state is pulled to the host exactly once.
        Overflow flags are validated *before* any reducer is touched, so
        on :class:`DeviceFoldOverflow` the reducers are untouched and the
        caller can refold the identical range on the host path.

        On several devices the range is split (:meth:`split`) and every
        round is one call of one program for the mesh
        (:func:`_get_mesh_step`) that steps each chip through its own
        range, in lockstep; the chips' carries are merged into
        ``reducers`` in device order, as the process pool merges ranges
        (every reported number but the variance is bit-equal to one chip's
        fold of the whole range; the variance combines through
        :func:`stream._chan_merge`, like any partition's).

        Its spans (:mod:`repro.core.spans`), added to ``profile`` when it
        is a dict: ``sweep.open`` (the upload of the carry and, once, of
        the tables: ``sweep.upload``), ``sweep.dispatch`` (the step
        enqueue loop; its first call is ``sweep.compile``), ``sweep.wait``
        (the host blocked on the queued steps, while it pulls the first
        carry leaf) and ``sweep.close`` (the rest of the pull,
        ``sweep.pull``, the overflow checks and the merge of the chips'
        states into the reducers, ``sweep.merge``).  Counters: ``chunks``,
        ``lanes`` (real chunks and their lanes), ``feasible`` (the points
        kept, counted on device under a mask), ``uploads``/``upload_bytes``
        (tables, the carry's leaves and each round's starts),
        ``pulls``/``pull_bytes`` (carry leaves), ``device_calls`` (rounds),
        ``devices`` (the devices that held a range) and ``lookups`` /
        ``lookup_gathers`` (the step's one-hot table reads and its gathers,
        counted as the step was traced, times the steps every device ran).
        """
        import jax

        from repro import compat as _compat

        n, chunk = self.n, self.chunk
        lo, hi = int(lo), min(int(hi), n)
        if lo % chunk:
            raise ValueError(f"range start {lo} is not chunk-aligned "
                             f"(chunk_size={chunk})")
        if hi % chunk and hi != n:
            raise ValueError(f"range stop {hi} is not chunk-aligned "
                             f"(chunk_size={chunk}) and is not the grid "
                             f"end {n}")
        if hi <= lo:
            return
        reducers = tuple(reducers)
        sig = self._sig(reducers)
        if sig is None:
            raise ValueError("unsupported reducer set for the device fold; "
                             "check supports() first")
        if profile is not None:
            profile.setdefault("path", "device-fused")
        ranges = self.split(lo, hi)
        ndev = len(ranges)
        n_chunks = -(-(hi - lo) // chunk)
        step, starts, put_tables, put_carry, per_chip = self._layout(
            sig, lo, hi, ranges)

        with _compat.enable_x64():
            with span("sweep.open", profile):
                carry = self._init_carry(sig)
                sent = jax.tree_util.tree_leaves(carry)
                with span("sweep.upload", profile):
                    if self._tables_dev is None:
                        self._tables_dev = put_tables(self._tables_host)
                        sent += jax.tree_util.tree_leaves(self._tables_host)
                    carry = put_carry(carry)
                tables = self._tables_dev
                count(profile, "uploads", len(sent))
                # every device receives its carry and the tables
                count(profile, "upload_bytes",
                      ndev * sum(np.asarray(t).nbytes for t in sent))
            with span("sweep.dispatch", profile):
                with span("sweep.compile", profile):
                    carry = step(carry, tables, starts[0])
                for s in starts[1:]:
                    carry = step(carry, tables, s)
            leaves, treedef = jax.tree_util.tree_flatten(carry)
            with span("sweep.wait", profile):
                # every leaf is an output of the last step
                first = np.asarray(leaves[0])
            with span("sweep.close", profile):
                with span("sweep.pull", profile):
                    state = [first] + [np.asarray(x) for x in leaves[1:]]
                chips = [jax.tree_util.tree_unflatten(treedef, st)
                         for st in per_chip(state)]
                for key, n_add in (
                        ("chunks", n_chunks),
                        ("lanes", n_chunks * chunk),
                        # the mask's count is the carry's last entry
                        ("feasible",
                         sum(int(t[-1]) for t in chips) if self.mask_sig
                         else hi - lo),
                        ("uploads", len(starts)),       # each round's starts
                        ("upload_bytes", 8 * ndev * len(starts)),
                        ("pulls", len(state)),
                        ("pull_bytes", sum(x.nbytes for x in state)),
                        ("device_calls", len(starts))):
                    count(profile, key, n_add)
                per_step = _STEP_LOOKUPS.get(
                    (chunk, sig, _table_shapes(self._tables_host)), {})
                for key, n_add in per_step.items():
                    count(profile, key, n_add * len(starts) * ndev)
                if profile is not None:
                    profile["devices"] = sum(r1 > r0 for r0, r1 in ranges)
                with span("sweep.merge", profile):
                    self._merge(reducers, sig, chips)

    def _layout(self, sig: tuple, lo: int, hi: int, ranges: list):
        """How a fold of ``[lo, hi)`` split into ``ranges`` (one a device)
        runs: ``(step, starts, put_tables, put_carry, per_chip)``.

        ``step`` is called once a round with the next entry of ``starts``;
        ``put_tables`` and ``put_carry`` upload the host tables and the
        empty host carry; ``per_chip`` turns the pulled carry leaves into
        one list of leaves a device, in device order.  One device runs the
        one-chip step, a start a call.  Several run the mesh program: a
        round's starts are one per device (a used-up device steps at
        ``n``), the carry is stacked over the devices and the tables are
        replicated.
        """
        import jax

        chunk, n, ndev = self.chunk, self.n, len(ranges)
        if ndev == 1:
            return (_get_step(chunk, sig),
                    [np.int64(s) for s in range(lo, hi, chunk)],
                    jax.device_put, jax.device_put, lambda state: [state])
        on_chips, replicated = _mesh_shardings(self.devices)
        # the first range is the longest
        rounds = -(-(ranges[0][1] - ranges[0][0]) // chunk)
        starts = [np.asarray([r0 + i * chunk if r0 + i * chunk < r1 else n
                              for r0, r1 in ranges], dtype=np.int64)
                  for i in range(rounds)]

        def put_carry(carry):
            return jax.device_put(jax.tree_util.tree_map(
                lambda a: np.stack([a] * ndev), carry), on_chips)

        return (_get_mesh_step(chunk, sig, self.devices), starts,
                lambda tables: jax.device_put(tables, replicated), put_carry,
                lambda state: [[x[i] for x in state] for i in range(ndev)])

    def _merge(self, reducers, sig, chips) -> None:
        """Merge each chip's state (a list, in device order) into
        ``reducers``."""
        # Validate every capacity flag of every chip before touching any
        # reducer — a partial merge would double-count when the host
        # refolds the range.
        for state in chips:
            for spec, st in zip(sig, state):
                if spec[0] == "stats" and bool(st["ovf"]):
                    raise DeviceFoldOverflow(
                        f"exact-sum partial count exceeded {N_PARTIALS}")
                if spec[0] == "pareto" and bool(st["ovf"]):
                    raise DeviceFoldOverflow(
                        f"pareto front exceeded the device cap {spec[1]}")
        for state in chips:
            self._merge_one(reducers, sig, state)

    def _merge_one(self, reducers, sig, state) -> None:
        # A masked range that kept nothing leaves each reducer as the host
        # leaves it: untouched.
        for r, spec, st in zip(reducers, sig, state):
            if spec[0] == "stats":
                if not int(st["n"]):
                    continue
                r.merge(_stream.StatsReducer.from_state({
                    "n_points": int(st["n"]),
                    "memory_bound": int(st["mb"]),
                    "t_exe_min": float(st["vmin"]),
                    "t_exe_min_id": int(st["vid"]),
                    "t_exe_sum":
                        [float(p) for p in
                         st["te_parts"][:int(st["te_cnt"])]],
                    "total_bytes_sum":
                        [float(p) for p in
                         st["tb_parts"][:int(st["tb_cnt"])]],
                    "mean": float(st["mean"]),
                    "m2": float(st["m2"]),
                }))
            elif spec[0] == "topk":
                held = min(int(st["n_seen"]), spec[1])
                if not held:
                    continue
                tmp = _stream.TopKReducer(spec[1], spec[2])
                tmp.cols = {c: np.asarray(st["cols"][c][:held])
                            for c in COLUMNS}
                r.merge(tmp)
            else:
                cnt = int(st["count"])
                if not cnt:
                    continue
                tmp = _stream.ParetoReducer(spec[2])
                tmp.cols = {c: np.asarray(st["cols"][c][:cnt])
                            for c in COLUMNS}
                r.merge(tmp)


def try_outcome(plan: "_stream.SweepPlan", reducers,
                profile: dict | None = None,
                ) -> "tuple[_stream.StreamOutcome | None, str]":
    """Run the whole grid device-resident: ``(outcome, "")``, or
    ``(None, reason)`` when the host path must run instead.

    Folds ``[0, n)`` into ``reducers`` (which are only touched on success
    — a capacity overflow returns ``None`` with the reducers pristine) and
    returns the same :class:`stream.StreamOutcome` ``run_stream`` would.
    Device, compile and lowering errors propagate: only a declared
    ineligibility or a capacity overflow selects the host path.
    ``profile`` receives the spans of :meth:`DeviceSweep.fold_range`, and
    ``sweep.plan`` around the host tables' build.
    """
    try:
        with span("sweep.plan", profile):
            dev = DeviceSweep.build(plan)
    except DeviceIneligible as e:
        return None, str(e)
    reducers = tuple(reducers)
    if not dev.supports(reducers):
        return None, "custom reducer set"
    n = dev.n
    try:
        dev.fold_range(0, n, reducers, profile=profile)
    except DeviceFoldOverflow as e:
        return None, f"device fold overflow: {e}"
    return _stream.StreamOutcome(
        reducers=reducers, n_points=n,
        n_chunks=-(-n // plan.chunk_size), chunk_size=plan.chunk_size), ""
