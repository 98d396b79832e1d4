"""repro — analytical model of memory-bound HLS applications, and its
TPU/XLA transplant, behind one unified public API.

Describe a design once (:class:`Design`), evaluate it in a hardware +
calibration context (:class:`Session`), and every pipeline stage — estimate,
sweep, autotune, validate, roofline, predict — speaks the same
:class:`Estimate`/:class:`Report` result family:

    >>> import repro
    >>> sess = repro.Session()                        # DDR4-1866, numpy-batch
    >>> d = repro.Design.microbench(repro.LsuType.BC_ALIGNED, n_ga=4)
    >>> sess.estimate(d).t_exe
    >>> sess.sweep(repro.Space.grid(n_ga=[1, 2, 4], simd=[1, 16])).top_k(3)

Hardware is data, not constants: :mod:`repro.hw` holds one serializable
:class:`Hardware` spec family behind a named registry —
``sess.with_hardware(repro.hw.get("tpu_v4"))`` swaps the whole memory
system, and a sweep can fan out over a ``hardware`` axis.  The convenience
constants re-exported below (``DDR4_1866`` …) are built from those registry
entries; their former homes (``repro.core.fpga.DDR4_1866``,
``repro.core.hbm.TPU_V5E``) completed their one-release deprecation cycle
and are removed — use ``repro.hw.get(name)`` views instead.

Million-point design spaces stream instead of materializing:
``sess.sweep(repro.Space.grid(...).stream(), chunk_size=65536)`` enumerates
points lazily, evaluates fixed-shape chunks (on the ``jax-jit`` backend in
one fused device step, the grid split over every local device) and folds
them into online Pareto/top-k/stats reducers, so peak memory is
O(chunk + front + k) at any sweep size.

Streaming sweeps also distribute: ``sess.sweep(space,
executor="processes", workers=4)`` partitions the grid into chunk-aligned
id ranges, fans them out over a spawn-based process pool (each worker
rebuilds its evaluator from the picklable :class:`SweepPlan`), re-issues
stragglers, and merges reducer states into a report bit-equal to the
single-process run (:mod:`repro.core.distributed`).

Search does not have to enumerate at all: every :class:`Hardware` preset
carries a :class:`ResourceEnvelope` budget, ``sess.sweep(space,
constraints=[board.envelope])`` feasibility-masks each streaming chunk
*before* scoring (bit-equal to post-filtering the unconstrained sweep),
and ``sess.optimize(space, objective=("t_exe", "resource"))`` finds the
grid optimum / Pareto front by relaxing the integer axes and descending
the differentiable model — typically evaluating under 1% of the grid
(:mod:`repro.search`).

Whole models compose from the same per-kernel model:
``sess.estimate_model(cfg)`` walks a compiled train/decode step op by op
(trip-count aware), scores every op's DRAM traffic through Eqs. 1-10 in
one batched pass, and returns a :class:`ModelReport` whose phase totals
are exactly the sum of the per-op estimates; ``sess.sweep_model(...)``
makes model shape x sharding x hardware a streaming grid behind a
picklable :class:`ModelSweepPlan` (:mod:`repro.workload`).

Interactive advisor traffic goes through the serving layer:
``sess.serve()`` returns a :class:`Server` that micro-batches concurrent
``estimate`` calls from any number of threads into single batched scoring
passes (bit-equal to serial evaluation), memoizes results in a
content-hash LRU, and reports p50/p99 latency via ``stats()``.

Everything else (``repro.core.*``, ``repro.kernels.*``, ``repro.launch.*``)
is implementation; the pre-PR-3 module-level entry points
(``model.estimate``, ``sweep.sweep_grid``/``sweep_random``,
``predictor.predict``, ``autotune.autotune``, ``validate.validate``) have
completed their one-release deprecation cycle and are removed.

This module imports NumPy only; jax loads lazily, on first use of the
``jax-jit`` backend, ``Design.from_kernel`` or ``Session.validate``.
"""
from repro import hw
from repro.api import (
    BACKENDS,
    EXECUTORS,
    AutotuneReport,
    Design,
    Estimate,
    Report,
    RequestTimeout,
    RooflineReport,
    Server,
    ServerClosed,
    ServerOverloaded,
    Session,
    Space,
    SweepPlan,
    SweepReport,
    ValidateReport,
)
# Registry-backed convenience constants (the legacy parameter views of the
# repro.hw presets, built once in repro.core; reading them here does not
# warn).
from repro.core import (
    DDR4_1866,
    DDR4_2666,
    DRAM_CONFIGS,
    STRATIX10_BSP,
)
from repro.core.fpga import BspParams, DramParams
from repro.core.hbm import AccessClass, TpuParams
from repro.core.lsu import Lsu, LsuType, make_global_access
from repro.hw import ClockDomain, DramOrganization, Hardware, MemorySystem
# The constrained/gradient-based search layer (repro.search is lazy: these
# resolve through its PEP 562 __getattr__ after repro.api is fully loaded).
from repro.search import (
    Constraint,
    OptimizeReport,
    ResourceEnvelope,
    within,
)
# Whole-model estimation (Session.estimate_model / plan_model / sweep_model
# return these; repro.workload imports NumPy only — jax stays lazy).
from repro.workload import (
    ModelReport,
    ModelSweepPlan,
    ModelSweepReport,
    OpRecord,
    PhaseReport,
)

TPU_V5E = hw.get("tpu_v5e").tpu_params()

__version__ = "0.9.0"

__all__ = [
    # the unified API
    "Design", "Session", "Space", "Estimate", "Report",
    "SweepPlan", "SweepReport", "AutotuneReport", "ValidateReport",
    "RooflineReport", "BACKENDS", "EXECUTORS",
    # the serving layer
    "Server", "ServerClosed", "ServerOverloaded", "RequestTimeout",
    # constrained + gradient-based search
    "ResourceEnvelope", "Constraint", "within", "OptimizeReport",
    # whole-model estimation (repro.workload)
    "ModelReport", "PhaseReport", "OpRecord",
    "ModelSweepPlan", "ModelSweepReport",
    # the hardware-spec layer
    "hw", "Hardware", "MemorySystem", "DramOrganization", "ClockDomain",
    # design vocabulary (paper Tables I-III)
    "Lsu", "LsuType", "make_global_access",
    "DramParams", "BspParams", "DDR4_1866", "DDR4_2666", "DRAM_CONFIGS",
    "STRATIX10_BSP",
    # TPU transplant hardware
    "TpuParams", "TPU_V5E", "AccessClass",
    "__version__",
]
