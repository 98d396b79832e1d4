"""repro.search: resource envelopes, the constraint algebra, feasibility-
masked streaming (bit-equal to post-filtering), constrained random
sampling, and the gradient-based Session.optimize."""
import json

import numpy as np
import pytest

from repro import Session, Space
from repro.core import DDR4_1866, DDR4_2666, LsuType
from repro.core.stream import StatsReducer
from repro.search import (
    BoundConstraint,
    LambdaConstraint,
    ResourceEnvelope,
    usage_from_axes,
    usage_of_design,
    within,
)
from repro.search.constraints import (
    columns_from_lists,
    constraint_from_json,
    constraint_to_json,
    feasibility_mask,
    normalize_constraints,
)

ALL_TYPES = [LsuType.BC_ALIGNED, LsuType.BC_NON_ALIGNED,
             LsuType.BC_WRITE_ACK, LsuType.ATOMIC_PIPELINED]

GRID = dict(
    lsu_type=ALL_TYPES,
    n_ga=[1, 2, 4],
    simd=[1, 4, 16],
    n_elems=[1 << 14, 1 << 16],
    delta=[1, 2, 7],
    include_write=[False, True],
    dram=[DDR4_1866, DDR4_2666],
)

ENV = ResourceEnvelope(lsu_ports=6, interconnect_bytes=64)


# ---------------------------------------------------------------------------
# envelopes
# ---------------------------------------------------------------------------


def test_envelope_round_trip():
    env = ResourceEnvelope(lsu_ports=128, interconnect_bytes=4096,
                           buffer_bytes=30e6)
    again = ResourceEnvelope.from_json(env.to_json())
    assert again == env
    assert again.dram_channels is None
    assert env.caps() == {"lsu_ports": 128.0, "interconnect_bytes": 4096.0,
                          "buffer_bytes": 30e6}


def test_envelope_rejects_negative_and_newer_schema():
    with pytest.raises(ValueError, match="must be >= 0"):
        ResourceEnvelope(lsu_ports=-1)
    with pytest.raises(ValueError, match="newer"):
        ResourceEnvelope.from_dict({"schema": 99, "lsu_ports": 4})


def test_envelope_rides_on_hardware():
    from repro import hw

    board = hw.get("stratix10_ddr4_1866")
    assert board.envelope is not None
    again = hw.Hardware.from_json(board.to_json())
    assert again.envelope == board.envelope


def test_usage_of_design_matches_vectorized():
    from repro import Design
    from repro.core.stream import GridEnumerator

    for t in ALL_TYPES:
        for n_ga, simd, iw in [(1, 1, False), (4, 16, True), (2, 4, True)]:
            d = Design.microbench(t, n_ga=n_ga, simd=simd, n_elems=1 << 14,
                                  include_write=iw)
            scalar = usage_of_design(d)
            lists = Session().plan(Space.grid(
                lsu_type=[t], n_ga=[n_ga], simd=[simd], n_elems=[1 << 14],
                include_write=[iw])).lists
            enum = GridEnumerator({k: list(v) for k, v in lists.items()})
            cols = columns_from_lists(lists,
                                      enum.codes(np.zeros(1, np.int64)))
            for col in ("lsu_ports", "interconnect_bytes", "dram_channels",
                        "buffer_bytes"):
                assert scalar[col] == pytest.approx(float(cols[col][0])), \
                    (t, n_ga, simd, iw, col)


# ---------------------------------------------------------------------------
# feasibility-masked sweeps are bit-equal to post-filtering
# ---------------------------------------------------------------------------


def _post_filtered_reference(constraints):
    """Unconstrained materialized sweep, filtered after the fact."""
    rep = Session().sweep(Space.grid(**GRID))
    lists = Session().plan(Space.grid(**GRID)).lists
    n = rep.n_points
    from repro.core.stream import GridEnumerator

    enum = GridEnumerator({k: list(v) for k, v in lists.items()})
    ids = np.arange(n, dtype=np.int64)
    cols = columns_from_lists(lists, enum.codes(ids))
    mask = feasibility_mask(normalize_constraints(constraints), cols)
    return rep, mask


@pytest.mark.parametrize("backend", ["numpy-batch", "scalar", "jax-jit"])
def test_masked_sweep_bit_equal_to_post_filter(backend):
    if backend == "jax-jit":
        pytest.importorskip("jax")
    ref, mask = _post_filtered_reference([ENV])
    sess = Session(backend=backend) if backend != "numpy-batch" else Session()
    got = sess.sweep(Space.grid(**GRID), constraints=[ENV])
    assert got.n_candidates == ref.n_points
    assert got.n_points == int(mask.sum())
    ref_t = np.asarray(ref.estimate.t_exe)[mask]
    np.testing.assert_array_equal(np.asarray(got.estimate.t_exe), ref_t)
    np.testing.assert_array_equal(got.resource, ref.resource[mask])


def test_masked_streaming_matches_materialized_constrained():
    ref, mask = _post_filtered_reference([ENV])
    st = Session().sweep(Space.grid(**GRID), chunk_size=97,
                         constraints=[ENV])
    assert st.stats["n_points"] == int(mask.sum())
    ref_t = np.asarray(ref.estimate.t_exe)[mask]
    assert st.stats["t_exe_min"] == ref_t.min()
    # the exact-sum reducer folds per-chunk partial sums, so the total
    # agrees to float64 round-off (per-point values are bit-equal above)
    assert st.stats["t_exe_sum"] == pytest.approx(ref_t.sum(), rel=1e-12)
    assert st.summary()["n_candidates"] == ref.n_points


def test_masked_sweep_property_random_constraints():
    """Property: any bound constraint masks bit-equal to post-filtering.

    Uses hypothesis when installed; falls back to a seeded sample of the
    same strategy space otherwise.
    """
    lists = Session().plan(Space.grid(**GRID)).lists
    from repro.core.stream import GridEnumerator

    enum = GridEnumerator({k: list(v) for k, v in lists.items()})
    ids = np.arange(enum.n, dtype=np.int64)
    cols = columns_from_lists(lists, enum.codes(ids))
    ref = Session().sweep(Space.grid(**GRID))
    ref_t = np.asarray(ref.estimate.t_exe)

    def check(column, bound, chunk):
        c = BoundConstraint(column, bound)
        mask = feasibility_mask((c,), cols)
        got = Session().sweep(Space.grid(**GRID), chunk_size=chunk,
                              constraints=c)
        assert got.stats["n_points"] == int(mask.sum())
        if mask.any():
            assert got.stats["t_exe_min"] == ref_t[mask].min()

    columns = ("lsu_ports", "interconnect_bytes", "buffer_bytes",
               "n_ga", "simd")
    try:
        from hypothesis import given, settings
        from hypothesis import strategies as st

        @settings(max_examples=20, deadline=None)
        @given(column=st.sampled_from(columns),
               bound=st.floats(0, 5000, allow_nan=False),
               chunk=st.integers(1, 300))
        def prop(column, bound, chunk):
            check(column, bound, chunk)

        prop()
    except ImportError:
        rng = np.random.default_rng(7)
        for _ in range(12):
            check(columns[rng.integers(len(columns))],
                  float(rng.uniform(0, 5000)), int(rng.integers(1, 300)))


def test_lambda_constraint_and_conjunction():
    c = within(ENV) & LambdaConstraint(lambda cols: cols["n_ga"] >= 2)
    got = Session().sweep(Space.grid(**GRID), constraints=c)
    assert got.n_points > 0
    assert np.asarray(got.points["n_ga"], dtype=np.int64).min() >= 2
    # custom callables are explicitly not JSON-serializable
    with pytest.raises(TypeError):
        constraint_to_json(c)


def test_constraint_json_round_trip():
    c = within(ENV) & BoundConstraint("n_ga", 2, op=">=")
    again = constraint_from_json(json.loads(json.dumps(constraint_to_json(c))))
    lists = Session().plan(Space.grid(**GRID)).lists
    from repro.core.stream import GridEnumerator

    enum = GridEnumerator({k: list(v) for k, v in lists.items()})
    ids = np.arange(enum.n, dtype=np.int64)
    cols = columns_from_lists(lists, enum.codes(ids))
    np.testing.assert_array_equal(c.mask(cols), again.mask(cols))


def test_plan_json_round_trip_with_constraints():
    plan = Session().plan(Space.grid(**GRID), chunk_size=128,
                          constraints=[ENV])
    from repro.core.stream import SweepPlan

    again = SweepPlan.from_json(plan.to_json())
    assert again.constraints == plan.constraints
    ids = np.arange(plan.n, dtype=np.int64)
    np.testing.assert_array_equal(again.feasible_mask(ids),
                                  plan.feasible_mask(ids))


# ---------------------------------------------------------------------------
# empty feasible regions fail loudly
# ---------------------------------------------------------------------------

IMPOSSIBLE = ResourceEnvelope(lsu_ports=0)


def test_constrained_sweep_empty_region_errors_on_best():
    got = Session().sweep(Space.grid(**GRID), constraints=[IMPOSSIBLE])
    assert got.n_points == 0
    s = got.summary()
    assert s["n_feasible"] == 0 and s["n_candidates"] == 864
    with pytest.raises(ValueError, match="constraints eliminated every"):
        got.best()


def test_random_space_rejection_sampling():
    sp = Space.random(64, seed=3, **GRID)
    sess = Session()
    rep = sess.sweep(sp, constraints=[ENV])
    assert rep.n_points == 64            # rejection refills to n
    # every drawn point satisfies the envelope
    from repro.core import sweep as _sweep
    from repro.search.constraints import columns_from_parts

    cats = {a: _sweep._factorize(rep.points[a]) for a in _sweep._CATEGORICAL}
    gc = columns_from_parts({a: np.asarray(rep.points[a])
                             for a in _sweep._NUMERIC}, cats, 64)
    assert feasibility_mask(normalize_constraints([ENV]), gc).all()
    # deterministic under the same seed
    rep2 = Session().sweep(Space.random(64, seed=3, **GRID),
                           constraints=[ENV])
    np.testing.assert_array_equal(np.asarray(rep.estimate.t_exe),
                                  np.asarray(rep2.estimate.t_exe))


def test_random_space_empty_region_errors():
    with pytest.raises(ValueError, match="feasible region"):
        Session().sweep(Space.random(16, seed=0, **GRID),
                        constraints=[IMPOSSIBLE])


def test_optimize_empty_region_errors():
    with pytest.raises(ValueError, match="eliminated every|no feasible"):
        Session().optimize(GRID, constraints=[IMPOSSIBLE])


# ---------------------------------------------------------------------------
# the optimizer
# ---------------------------------------------------------------------------


def test_adamw_converges_on_quadratic():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.optim.adamw import (OptimizerConfig, adamw_init,
                                   adamw_update)

    cfg = OptimizerConfig(lr=0.2, warmup_steps=0, total_steps=200,
                          weight_decay=0.0, clip_norm=1e6)
    target = jnp.asarray([3.0, -2.0])
    params = {"x": jnp.zeros(2)}
    state = adamw_init(params, cfg)
    loss = lambda p: jnp.sum((p["x"] - target) ** 2)
    vg = jax.value_and_grad(loss)
    for _ in range(200):
        val, g = vg(params)
        params, state, _ = adamw_update(g, state, params, cfg)
    assert float(loss(params)) < 1e-3


def test_optimize_small_grid_is_exhaustive_and_exact():
    rep = Session().optimize(GRID)
    full = Session().sweep(Space.grid(**GRID))
    assert rep.n_grid_evals == full.n_points
    assert rep.best.t_exe == float(np.asarray(full.estimate.t_exe).min())
    assert rep.trajectory[0]["phase"] == "exhaustive"
    assert rep.summary()["best_id"] == rep.best_id


BIG = dict(
    lsu_type=ALL_TYPES,
    n_ga=[1, 2, 3, 4, 6, 8, 12, 16],
    simd=[1, 2, 4, 8, 16],
    n_elems=[1 << 10, 1 << 12, 1 << 14, 1 << 16],
    delta=[1, 2, 3, 4, 5, 6, 7, 8],
    elem_bytes=[4, 8],
    include_write=[False, True],
    val_constant=[False, True],
)   # 40960 points


def test_optimize_matches_full_grid_under_budget():
    pytest.importorskip("jax")
    sess = Session()
    rep = sess.optimize(BIG, max_evals=2000, seed=0)
    st = sess.sweep(BIG, chunk_size=8192,
                    reducers=(StatsReducer(),))
    assert rep.n_evals <= 2000
    assert rep.n_grid_evals < 0.05 * rep.n_total
    assert rep.best.t_exe == st.stats["t_exe_min"]
    phases = [t["phase"] for t in rep.trajectory]
    assert phases[0] == "screen" and "descend" in phases


def test_optimize_constrained_matches_constrained_grid():
    pytest.importorskip("jax")
    env = ResourceEnvelope(lsu_ports=4, interconnect_bytes=64)
    sess = Session()
    rep = sess.optimize(BIG, constraints=[env], max_evals=2000, seed=1)
    st = sess.sweep(BIG, chunk_size=8192, constraints=[env],
                    reducers=(StatsReducer(),))
    assert rep.best.t_exe == st.stats["t_exe_min"]
    # every point the optimizer ever scored was feasible
    usage = rep.best_config
    assert float(usage["n_ga"]) <= 4


def test_optimize_pareto_front_recall():
    pytest.importorskip("jax")
    sess = Session()
    rep = sess.optimize(BIG, objective=("t_exe", "resource"),
                        max_evals=3000, seed=0)
    full = sess.sweep(BIG, chunk_size=8192)
    fr = full.pareto()
    ref = {(float(np.asarray(full.estimate.t_exe)[i]),
            float(full.resource[i])) for i in fr}
    got = {(float(rep.front["t_exe"][i]), float(rep.front["resource"][i]))
           for i in range(rep.n_front)}
    assert len(ref & got) / len(ref) >= 0.95
    assert rep.evals_fraction < 0.1


def test_optimize_rejects_bad_objective():
    with pytest.raises(ValueError, match="unknown objective"):
        Session().optimize(GRID, objective="latency")
    with pytest.raises(ValueError, match="one column or a pair"):
        Session().optimize(GRID, objective=("t_exe", "resource", "t_ovh"))


@pytest.mark.parametrize("backend", ["numpy-batch", "jax-jit"])
def test_optimize_descent_phase_runs(backend):
    """The relaxed descent really runs on every backend: lanes descended
    for every step, its evaluations billed, its loss recorded."""
    rep = Session(backend=backend).optimize(BIG, max_evals=2000, seed=0)
    (d,) = [t for t in rep.trajectory if t["phase"] == "descend"]
    assert "skipped" not in d
    assert d["steps"] == 16 and d["lanes"] > 0
    assert rep.n_relaxed_evals == d["lanes"] * d["steps"]
    assert np.isfinite(d["loss_first"]) and np.isfinite(d["loss_last"])


def test_optimize_descent_failure_propagates(monkeypatch):
    """A failing descent is an error, never a silently skipped phase."""
    from repro.optim import adamw

    def boom(*args, **kwargs):
        raise RuntimeError("descent step failed")

    monkeypatch.setattr(adamw, "adamw_update", boom)
    with pytest.raises(RuntimeError, match="descent step failed"):
        Session().optimize(BIG, max_evals=2000, seed=0)
