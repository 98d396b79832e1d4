"""Device-resident fold (repro.core.device_stream): bit-equality with the
host merge/state_dict protocol under arbitrary chunk partitions across all
three backends, the feasibility mask inside the fused step,
capacity-overflow fallback, per-stage profile attribution, the fold split
over four devices, and the persistent compilation cache."""
import math
import pathlib
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro import Session, Space
from repro.core import DDR4_1866, DDR4_2666, LsuType
from repro.core import device_stream as dev
from repro.core.stream import (ParetoReducer, StatsReducer, TopKReducer,
                               default_reducers, make_range_folder)
from repro.search import AllOf, BoundConstraint, ResourceEnvelope, within

ALL_TYPES = [LsuType.BC_ALIGNED, LsuType.BC_NON_ALIGNED,
             LsuType.BC_WRITE_ACK, LsuType.ATOMIC_PIPELINED]

#: Same 864-point grid as tests/test_stream.py (the acceptance grid).
GRID = dict(
    lsu_type=ALL_TYPES,
    n_ga=[1, 2, 4],
    simd=[1, 4, 16],
    n_elems=[1 << 14, 1 << 16],
    delta=[1, 2, 7],
    include_write=[False, True],
    dram=[DDR4_1866, DDR4_2666],
)
N = 864

multi_device = pytest.mark.skipif(
    jax.local_device_count() > 1,
    reason="one-device folds; TestMultiChip splits folds over several")


def _plan(backend: str, chunk: int):
    return Session(backend=backend).plan(Space.grid(**GRID),
                                         chunk_size=chunk)


def _canon(reducers) -> list:
    """state_dicts normalized to the representation-invariant form.

    Shewchuk partial *lists* are not canonical — ``merge`` re-runs two-sum
    over them and may compact ``[a, b, c, T]`` into ``[a+b+c, T]`` while
    preserving the exact total — so the sums compare through ``math.fsum``
    (exact for non-overlapping partials).  The Pareto front's held order is
    ascending-id on the device path and front-algorithm order on the host,
    so front rows are sorted by id.  Everything else must match exactly.
    """
    out = []
    for r in reducers:
        st = r.state_dict()
        if isinstance(r, StatsReducer):
            st = dict(st, t_exe_sum=math.fsum(st["t_exe_sum"]),
                      total_bytes_sum=math.fsum(st["total_bytes_sum"]))
        elif isinstance(r, ParetoReducer) and st["cols"] is not None:
            order = np.argsort(np.asarray(st["cols"]["id"][1]))
            st = dict(st, cols={c: [d, [v[i] for i in order]]
                                for c, (d, v) in st["cols"].items()})
        out.append(st)
    return out


def _protocol_fold(backend: str, chunk: int, bounds: list[int]) -> list:
    """Fold each ``bounds`` range into fresh reducers, merge the states.

    This is exactly the distributed coordinator/worker protocol
    (repro.core.distributed): per-range states travel as ``state_dict()``
    and merge in range order, so every backend sees the identical merge
    tree and the results must agree bit-for-bit.
    """
    fold = make_range_folder(_plan(backend, chunk))
    base = default_reducers(10)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        fresh = tuple(r.fresh() for r in base)
        fold(lo, hi, fresh)
        for b, r in zip(base, fresh):
            b.merge(type(b).from_state(r.state_dict()))
    return _canon(base)


@pytest.fixture(scope="module")
def materialized():
    return Session().sweep(Space.grid(**GRID))


def _lookup_table(case: str) -> np.ndarray:
    """A step table of ``case``'s dtype: one bucket long, or longer."""
    rng = np.random.default_rng(16)
    if case == "bool":
        return rng.random(dev._TABLE_BUCKET) < 0.5
    if case == "float64":
        tiny = np.finfo(np.float64).tiny
        special = [0.0, -0.0, np.inf, -np.inf, 5e-324, -5e-324, tiny / 3,
                   tiny, np.finfo(np.float64).max, 1.0, -1.5, np.nan]
        return np.concatenate([special, rng.standard_normal(
            dev._TABLE_BUCKET - len(special)) * 1e300])
    size = dev._TABLE_BUCKET * (2 if case == "int64_long" else 1)
    return rng.integers(-2 ** 63, 2 ** 63 - 1, size=size, dtype=np.int64)


@pytest.mark.parametrize("code_dtype", [np.int32, np.int64])
@pytest.mark.parametrize("case", ["int64", "bool", "float64", "int64_long"])
def test_lookup_is_the_gather_bit_for_bit(case, code_dtype):
    """``_lookup`` reads every code of the table bit for bit as the gather
    does: by one-hot over one bucket, by the gather itself past it, and
    counts which it took as the step does while it is traced."""
    from repro import compat

    table = _lookup_table(case)
    m = len(table)
    codes = np.random.default_rng(7).permutation(
        np.concatenate([np.arange(m)] * 3)).astype(code_dtype)
    tally = {"lookups": 0, "lookup_gathers": 0}
    token = dev._TALLY.set(tally)
    try:
        with compat.enable_x64():
            got = np.asarray(jax.jit(dev._lookup)(table, codes))
    finally:
        dev._TALLY.reset(token)
    want = table[codes]
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
    gathered = m > dev._TABLE_BUCKET
    assert tally == {"lookups": int(not gathered),
                     "lookup_gathers": int(gathered)}


class TestDeviceFoldBitEquality:
    @multi_device
    @pytest.mark.parametrize("chunk", [37, 100, 864, 4096])
    def test_whole_grid_matches_host_fold(self, chunk):
        """Device fold of [0, n) == host fold, any chunk size (incl. a
        non-dividing chunk with a masked padded tail and one > n)."""
        plan = _plan("jax-jit", chunk)
        drv = dev.DeviceSweep.build(plan)
        assert drv is not None
        device = default_reducers(10)
        assert drv.supports(device)
        drv.fold_range(0, N, device)

        host = default_reducers(10)
        hplan = _plan("numpy-batch", chunk)
        hplan.run_range(0, N, host, eval_chunk=hplan.evaluator())
        assert _canon(device) == _canon(host)

    @multi_device
    def test_session_sweep_takes_device_path(self, materialized):
        """The standard jax-jit streaming sweep actually runs device-fused
        and still bit-matches the materialized report."""
        st = Session(backend="jax-jit").sweep(Space.grid(**GRID),
                                              chunk_size=100, profile=True)
        assert st.summary()["profile"]["path"] == "device-fused"
        np.testing.assert_array_equal(
            np.sort(np.asarray(st.point_ids)[st.pareto()]),
            np.asarray(materialized.pareto()))
        assert st.top_k(10) == materialized.top_k(10)
        assert st.stats["t_exe_min"] == float(np.min(materialized.t_exe))
        assert st.stats["t_exe_min_id"] == int(np.argmin(materialized.t_exe))


def _check_partition(bounds: list[int]) -> None:
    ref = _protocol_fold("numpy-batch", 100, bounds)
    for backend in ("jax-jit", "scalar"):
        assert _protocol_fold(backend, 100, bounds) == ref, \
            f"{backend} diverged from numpy-batch on partition {bounds}"


try:
    from hypothesis import given, settings
    from hypothesis import strategies as hyp_st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


class TestPartitionProperty:
    """Device folds == host folds through the merge/state_dict protocol
    under *arbitrary* chunk-aligned partitions of [0, n)."""

    if HAVE_HYPOTHESIS:
        @multi_device
        @settings(max_examples=8, deadline=None)
        @given(cuts=hyp_st.sets(
            hyp_st.sampled_from(list(range(100, N, 100))), max_size=8))
        def test_random_partitions(self, cuts):
            _check_partition([0, *sorted(cuts), N])
    else:
        @multi_device
        @pytest.mark.parametrize("seed", range(4))
        def test_random_partitions(self, seed):
            rng = random.Random(seed)
            interior = list(range(100, N, 100))
            cuts = sorted(rng.sample(interior,
                                     rng.randint(0, len(interior))))
            _check_partition([0, *cuts, N])

    @multi_device
    def test_degenerate_partitions(self):
        _check_partition([0, N])                    # single range
        _check_partition([0, *range(100, N, 100), N])   # every chunk alone
        # a multiply-add XLA:CPU contracted into an FMA once put the
        # merged m2 one ulp off on this partition
        _check_partition([0, 400, 600, 864])


ENV = ResourceEnvelope(lsu_ports=5, interconnect_bytes=256,
                       buffer_bytes=20000)
#: Only the first 216 ids (bc_aligned) have the type code 0.
NOT_ALIGNED = BoundConstraint("lsu_type_code", 1, ">=")

#: case -> (constraints, folded range, what the range keeps)
MASKED = {
    "everything_feasible": ((within(ENV),), (0, 100), "all"),
    "nothing_feasible": ((within(ResourceEnvelope(
        lsu_ports=1, interconnect_bytes=1, buffer_bytes=1)),),
        (0, N), "none"),
    "padded_final_chunk": ((BoundConstraint("simd", 4, "<="), NOT_ALIGNED),
                           (800, N), "some"),
    "all_of": ((AllOf((within(ENV), BoundConstraint("simd", 4, "<="),
                       NOT_ALIGNED)),), (0, N), "some"),
}


class TestMaskedFold:
    """Envelope and bound constraints are masked inside the fused step;
    the fold keeps exactly the host's points, and its reducers are
    bit-equal to the host's ``run_range`` (stats partials, min and its id,
    top-k rows, front rows, feasible count)."""

    @multi_device
    @pytest.mark.parametrize("case", list(MASKED))
    def test_masked_fold_matches_host(self, case):
        constraints, (lo, hi), keeps = MASKED[case]
        plan = Session(backend="jax-jit").plan(
            Space.grid(**GRID), chunk_size=100, constraints=constraints)
        drv = dev.DeviceSweep.build(plan)
        assert drv.mask_sig
        device, prof = default_reducers(10), {}
        drv.fold_range(lo, hi, device, profile=prof)
        assert prof["path"] == "device-fused"

        host = default_reducers(10)
        Session().plan(Space.grid(**GRID), chunk_size=100,
                       constraints=constraints).run_range(lo, hi, host)
        assert _canon(device) == _canon(host)
        kept = int(plan.feasible_mask(np.arange(lo, hi)).sum())
        assert prof["feasible"] == kept == host[2].n_points
        assert {"all": kept == hi - lo, "none": kept == 0,
                "some": 0 < kept < hi - lo}[keeps]


class TestOverflowFallback:
    @multi_device
    def test_fold_range_raises_and_leaves_reducers_untouched(
            self, monkeypatch):
        monkeypatch.setattr(dev, "FRONT_CAP", 2)
        drv = dev.DeviceSweep.build(_plan("jax-jit", 100))
        assert drv is not None and drv.front_cap == 2
        reducers = default_reducers(10)
        before = [r.state_dict() for r in reducers]
        with pytest.raises(dev.DeviceFoldOverflow):
            drv.fold_range(0, N, reducers)
        assert [r.state_dict() for r in reducers] == before

    @multi_device
    def test_session_sweep_falls_back_to_host(self, monkeypatch,
                                              materialized):
        assert len(materialized.pareto()) > 2   # cap 2 must overflow
        monkeypatch.setattr(dev, "FRONT_CAP", 2)
        st = Session(backend="jax-jit").sweep(Space.grid(**GRID),
                                              chunk_size=100, profile=True)
        prof = st.summary()["profile"]
        assert prof["path"] == "host-stream"
        assert prof["host_reason"].startswith("device fold overflow")
        np.testing.assert_array_equal(
            np.sort(np.asarray(st.point_ids)[st.pareto()]),
            np.asarray(materialized.pareto()))
        assert st.top_k(10) == materialized.top_k(10)


class TestEligibility:
    def test_non_jax_backend_is_ineligible(self):
        with pytest.raises(dev.DeviceIneligible, match="backend"):
            dev.DeviceSweep.build(_plan("numpy-batch", 100))

    @multi_device
    def test_constrained_plan_is_ineligible(self):
        plan = Session(backend="jax-jit").plan(
            Space.grid(**GRID), chunk_size=100,
            constraints=(lambda cols: np.asarray(cols["n_ga"]) > 1,))
        with pytest.raises(dev.DeviceIneligible, match="constrained"):
            dev.DeviceSweep.build(plan)

    @multi_device
    def test_categorical_bound_is_ineligible(self):
        plan = Session(backend="jax-jit").plan(
            Space.grid(**GRID), chunk_size=100,
            constraints=(BoundConstraint("dram", 1, "<="),))
        with pytest.raises(dev.DeviceIneligible,
                           match="constrained plan: a bound on column 'dram'"):
            dev.DeviceSweep.build(plan)

    @multi_device
    def test_custom_reducer_is_unsupported(self):
        class Spy(StatsReducer):
            pass

        drv = dev.DeviceSweep.build(_plan("jax-jit", 100))
        assert drv is not None
        assert drv.supports(default_reducers(10))
        assert not drv.supports((Spy(),))
        assert not drv.supports((TopKReducer(3, key="no_such_column"),))


class TestProfileAndCache:
    def test_host_stream_profile_stages(self):
        st = Session().sweep(Space.grid(**GRID), chunk_size=100,
                             profile=True)
        prof = st.summary()["profile"]
        assert prof["path"] == "host-stream"
        for key in ("enumerate_s", "score_s", "reduce_s", "total_s"):
            assert prof[key] >= 0.0

    @multi_device
    def test_device_profile_stages(self):
        st = Session(backend="jax-jit").sweep(Space.grid(**GRID),
                                              chunk_size=100, profile=True)
        prof = st.summary()["profile"]
        assert prof["path"] == "device-fused"
        for key in ("compile_s", "score_s", "transfer_s", "enumerate_s",
                    "reduce_s", "total_s"):
            assert prof[key] >= 0.0

    def test_compilation_cache_enable_is_idempotent(self):
        from repro import compat

        first = compat.enable_compilation_cache()
        assert compat.enable_compilation_cache() == first
        assert jax.config.jax_compilation_cache_dir == first

    @pytest.mark.parametrize("env", [None, "set"])
    def test_compilation_cache_directory(self, env, monkeypatch, tmp_path):
        """$JAX_COMPILATION_CACHE_DIR wins; otherwise one fixed directory
        at the root of the checkout, never the home directory."""
        from repro import compat

        seen = {}
        monkeypatch.setattr(compat, "_CACHE_DIR", None)
        monkeypatch.setattr(compat.jax.config, "update",
                            lambda k, v: seen.__setitem__(k, v))
        if env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            want = str(tmp_path)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(compat.DEFAULT_CACHE_DIR)
            assert compat.DEFAULT_CACHE_DIR.parent == \
                pathlib.Path(compat.__file__).resolve().parents[2]
        assert compat.enable_compilation_cache() == want
        assert seen["jax_compilation_cache_dir"] == want


#: Runs under four forced host devices (a fresh process: the device count
#: is fixed when jax starts) and prints one JSON line, keyed by case.
_MULTI_CHIP = r"""
import json, sys
import numpy as np
import jax
sys.path.insert(0, TESTS)
from test_device_stream import GRID, N, _canon
from repro import Session, Space
from repro.core import device_stream as dev
from repro.core.stream import default_reducers
from repro.hw import get as hw_get
from repro.search import within

assert jax.local_device_count() == 4
S10 = (within(hw_get("stratix10_ddr4_1866").envelope),)
#: 720 of its 864 points fit the Stratix 10 envelope
S10_GRID = dict(GRID, n_ga=[1, 16, 128])


def canon(reducers):
    return json.loads(json.dumps(_canon(reducers), default=str))


def plan(backend, chunk, cons=(), grid=GRID):
    return Session(backend=backend).plan(Space.grid(**grid),
                                         chunk_size=chunk, constraints=cons)


def by_ranges(fold, ranges):
    # each range into fresh reducers, merged in order: the process pool's
    # protocol (an empty range holds nothing to merge)
    base = default_reducers(10)
    for lo, hi in (r for r in ranges if r[1] > r[0]):
        fresh = tuple(r.fresh() for r in base)
        fold(lo, hi, fresh)
        for b, r in zip(base, fresh):
            b.merge(type(b).from_state(r.state_dict()))
    return canon(base)


def report(grid, cons=(), backend="jax-jit"):
    rep = Session(backend=backend).sweep(Space.grid(**grid), chunk_size=100,
                                         constraints=cons, profile=True)
    return {"path": rep.profile["path"],
            "devices": rep.profile.get("devices"),
            "host_reason": rep.profile.get("host_reason"),
            "front": np.sort(np.asarray(rep.point_ids)[rep.pareto()]
                             ).tolist(),
            "top_k": json.loads(json.dumps(rep.top_k(10), default=str)),
            "stats": rep.stats}


def case(chunk, lo, hi, cons=(), grid=GRID):
    four = dev.DeviceSweep.build(plan("jax-jit", chunk, cons, grid))
    host = plan("numpy-batch", chunk, cons, grid)
    ranges = four.split(lo, hi)
    red, prof = default_reducers(10), {}
    four.fold_range(lo, hi, red, profile=prof)
    whole = default_reducers(10)
    host.run_range(lo, hi, whole)
    return {
        "ranges": ranges, "profile": prof,
        "four": canon(red), "host_whole": canon(whole),
        "host_by_ranges": by_ranges(host.run_range, ranges),
        "kept": int(host.feasible_mask(np.arange(lo, hi)).sum()),
    }


out = {
    "unconstrained": case(100, 0, N),
    "within_s10": case(100, 0, N, S10, S10_GRID),
    "padded_final_chunk": case(200, 0, N),
    "fewer_chunks_than_chips": case(100, 600, N),
}
out["unconstrained"]["sweep"] = report(GRID)
out["unconstrained"]["host_sweep"] = report(GRID, backend="numpy-batch")
out["within_s10"]["sweep"] = report(S10_GRID, S10)
out["within_s10"]["host_sweep"] = report(S10_GRID, S10, "numpy-batch")

# an overflow: a front cap that exactly one chip's range outgrows
host = plan("numpy-batch", 100)
ranges = dev.DeviceSweep.build(plan("jax-jit", 100)).split(0, N)
fronts = []
for lo, hi in ranges:
    red = default_reducers(10)
    host.run_range(lo, hi, red)
    fronts.append(len(red[0].cols["id"]))
dev.FRONT_CAP = sorted(fronts)[-2]
seeded = default_reducers(10)
host.run_range(0, 100, seeded)
before = canon(seeded)
try:
    dev.DeviceSweep.build(plan("jax-jit", 100)).fold_range(0, N, seeded)
    raised = ""
except dev.DeviceFoldOverflow as e:
    raised = str(e)
out["overflow_on_one_chip"] = {
    "fronts": fronts, "cap": dev.FRONT_CAP, "raised": raised,
    "untouched": canon(seeded) == before,
    "sweep": report(GRID), "host_sweep": report(GRID, backend="numpy-batch")}
print(json.dumps(out))
"""


def _partition_invariant(state: list) -> list:
    """A canonical state without the Chan moments: ``mean`` and ``m2``
    depend on how the range was partitioned, in the last ulps."""
    return [{k: v for k, v in st.items() if k not in ("mean", "m2")}
            for st in state]


@pytest.fixture(scope="module")
def multi_chip():
    import json
    import os
    import subprocess
    import sys

    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(tests, "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", f"TESTS = {tests!r}\n" + _MULTI_CHIP],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestMultiChip:
    """Four forced host devices: one fold's range is split over the four,
    one program steps them in lockstep, and their carries merge in chip
    order.  The result is bit-equal to the host folding the same four
    ranges and merging them (the process pool's protocol); against the
    host's fold of the whole range, which the tests above hold bit-equal
    to one chip's, every state is bit-equal but the variance's Chan
    moments (mean, m2), which merging re-groups, as any partition
    does."""

    #: case -> (chunk, chip ranges, devices that held a range, rounds)
    SPLITS = {
        "unconstrained": (100, [[0, 300], [300, 500], [500, 700],
                                [700, N]], 4, 3),
        "within_s10": (100, [[0, 300], [300, 500], [500, 700], [700, N]],
                       4, 3),
        "padded_final_chunk": (200, [[0, 400], [400, 600], [600, 800],
                                     [800, N]], 4, 2),
        "fewer_chunks_than_chips": (100, [[600, 700], [700, 800], [800, N],
                                          [N, N]], 3, 1),
    }

    @pytest.mark.parametrize("case", list(SPLITS))
    def test_four_chip_fold_matches_one_chip_and_host(self, multi_chip,
                                                      case):
        res = multi_chip[case]
        chunk, ranges, devices, rounds = self.SPLITS[case]
        assert res["ranges"] == ranges
        prof = res["profile"]
        assert prof["path"] == "device-fused"
        assert prof["devices"] == devices
        assert prof["device_calls"] == rounds
        lo, hi = ranges[0][0], ranges[-1][1]
        assert prof["chunks"] == -(-(hi - lo) // chunk)
        assert prof["lanes"] == prof["chunks"] * chunk
        assert prof["feasible"] == res["kept"]
        assert prof["merge_s"] >= 0.0
        assert res["four"] == res["host_by_ranges"]
        assert (_partition_invariant(res["four"])
                == _partition_invariant(res["host_whole"]))
        (stats4,) = [st for st in res["four"] if "m2" in st]
        (stats1,) = [st for st in res["host_whole"] if "m2" in st]
        assert stats4["m2"] == pytest.approx(stats1["m2"], rel=1e-12)
        if case == "within_s10":
            assert 0 < res["kept"] < hi - lo

    @pytest.mark.parametrize("case", ["unconstrained", "within_s10"])
    def test_session_sweep_runs_fused_on_four(self, multi_chip, case):
        """``Session.sweep`` takes the fused step on all four chips and
        reports what the host stream reports (the variance to 1e-12)."""
        rep, host = (multi_chip[case]["sweep"],
                     multi_chip[case]["host_sweep"])
        assert rep["path"] == "device-fused" and rep["devices"] == 4
        assert rep["host_reason"] is None
        assert rep["front"] == host["front"]
        assert rep["top_k"] == host["top_k"]
        var = rep["stats"].pop("t_exe_var")
        assert var == pytest.approx(host["stats"].pop("t_exe_var"),
                                    rel=1e-12)
        assert rep["stats"] == host["stats"]

    def test_overflow_on_one_chip_leaves_reducers_untouched(self,
                                                            multi_chip):
        res = multi_chip["overflow_on_one_chip"]
        assert sum(f > res["cap"] for f in res["fronts"]) == 1
        assert res["raised"].startswith("pareto front exceeded")
        assert res["untouched"]
        rep, host = res["sweep"], res["host_sweep"]
        assert rep["path"] == "host-stream"
        assert rep["host_reason"].startswith("device fold overflow")
        assert rep["front"] == host["front"]
        assert rep["top_k"] == host["top_k"]
        assert rep["stats"] == host["stats"]
