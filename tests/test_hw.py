"""The pluggable hardware-spec layer: registry, serialization, calibration
fold-back, legacy-constant removal, backend equivalence on a
(Design x Hardware) grid, the sweep hardware axis, and the cache-key
regression."""
import dataclasses
import warnings

import numpy as np
import pytest

import repro
from repro import Design, Session, Space, hw
from repro.core import validate as V
from repro.core.lsu import LsuType
from repro.hw import ClockDomain, DramOrganization, Hardware, MemorySystem

ALL_TYPES = [LsuType.BC_ALIGNED, LsuType.BC_NON_ALIGNED,
             LsuType.BC_WRITE_ACK, LsuType.ATOMIC_PIPELINED]

PRESETS = ("stratix10_ddr4_1866", "stratix10_ddr4_2666", "tpu_v5e", "tpu_v4")


def _designs() -> list[Design]:
    """The shared (Design) half of the (Design x Hardware) grid."""
    return [Design.microbench(t, n_ga=g, simd=s, n_elems=1 << 14, delta=d)
            for t in ALL_TYPES for g in (1, 3) for s in (1, 4)
            for d in (1, 7)]


def _synthetic_report(factor: float = 1.7) -> V.ValidationReport:
    """A deterministic ValidationReport (no jax, no wall clock)."""

    def kv(name, measured, predicted):
        return V.KernelValidation(
            name=name, backend="cpu", interpret=True,
            measured_s=measured, predicted_s=predicted,
            bytes_moved=1e6, flops=0.0,
            err_pct=abs(predicted - measured) / measured * 100.0,
            memory_bound=True)

    measured_bw = 5e9
    return V.ValidationReport(
        results=[kv("membench_aligned", 1.0, 1.0),
                 kv("membench_strided", 1.0, 0.8),
                 kv("membench_gather", 2.0, 1.0)],
        failures=[], dram=V.calibrate_dram(measured_bw),
        measured_bw=measured_bw, calibration_factor=factor)


class TestRegistry:
    def test_presets_resolve(self):
        for name in PRESETS:
            spec = hw.get(name)
            assert isinstance(spec, Hardware) and spec.name == name
        assert set(PRESETS) <= set(hw.names())

    def test_unknown_name_lists_known(self):
        with pytest.raises(KeyError, match="tpu_v5e"):
            hw.get("nonexistent-board")

    def test_register_and_overwrite(self):
        custom = hw.get("tpu_v5e").with_name("test-custom") \
            .with_efficiencies(k_gather=0.5)
        try:
            assert hw.register(custom) is custom
            assert hw.get("test-custom").mem.k_gather == pytest.approx(0.5)
            with pytest.raises(ValueError, match="already registered"):
                hw.register(custom)
            hw.register(custom.with_host_factor(2.0), overwrite=True)
            assert hw.get("test-custom").host_factor == 2.0
        finally:
            hw.unregister("test-custom")

    def test_register_rejects_non_hardware(self):
        with pytest.raises(TypeError):
            hw.register(repro.DDR4_1866)


class TestSerialization:
    def test_round_trip_every_preset(self):
        for name in PRESETS:
            spec = hw.get(name)
            again = Hardware.from_json(spec.to_json())
            assert again == spec
            assert again.to_json() == spec.to_json()

    def test_round_trip_calibrated(self):
        spec = Hardware.from_calibration(_synthetic_report())
        assert Hardware.from_json(spec.to_json()) == spec

    def test_future_schema_rejected(self):
        obj = hw.get("tpu_v4").to_dict()
        obj["schema"] = 999
        with pytest.raises(ValueError, match="newer"):
            Hardware.from_dict(obj)

    def test_unknown_fields_ignored(self):
        """A spec written by a slightly newer minor version still loads."""
        obj = hw.get("tpu_v4").to_dict()
        obj["mem"]["brand_new_field"] = 7
        assert Hardware.from_dict(obj).mem == hw.get("tpu_v4").mem


class TestBuilders:
    def test_with_helpers_are_pure(self):
        base = hw.get("stratix10_ddr4_1866")
        derived = base.with_name("x").with_host_factor(3.0) \
            .with_efficiencies(k_stream=0.5)
        assert (base.name, base.host_factor, base.mem.k_stream) == \
            ("stratix10_ddr4_1866", 1.0, 0.92)
        assert (derived.name, derived.host_factor, derived.mem.k_stream) == \
            ("x", 3.0, 0.5)
        with pytest.raises(dataclasses.FrozenInstanceError):
            base.name = "y"
        with pytest.raises(TypeError, match="unknown"):
            base.with_efficiencies(k_vmem=0.5)

    def test_from_parts_views_round_trip(self):
        spec = Hardware.from_parts("board", dram=repro.DDR4_2666,
                                   bsp=repro.STRATIX10_BSP)
        assert spec.dram_params() == repro.DDR4_2666
        assert spec.bsp_params() == repro.STRATIX10_BSP
        assert spec.mem.peak_bw == pytest.approx(repro.DDR4_2666.bw_mem)


class TestLegacyAliases:
    """The PR-4 alias shims completed their cycle and are gone as of 0.6:
    the old names raise AttributeError; the registry views (and the curated
    repro/repro.core re-exports built from them) are the replacement."""

    CASES = [
        ("repro.core.fpga", "DDR4_1866", "stratix10_ddr4_1866", "dram_params"),
        ("repro.core.fpga", "DDR4_2666", "stratix10_ddr4_2666", "dram_params"),
        ("repro.core.fpga", "DRAM_CONFIGS", "stratix10_ddr4_1866",
         "dram_params"),
        ("repro.core.fpga", "STRATIX10_BSP", "stratix10_ddr4_1866",
         "bsp_params"),
        ("repro.core.hbm", "TPU_V5E", "tpu_v5e", "tpu_params"),
    ]

    @pytest.mark.parametrize("mod,attr,preset,view", CASES)
    def test_alias_removed_and_registry_replaces(self, mod, attr, preset,
                                                 view):
        import importlib

        module = importlib.import_module(mod)
        with pytest.raises(AttributeError, match=attr):
            getattr(module, attr)
        # the documented replacement resolves
        assert getattr(hw.get(preset), view)() is not None

    def test_curated_surfaces_warning_free(self):
        """repro / repro.core / repro.hw re-exports never touch the shims."""
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            assert repro.DDR4_1866.name == "DDR4-1866"
            assert repro.TPU_V5E.hbm_bw == hw.get("tpu_v5e").mem.peak_bw
            from repro.core import DDR4_2666, DRAM_CONFIGS, STRATIX10_BSP
            assert DDR4_2666 in DRAM_CONFIGS.values()
            assert STRATIX10_BSP.burst_cnt == 4


class TestBackendEquivalence:
    """Acceptance: Session.with_hardware(hw.get(...)) estimates bit-identical
    across scalar / numpy-batch / jax-jit on a (Design x Hardware) grid."""

    @pytest.mark.parametrize("name", PRESETS)
    def test_scalar_vs_batch_bit_identical(self, name):
        designs = _designs()
        ref = Session(backend="numpy-batch").with_hardware(hw.get(name))
        got = Session(backend="scalar").with_hardware(hw.get(name))
        for r, g in zip(ref.estimate_many(designs), got.estimate_many(designs)):
            assert g.t_exe == r.t_exe
            assert g.t_ideal == r.t_ideal
            assert g.bound_ratio == r.bound_ratio
            assert g.memory_bound == r.memory_bound

    @pytest.mark.parametrize("name", PRESETS)
    def test_jax_jit_vs_batch_bit_identical(self, name):
        pytest.importorskip("jax")
        designs = _designs()
        ref = Session(backend="numpy-batch").with_hardware(hw.get(name))
        got = Session(backend="jax-jit").with_hardware(hw.get(name))
        for r, g in zip(ref.estimate_many(designs), got.estimate_many(designs)):
            assert g.t_exe == r.t_exe
            assert g.total_bytes == r.total_bytes

    def test_hardware_ordering_is_physical(self):
        """Faster memory systems predict faster streams."""
        d = Design.microbench(LsuType.BC_ALIGNED, n_ga=2, n_elems=1 << 16)
        t = {n: Session().with_hardware(hw.get(n)).estimate(d).t_exe
             for n in PRESETS}
        assert t["stratix10_ddr4_2666"] < t["stratix10_ddr4_1866"]
        assert t["tpu_v4"] < t["tpu_v5e"] < t["stratix10_ddr4_2666"]


class TestSessionIntegration:
    def test_with_hardware_sets_all_views(self):
        spec = hw.get("tpu_v4")
        sess = Session().with_hardware(spec)
        assert sess.hardware is spec
        assert sess.dram == spec.dram_params()
        assert sess.bsp == spec.bsp_params()
        assert sess.hw == spec.tpu_params()
        assert sess.calibration_factor == spec.host_factor
        # constructor path derives identically
        assert Session(hardware=spec) == sess

    def test_diverging_overrides_drop_stale_spec(self):
        """with_dram / with_calibration invalidate the hardware field — a
        stale spec must not leak into cache keys or simulator geometry."""
        sess = Session().with_hardware(hw.get("stratix10_ddr4_2666"))
        assert sess.with_dram(repro.DDR4_1866).hardware is None
        assert sess.with_calibration(_synthetic_report()).hardware is None

    def test_host_factor_scales_estimates(self):
        spec = hw.get("stratix10_ddr4_1866")
        d = Design.microbench(LsuType.BC_ALIGNED, n_ga=2, n_elems=1 << 14)
        base = Session().with_hardware(spec).estimate(d).t_exe
        doubled = Session().with_hardware(
            spec.with_host_factor(2.0)).estimate(d).t_exe
        assert doubled == pytest.approx(2.0 * base, rel=1e-12)

    def test_from_calibration_matches_with_calibration(self):
        """Acceptance: the persisted fold-back predicts what the session-local
        calibration predicts, to 1e-6."""
        rep = _synthetic_report(factor=1.7)
        spec = Hardware.from_calibration(rep)
        for t in ALL_TYPES:
            d = Design.microbench(t, n_ga=2, simd=4, n_elems=1 << 14)
            a = Session().with_calibration(rep).estimate(d)
            b = Session().with_hardware(spec).estimate(d)
            assert b.t_exe == pytest.approx(a.t_exe, rel=1e-6)
            assert b.memory_bound == a.memory_bound
        # ... and survives a disk round trip
        again = Hardware.from_json(spec.to_json())
        d = Design.microbench(LsuType.BC_ALIGNED, n_ga=2, n_elems=1 << 14)
        assert Session().with_hardware(again).estimate(d).t_exe == \
            pytest.approx(Session().with_calibration(rep).estimate(d).t_exe,
                          rel=1e-6)

    def test_from_calibration_folds_class_errors(self):
        spec = Hardware.from_calibration(_synthetic_report())
        assert spec.host_factor == pytest.approx(1.7)
        assert spec.mem.peak_bw == pytest.approx(5e9)
        assert spec.mem.k_stream == pytest.approx(0.92)        # anchor: 1.0
        assert spec.mem.k_strided == pytest.approx(0.92 * 0.8)
        assert spec.mem.k_gather == pytest.approx(0.92 * 0.5)

    def test_predict_and_traffic_accept_hardware(self):
        from repro.core.hbm import AccessClass, Traffic, traffic_time

        spec = hw.get("tpu_v5e")
        t = Traffic(AccessClass.GATHER, 1 << 20, row_bytes=256.0)
        assert traffic_time(t, spec) == traffic_time(t, spec.tpu_params())


class TestSweepHardwareAxis:
    def test_hardware_axis_overrides_and_reports(self):
        specs = [hw.get("stratix10_ddr4_1866"), hw.get("tpu_v5e")]
        res = Session().sweep(Space.grid(
            lsu_type=[LsuType.BC_ALIGNED, LsuType.BC_WRITE_ACK],
            n_ga=[1, 2], n_elems=[1 << 14], hardware=specs))
        assert res.n_points == 8
        rows = res.rows()
        assert {r["hardware"] for r in rows} == set(s.name for s in specs)
        # the effective dram column reflects the spec, not the default axis
        assert {r["dram"] for r in rows} == {"DDR4-1866", "HBM-v5e"}

    def test_hardware_axis_backend_equivalence(self):
        sp = Space.grid(
            lsu_type=ALL_TYPES, n_ga=[1, 2], simd=[1, 4],
            n_elems=[1 << 14],
            hardware=[hw.get(n) for n in PRESETS])
        ref = Session(backend="numpy-batch").sweep(sp)
        got = Session(backend="scalar").sweep(sp)
        assert ref.n_points == got.n_points == 4 * 2 * 2 * 4
        np.testing.assert_array_equal(got.t_exe, ref.t_exe)
        np.testing.assert_array_equal(np.asarray(got.memory_bound),
                                      np.asarray(ref.memory_bound))

    def test_hardware_axis_applies_host_factor(self):
        base = hw.get("stratix10_ddr4_1866")
        res = Session().sweep(Space.grid(
            n_ga=[1, 2], n_elems=[1 << 14],
            hardware=[base, base.with_host_factor(2.0).with_name("x2")]))
        t = np.asarray(res.t_exe).reshape(2, 2)     # [n_ga, hardware]
        np.testing.assert_allclose(t[:, 1], 2.0 * t[:, 0], rtol=1e-12)

    def test_session_calibration_not_applied_to_overridden_points(self):
        """A calibrated session must not re-scale points whose hardware-axis
        spec fully overrides the session hardware (double scaling)."""
        spec = hw.get("stratix10_ddr4_2666")
        sp = Space.grid(n_ga=[1, 2], n_elems=[1 << 14], hardware=[spec])
        plain = Session().sweep(sp)
        calibrated = dataclasses.replace(
            Session(), calibration_factor=2.0).sweep(sp)
        np.testing.assert_array_equal(calibrated.t_exe, plain.t_exe)
        # ...while points on the session's own hardware still scale
        own = Space.grid(n_ga=[1, 2], n_elems=[1 << 14])
        a = Session().sweep(own)
        b = dataclasses.replace(Session(), calibration_factor=2.0).sweep(own)
        np.testing.assert_allclose(b.t_exe, 2.0 * np.asarray(a.t_exe),
                                   rtol=1e-12)

    def test_random_space_accepts_hardware(self):
        res = Session().sweep(Space.random(
            32, seed=5, n_ga=(1, 4), n_elems=(1 << 12, 1 << 14),
            hardware=[hw.get(n) for n in PRESETS]))
        assert res.n_points == 32
        assert np.all(np.asarray(res.t_exe) > 0)

    def test_vectorized_apply_hardware_axis_matches_reference_loop(self):
        """The factorize + table-gather rewrite of `_apply_hardware_axis`
        keeps the old per-point loop's semantics exactly: same view per
        unique spec (dedup), same host-factor scale, None rows untouched."""
        from repro.core.sweep import _apply_hardware_axis

        specs = [None, hw.get("stratix10_ddr4_1866"),
                 hw.get("tpu_v5e").with_host_factor(1.7)]
        rng = np.random.default_rng(13)
        n = 64
        col = np.empty(n, dtype=object)
        col[:] = [specs[i] for i in rng.integers(0, len(specs), n)]
        base_d, base_b = (hw.get("stratix10_ddr4_2666").dram_params(),
                          hw.get("stratix10_ddr4_2666").bsp_params())
        dram = np.empty(n, dtype=object)
        dram[:] = [base_d] * n
        bsp = np.empty(n, dtype=object)
        bsp[:] = [base_b] * n
        points = {"hardware": col, "dram": dram, "bsp": bsp}

        got_points, got_scale = _apply_hardware_axis(dict(points), n)

        # reference: the pre-vectorization per-point loop
        views = {}
        ref_d, ref_b, ref_s = dram.copy(), bsp.copy(), np.ones(n)
        for i, h in enumerate(col):
            if h is None:
                continue
            v = views.get(id(h))
            if v is None:
                v = views[id(h)] = (h.dram_params(), h.bsp_params(),
                                    float(h.host_factor))
            ref_d[i], ref_b[i], ref_s[i] = v
        np.testing.assert_array_equal(got_scale, ref_s)
        assert all(d == r for d, r in zip(got_points["dram"], ref_d))
        assert all(b == r for b, r in zip(got_points["bsp"], ref_b))
        # dedup contract: one view object per unique spec
        ids = {id(d) for d, h in zip(got_points["dram"], col)
               if h is not None}
        assert len(ids) == len({id(h) for h in col if h is not None})

    def test_all_none_hardware_axis_is_identity(self):
        from repro.core.sweep import _apply_hardware_axis

        n = 8
        col = np.empty(n, dtype=object)
        pts = {"hardware": col}        # dram/bsp untouched when all None
        out, scale = _apply_hardware_axis(pts, n)
        assert out is pts and np.all(scale == 1.0)


class TestCacheKey:
    def test_candidate_key_includes_hardware(self):
        """Satellite regression: a calibrated or swapped memory system must
        change the on-disk analysis/ranking cache key."""
        pytest.importorskip("jax")
        from repro.core import autotune as AT

        @dataclasses.dataclass
        class Cfg:
            a: int = 1

        @dataclasses.dataclass
        class Shape:
            kind: str = "train"

        cand = AT.Candidate("c", {}, {})
        k_default = AT.candidate_key(Cfg(), Shape(), None, cand)
        k_v5e = AT.candidate_key(Cfg(), Shape(), None, cand, hw.get("tpu_v5e"))
        k_v4 = AT.candidate_key(Cfg(), Shape(), None, cand, hw.get("tpu_v4"))
        k_cal = AT.candidate_key(Cfg(), Shape(), None, cand,
                                 hw.get("tpu_v5e").with_host_factor(1.5))
        assert k_default == k_v5e          # None resolves to the default chip
        assert len({k_v5e, k_v4, k_cal}) == 3
        # legacy TpuParams objects key too
        k_tpu = AT.candidate_key(Cfg(), Shape(), None, cand,
                                 hw.get("tpu_v4").tpu_params())
        assert k_tpu != k_v5e


class TestPytree:
    def test_spec_is_a_pytree(self):
        jax = pytest.importorskip("jax")
        assert hw.enable_jax()
        spec = hw.get("tpu_v5e")
        leaves, treedef = jax.tree_util.tree_flatten(spec)
        assert all(isinstance(x, (int, float)) for x in leaves)
        assert jax.tree_util.tree_unflatten(treedef, leaves) == spec

    def test_spec_threads_through_jit(self):
        jax = pytest.importorskip("jax")
        hw.enable_jax()
        spec = hw.get("tpu_v4")

        @jax.jit
        def stream_time(h, nbytes):
            return nbytes / (h.mem.peak_bw * h.mem.k_stream) * h.host_factor

        got = float(stream_time(spec, 1e9))
        assert got == pytest.approx(1e9 / (1228e9 * 0.92), rel=1e-6)


class TestDeviceKindPresets:
    @pytest.mark.parametrize("kind,preset", [("TPU v5 lite", "tpu_v5e"),
                                             ("TPU v4", "tpu_v4")])
    def test_known_kinds(self, kind, preset):
        assert hw.preset_for_device_kind(kind) == preset
        assert hw.get(hw.preset_for_device_kind(kind)).name == preset

    def test_unknown_kind_is_an_error(self):
        with pytest.raises(ValueError, match="no repro.hw preset"):
            hw.preset_for_device_kind("TPU v9000")

    def _on_fake_tpu(self, monkeypatch, kind):
        import jax

        seen = {}

        def fake_validate(cases, **kw):
            seen.update(kw)
            return V.ValidationReport([], [], kw["base"], float("nan"))

        class _Dev:
            device_kind = kind

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
        monkeypatch.setattr(V, "_validate", fake_validate)
        return seen

    def test_validate_on_tpu_predicts_with_the_chip_preset(self,
                                                           monkeypatch):
        seen = self._on_fake_tpu(monkeypatch, "TPU v5 lite")
        Session().validate([])
        assert seen["base"] == hw.get("tpu_v5e").dram_params()
        # an explicit hardware spec still wins
        Session().with_hardware(hw.get("tpu_v4")).validate([])
        assert seen["base"] == hw.get("tpu_v4").dram_params()

    def test_validate_on_unknown_chip_raises(self, monkeypatch):
        self._on_fake_tpu(monkeypatch, "TPU v9000")
        with pytest.raises(ValueError, match="no repro.hw preset"):
            Session().validate([])
