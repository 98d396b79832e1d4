"""Unit tests for the shared jax helpers in ``repro.compat``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat


class TestMakeMesh:
    def test_builds_mesh_on_installed_jax(self):
        mesh = compat.make_mesh((len(jax.devices()),), ("d",))
        assert tuple(mesh.axis_names) == ("d",)

    def test_new_jax_branch_passes_axis_types(self, monkeypatch):
        calls = {}

        class FakeAxisType:
            Auto = "auto"
            Explicit = "explicit"

        def fake_make_mesh(shape, axes, axis_types=None):
            calls["axis_types"] = axis_types
            return "mesh"

        monkeypatch.setattr(compat, "AxisType", FakeAxisType)
        monkeypatch.setattr(compat.jax, "make_mesh", fake_make_mesh)
        assert compat.make_mesh((2, 2), ("a", "b")) == "mesh"
        assert calls["axis_types"] == ("auto", "auto")
        compat.make_mesh((2,), ("a",), explicit=True)
        assert calls["axis_types"] == ("explicit",)


class TestDefaultInterpret:
    def test_explicit_flag_wins(self):
        assert compat.default_interpret(True, backend="tpu") is True
        assert compat.default_interpret(False, backend="cpu") is False

    def test_backend_policy(self):
        assert compat.default_interpret(backend="tpu") is False
        assert compat.default_interpret(backend="cpu") is True
        assert compat.default_interpret(backend="gpu") is True


class TestEnableX64:
    def test_scopes_64_bit_types(self):
        assert jnp.asarray(1.0).dtype == jnp.float32
        with compat.enable_x64():
            assert jnp.asarray(1.0).dtype == jnp.float64
            assert jnp.asarray(1).dtype == jnp.int64
        assert jnp.asarray(1.0).dtype == jnp.float32

    def test_jit_traces_under_the_scope(self):
        with compat.enable_x64():
            y = jax.jit(lambda x: x * 3)(np.float64(1) / 3)
        assert y.dtype == jnp.float64 and float(y) == (1 / 3) * 3


class TestOptimizationBarrier:
    """The model stack calls ``jax.lax.optimization_barrier`` directly: the
    installed jax differentiates it natively."""

    def test_identity_forward(self):
        x = jnp.arange(6.0).reshape(2, 3)
        np.testing.assert_array_equal(
            np.asarray(jax.lax.optimization_barrier(x)), np.asarray(x))

    def test_differentiates_on_this_jax(self):
        g = jax.grad(
            lambda x: (jax.lax.optimization_barrier(x) ** 2).sum())(
                jnp.ones(4))
        np.testing.assert_allclose(np.asarray(g), 2.0 * np.ones(4))


class TestAutotuneFailureHandling:
    def _patch(self, monkeypatch, errors):
        """Make analyze_candidate raise per-candidate errors (or succeed)."""
        from repro.core import autotune as AT

        def fake_analyze(cfg, shape, mesh, candidate, cache=None, hw=None):
            err = errors.get(candidate.name)
            if err is not None:
                raise err
            return {"flops": 1.0, "bytes_by_class": {"stream": 1e6},
                    "collective_wire_bytes": 0.0,
                    "collective_operand_bytes": 0.0,
                    "collective_by_kind": {}, "n_collectives": 0.0,
                    "memory_bytes": None, "xla_cost": {},
                    "compile_s": 0.0, "cached": False}

        monkeypatch.setattr(AT, "analyze_candidate", fake_analyze)
        return AT

    def test_all_same_error_reraises(self, monkeypatch):
        AT = self._patch(monkeypatch, {
            "a": NotImplementedError("no rule for optimization_barrier"),
            "b": NotImplementedError("no rule for optimization_barrier")})
        cands = [AT.Candidate("a", {}, {}), AT.Candidate("b", {}, {})]
        with pytest.raises(RuntimeError, match="not candidate-specific"):
            AT._autotune(None, None, None, cands, cache=False)

    def test_partial_failure_recorded(self, monkeypatch):
        AT = self._patch(monkeypatch,
                         {"bad": ValueError("candidate-specific boom")})
        cands = [AT.Candidate("ok", {}, {}), AT.Candidate("bad", {}, {})]
        res = AT._autotune(None, None, None, cands, cache=False)
        assert len(res) == 1 and res[0].candidate.name == "ok"
        assert len(res.failures) == 1
        assert res.failures[0].summary()["name"] == "bad"
        assert res.failures[0].error_type == "ValueError"

    def test_distinct_errors_return_empty_with_failures(self, monkeypatch):
        AT = self._patch(monkeypatch, {"a": ValueError("x"),
                                       "b": TypeError("y")})
        cands = [AT.Candidate("a", {}, {}), AT.Candidate("b", {}, {})]
        res = AT._autotune(None, None, None, cands, cache=False)
        assert list(res) == []
        assert {f.error_type for f in res.failures} == {"ValueError",
                                                        "TypeError"}
