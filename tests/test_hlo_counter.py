"""Trip-count-aware HLO analyzer: validated against XLA's cost_analysis on
scan-free modules and against unrolled ground truth on scan modules."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import hlo as HLO
from repro.core import hlo_counter as HC


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


class TestAgainstXla:
    def test_scan_free_flops_and_bytes(self):
        def f(x, w1, w2):
            return jnp.tanh(x @ w1) @ w2

        specs = [jax.ShapeDtypeStruct(s, jnp.float32)
                 for s in [(64, 256), (256, 512), (512, 128)]]
        c = _compile(f, *specs)
        xla = HLO.cost_analysis_stats(c)
        mine = HC.analyze(c.as_text(), fused=False)
        assert mine.flops == pytest.approx(xla["flops"], rel=0.05)
        assert mine.total_bytes == pytest.approx(xla["bytes_accessed"], rel=0.1)
        # fused (TPU) traffic model must be <= the unfused count and still
        # include the dot operands
        fm = HC.analyze(c.as_text())
        assert 0 < fm.total_bytes <= mine.total_bytes

    def test_scan_multiplies_by_trip_count(self):
        def body(x, w):
            return jnp.tanh(x @ w), None

        def scan(x, ws):
            return jax.lax.scan(body, x, ws)[0]

        def unrolled(x, ws):
            for i in range(12):
                x, _ = body(x, ws[i])
            return x

        x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
        ws = jax.ShapeDtypeStruct((12, 128, 128), jnp.float32)
        truth = HLO.cost_analysis_stats(_compile(unrolled, x, ws))
        mine = HC.analyze(_compile(scan, x, ws).as_text(), fused=False)
        assert mine.flops == pytest.approx(truth["flops"], rel=0.05)
        assert mine.total_bytes == pytest.approx(truth["bytes_accessed"],
                                                 rel=0.15)

    def test_nested_scan(self):
        def inner(c, x):
            return c * x, None

        def outer(c, xs):
            def step(c, x):
                c2, _ = jax.lax.scan(inner, c, x)
                return c2, None
            return jax.lax.scan(step, c, xs)[0]

        c0 = jax.ShapeDtypeStruct((64,), jnp.float32)
        xs = jax.ShapeDtypeStruct((5, 7, 64), jnp.float32)
        mine = HC.analyze(_compile(outer, c0, xs).as_text())
        # 5*7 = 35 multiplies of 64 elements
        assert mine.flops == pytest.approx(35 * 64, rel=0.3)


class TestClassification:
    def test_gather_classified(self):
        def f(emb, idx):
            return emb[idx].sum()

        emb = jax.ShapeDtypeStruct((1024, 64), jnp.float32)
        idx = jax.ShapeDtypeStruct((128,), jnp.int32)
        mine = HC.analyze(_compile(f, emb, idx).as_text())
        assert mine.bytes_by_class.get("gather", 0) > 0

    def test_sort_classified_strided(self):
        def f(x):
            return jnp.sort(x)

        x = jax.ShapeDtypeStruct((4096,), jnp.float32)
        mine = HC.analyze(_compile(f, x).as_text())
        assert mine.bytes_by_class.get("strided", 0) > 0


class TestCollectives:
    def _mesh(self):
        from repro.compat import make_mesh
        return make_mesh((len(jax.devices()),), ("d",))

    def test_psum_collective_counted(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = self._mesh()
        n = len(jax.devices())
        if n < 2:
            pytest.skip("needs >1 device")

    def test_group_size_parsing(self):
        line = ("%ar = f32[256]{0} all-reduce(%x), channel_id=1, "
                "replica_groups=[2,4]<=[8], to_apply=%sum")
        ops = HLO.parse_collectives(line)
        assert len(ops) == 1 and ops[0].group_size == 4
        line2 = ("%ag = f32[256]{0} all-gather(%x), channel_id=1, "
                 "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}")
        ops2 = HLO.parse_collectives(line2)
        assert ops2[0].group_size == 8
        assert ops2[0].operand_bytes == pytest.approx(1024 / 8)
        assert ops2[0].wire_bytes == pytest.approx(1024 * 7 / 8)

    def test_shape_bytes(self):
        assert HLO.shape_bytes("bf16[2,16,4096]{2,1,0}") == 2 * 16 * 4096 * 2
        assert HLO.shape_bytes("(f32[8]{0}, s32[4]{0})") == 32 + 16
        assert HLO.shape_bytes("pred[]") == 1


class TestDegenerateModules:
    """Satellite hardening: constant-folded / empty modules must analyze to
    an empty cost, never raise."""

    def test_no_entry_returns_empty_cost(self):
        cost = HC.analyze("not hlo at all")
        assert cost.total_bytes == 0 and cost.flops == 0
        assert any("no ENTRY" in w for w in cost.warnings)

    def test_entry_with_zero_materialized_instructions(self):
        # A fully constant-folded step: the entry body holds only a
        # constant and its ROOT tuple — no materialized traffic.
        text = "\n".join([
            "HloModule folded",
            "",
            "ENTRY %main () -> (f32[]) {",
            "  %c = f32[] constant(42)",
            "  ROOT %t = (f32[]) tuple(%c)",
            "}",
        ])
        cost = HC.analyze(text)
        assert cost.total_bytes == 0
        assert dict(cost.bytes_by_class) == {}

    def test_walker_empty_on_degenerate_module(self):
        from repro.workload import walk_module
        assert walk_module("not hlo at all") == []


class TestScaled:
    """Satellite fix: scaled(0.0) must drop class keys, not keep stale
    zero-valued entries (LSU groups are keyed off class *names*)."""

    def test_scaled_zero_drops_classes(self):
        c = HC.HloCost()
        c.bytes_by_class["gather"] = 512.0
        c.collective_by_kind["all-reduce"] = 64.0
        c.flops = 100.0
        z = c.scaled(0.0)
        assert dict(z.bytes_by_class) == {}
        assert dict(z.collective_by_kind) == {}
        assert z.flops == 0.0 and z.total_bytes == 0.0

    def test_add_after_zero_scaling(self):
        a = HC.HloCost()
        a.bytes_by_class["stream"] = 100.0
        b = a.scaled(0.0)
        b.add(a.scaled(2.0))
        assert dict(b.bytes_by_class) == {"stream": 200.0}
        # defaultdict behavior intact after the scaled(0) path
        assert b.bytes_by_class["gather"] == 0.0

    def test_scaled_nonzero_unchanged(self):
        a = HC.HloCost()
        a.bytes_by_class["strided"] = 10.0
        s = a.scaled(3.0)
        assert dict(s.bytes_by_class) == {"strided": 30.0}


_PALLAS_MODULE = """HloModule kernel

ENTRY %main (p0: f32[1024], p1: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %p1 = f32[1024]{0} parameter(1)
  ROOT %k = f32[1024]{0:T(1024)} custom-call(%p0, %p1), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{"body":"AAAA"%COST%,"needs_layout_passes":true}}
}
"""


class TestCompiledPallasKernel:
    """On the TPU a Pallas kernel is one opaque custom call; its
    pallas_call cost estimate carries the traffic it moves."""

    def test_cost_estimate_is_counted(self):
        cost = (',"cost_estimate":{"flops":"1024","transcendentals":"8",'
                '"bytes_accessed":"12288","remote_bytes_transferred":"0"}')
        hc = HC.analyze(_PALLAS_MODULE.replace("%COST%", cost))
        assert hc.bytes_by_class == {"stream": 12288.0}
        assert hc.flops == 1024.0 and hc.transcendentals == 8.0
        assert not hc.warnings

    def test_without_estimate_it_is_uncounted_and_says_so(self):
        hc = HC.analyze(_PALLAS_MODULE.replace("%COST%", ""))
        assert hc.total_bytes == 0.0
        assert any("uncounted" in w for w in hc.warnings)
