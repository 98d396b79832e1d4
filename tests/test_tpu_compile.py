"""Compile the main path for a described TPU v5e chip; no chip is needed.

The TPU compiler refuses programs that XLA:CPU and Pallas interpret mode
accept: a float64 -> int64 bitcast in the fused sweep step, kernel blocks
off the (8, 128) tiling, primitives Mosaic cannot lower.  These tests compile
for one chip of a described ``v5e:2x2`` topology the fused sweep step (chunk
2**17, default reducers; unconstrained and masked to a board's envelope;
on one chip and as one program for all four),
the batched estimator core behind ``serve()`` and the host-stream path (on
one chip, and with its batch sharded over the four), and the seven
``validate()`` kernels at their measurement shapes.  Nothing runs, so
results and times are out of scope.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU compiler's library.
"""
import dataclasses
import re

import numpy as np
import pytest

KERNELS = ("membench_aligned", "membench_strided", "membench_gather",
           "flash_attention", "decode_attention", "rglru_scan", "mlstm_chunk")


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off: what
    is compiled for a described chip cannot be read back."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """A leading-axis sharding over the four chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    return NamedSharding(Mesh(np.asarray(topo.devices), ("data",)),
                         PartitionSpec("data"))


def _specs(tree, sharding):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), tree)


#: sha256 of the one-chip fused step's lowered text at chunk 2**17 on the
#: stream_10m grid, since its table reads became one-hot lookups: a change
#: to this program changes what the one-chip cells run.
ONE_CHIP_STEP_SHA256 = {
    False: "edd0a82f86341131ef7073852de5954fa9ed82cf334fea225aed649e318b2e4c",
    True: "e2d31ed72f1e50116566b0202329a080832edbb91808cd50b9e7bc4de9248730",
}


def _fused_sweep(constrained: bool):
    """The stream_10m grid's device driver at chunk 2**17 and its reducer
    signature, unconstrained or within a board's envelope."""
    from benchmarks.sweep_bench import STREAM_GRIDS
    from repro import Session, Space
    from repro.core import device_stream as dev
    from repro.core import stream as st
    from repro.hw import get as hw_get
    from repro.search import within

    constraints = ((within(hw_get("stratix10_ddr4_1866").envelope),)
                   if constrained else ())
    plan = Session(backend="jax-jit").plan(
        Space.grid(**STREAM_GRIDS["10m"]), chunk_size=1 << 17,
        constraints=constraints)
    sweep = dev.DeviceSweep.build(plan)
    assert bool(sweep.mask_sig) == constrained
    return sweep, sweep._sig(st.default_reducers())


def _lower_one_chip(constrained, one_chip):
    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.core import device_stream as dev

    sweep, sig = _fused_sweep(constrained)
    step = dev._get_step(sweep.chunk, sig)
    with compat.enable_x64():
        return step.lower(
            _specs(sweep._init_carry(sig), one_chip),
            _specs(sweep._tables_host, one_chip),
            jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip))


@pytest.fixture(scope="module")
def one_chip_step_text(one_chip):
    """``constrained -> compiled text`` of the one-chip step, each compiled
    once for the tests that read it."""
    texts: dict = {}

    def text(constrained: bool) -> str:
        if constrained not in texts:
            texts[constrained] = _lower_one_chip(
                constrained, one_chip).compile().as_text()
        return texts[constrained]

    return text


@pytest.mark.parametrize("constrained", [False, True],
                         ids=["unconstrained", "within_envelope"])
def test_fused_sweep_step_compiles(constrained, one_chip_step_text):
    """The device-fused step at the stream_10m grid's chunk and reducers,
    unconstrained and with the feasibility mask of a board's envelope."""
    assert one_chip_step_text(constrained)


@pytest.mark.parametrize("constrained", [False, True],
                         ids=["unconstrained", "within_envelope"])
def test_one_chip_step_is_unchanged(constrained, one_chip):
    """The one-chip step lowers to the pinned program: the one shared with
    the mesh step since it learned to run on several chips, with its table
    reads as one-hot lookups."""
    import hashlib

    text = _lower_one_chip(constrained, one_chip).as_text()
    assert (hashlib.sha256(text.encode()).hexdigest()
            == ONE_CHIP_STEP_SHA256[constrained])


#: A per-lane gather of the compiled step outside its reducer folds: a
#: custom fusion with a 2**17-lane result, from a gather in the ``decode``,
#: ``mask`` or ``score`` scope.
LANE_GATHER = re.compile(r'= \w+\[131072\]\S* fusion\(.*kind=kCustom.*'
                         r'op_name="[^"]*/(decode|mask|score)/[^"]*gather')


@pytest.mark.parametrize("constrained", [False, True],
                         ids=["unconstrained", "within_envelope"])
def test_one_chip_step_reads_tables_without_lane_gathers(
        constrained, one_chip_step_text):
    """The step reads its axis and hardware tables by one-hot lookups: the
    chip's compiler leaves no 2**17-lane gather where they are read (the
    lookups' gathers numbered 35 unconstrained and 47 masked)."""
    text = one_chip_step_text(constrained)
    assert text.count("kind=kCustom") > 0
    assert [ln for ln in text.splitlines() if LANE_GATHER.search(ln)] == []


@pytest.mark.parametrize("constrained", [False, True],
                         ids=["unconstrained", "within_envelope"])
def test_sharded_fused_sweep_step_compiles(constrained, four_chips):
    """The fused step as one program for the four chips of the described
    v5e:2x2: the carry stacked over the chips, the tables replicated, one
    start per chip."""
    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.core import device_stream as dev

    devices = tuple(four_chips.mesh.devices.flat)
    sweep, sig = _fused_sweep(constrained)
    step = dev._get_mesh_step(sweep.chunk, sig, devices)
    chips, replicated = dev._mesh_shardings(devices)
    with compat.enable_x64():
        carry = jax.tree_util.tree_map(
            lambda a: np.stack([a] * len(devices)), sweep._init_carry(sig))
        compiled = step.lower(
            _specs(carry, chips), _specs(sweep._tables_host, replicated),
            jax.ShapeDtypeStruct((len(devices),), jnp.int64,
                                 sharding=chips)).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("chips", ["one_chip", "four_chips"])
def test_estimator_core_compiles(chips, request):
    """The batched Eqs. 1-10 core at one streaming chunk's group count, on
    one chip and with the group axis sharded over four."""
    import jax

    from repro import compat
    from repro.core import LsuType
    from repro.core.lsu import Lsu
    from repro.core.model_batch import GroupBatch, _jax_estimator_fn
    from repro.hw import get as hw_get

    hw = hw_get("stratix10_ddr4_1866")
    small = GroupBatch.from_kernels(
        [[Lsu(LsuType.BC_ALIGNED, ls_width=64, ls_acc=1024, ls_bytes=64)]],
        hw.dram_params(), hw.bsp_params())
    sharding = request.getfixturevalue(chips)
    groups = 2 << 17
    with compat.enable_x64():
        batch = dataclasses.replace(small, n_kernels=1 << 17, **{
            f.name: jax.ShapeDtypeStruct(
                (groups,), np.asarray(getattr(small, f.name)).dtype,
                sharding=sharding)
            for f in dataclasses.fields(GroupBatch) if f.name != "n_kernels"})
        compiled = _jax_estimator_fn().lower(batch).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("name", KERNELS)
def test_validate_kernel_compiles(name, one_chip, monkeypatch):
    """Each validate() kernel at small=False shapes, compiled for the chip
    (not interpreted) into a Mosaic custom call."""
    from repro import compat
    from repro.core.validate import default_cases

    # Here the default backend is the CPU, where kernels interpret; steer
    # them to the chip's compiler instead.
    real = compat.default_interpret
    monkeypatch.setattr(
        compat, "default_interpret",
        lambda interpret=None, *, backend=None: real(interpret,
                                                     backend="tpu"))
    (case,) = [c for c in default_cases(small=False) if c.name == name]
    fn, args = case.build()
    compiled = fn.lower(*_specs(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
