"""``repro.hw`` — the pluggable hardware-spec layer.

One serializable description of a memory system (:class:`Hardware`,
composing :class:`MemorySystem` + :class:`DramOrganization` +
:class:`ClockDomain`) behind a named registry:

    >>> from repro import hw
    >>> board = hw.get("stratix10_ddr4_1866")       # preset lookup
    >>> sess = repro.Session().with_hardware(board) # evaluate against it
    >>> hw.register(board.with_efficiencies(k_gather=0.5).with_name("mine"))
    >>> spec = hw.Hardware.from_json(saved)         # persisted calibration

Presets: ``tpu_v5e``, ``tpu_v4``, ``stratix10_ddr4_1866``,
``stratix10_ddr4_2666`` (see :mod:`repro.hw.presets`).  The pre-0.4
module constants (``repro.core.fpga.DDR4_1866``/``STRATIX10_BSP``,
``repro.core.hbm.TPU_V5E``) are removed; these entries are their only
home (the curated ``repro``/``repro.core`` re-exports are built from
them).
"""
from repro.hw.registry import get, names, register, unregister
from repro.hw.spec import (
    SCHEMA_VERSION,
    ClockDomain,
    DramOrganization,
    Hardware,
    MemorySystem,
    enable_jax,
)
from repro.hw import presets  # populates the registry
from repro.hw.presets import DEFAULT_BOARD, DEFAULT_CHIP, preset_for_device_kind


def resolve(spec: "Hardware | str | None") -> "Hardware | None":
    """One place axis values become Hardware: a spec passes through, a
    string looks up the registry, ``None`` stays ``None`` (meaning "the
    session's own hardware" wherever an axis admits a default)."""
    if spec is None or isinstance(spec, Hardware):
        return spec
    if isinstance(spec, str):
        return get(spec)
    raise TypeError(f"cannot resolve {spec!r} to a Hardware spec "
                    f"(want Hardware | preset name | None)")


__all__ = [
    "Hardware", "MemorySystem", "DramOrganization", "ClockDomain",
    "get", "register", "unregister", "names", "enable_jax", "resolve",
    "preset_for_device_kind", "DEFAULT_BOARD", "DEFAULT_CHIP", "SCHEMA_VERSION", "presets",
]
