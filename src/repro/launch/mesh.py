"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first jax
initialization, while smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh

from repro.compat import make_mesh as _make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(*, model_parallel: int | None = None) -> Mesh:
    """Small mesh over whatever local devices exist (tests / examples)."""
    n = len(jax.devices())
    model = model_parallel or (2 if n % 2 == 0 and n > 1 else 1)
    data = n // model
    return _make_mesh((data, model), ("data", "model"))
