"""The jax helpers every layer of this repo shares.

One jax release is supported (the one ``pyproject.toml`` pins as its
floor); if a later release renames something, the fix goes here rather
than into a kernel or model file:

* ``enable_x64()`` — the scope every float64/int64 jax computation of the
  estimator runs in (jit-compiled scoring, the device-fused sweep step,
  the optimizer's descent).
* ``make_mesh(shape, axes)`` — ``jax.make_mesh`` with explicit
  ``AxisType``s.
* ``default_interpret(flag)`` — one place deciding when Pallas kernels run
  in interpret mode (everywhere except a real TPU backend).
* ``data_sharding(n)`` — a 1-D leading-axis ``NamedSharding``; the
  streaming sweep engine shards each fixed-shape chunk batch with it.
* ``chip_mesh(devices)`` and ``shard_map(f, mesh, in_specs, out_specs)`` —
  a one-axis mesh over given devices and ``jax.shard_map`` over it; the
  fused sweep step runs one per-chip body on every chip of a host with it.
* ``enable_compilation_cache()`` — jax's persistent compilation cache, in
  ``$JAX_COMPILATION_CACHE_DIR`` when set and otherwise in one fixed
  directory of the checkout, so fresh processes (benchmarks, distributed
  workers, ``chip_smoke.py``) skip recompiling the same executables.

The module imports jax but never touches device state at import time, so it
is safe to import before ``XLA_FLAGS`` tricks (dry-run, subprocess tests).
"""
from __future__ import annotations

import os
import pathlib

import jax
import numpy as np
from jax.sharding import AxisType

#: Where the persistent compilation cache lives when
#: ``$JAX_COMPILATION_CACHE_DIR`` is unset: a fixed, git-ignored directory
#: at the root of the checkout (the path is part of jax's cache key, so it
#: must not move between runs).
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_x64():
    """Context manager scoping 64-bit jax types (``jax.enable_x64``)."""
    return jax.enable_x64(True)


def default_interpret(interpret: bool | None = None, *,
                      backend: str | None = None) -> bool:
    """Resolve a kernel wrapper's ``interpret`` flag.

    Explicit True/False wins; ``None`` means "interpret everywhere except a
    real TPU backend" — the single policy all ops.py wrappers share.
    """
    if interpret is not None:
        return interpret
    return (backend or jax.default_backend()) != "tpu"


# ---------------------------------------------------------------------------
# Meshes
# ---------------------------------------------------------------------------

def make_mesh(shape, axes, *, explicit: bool = False):
    """``jax.make_mesh`` with every axis Auto (or Explicit)."""
    kind = AxisType.Explicit if explicit else AxisType.Auto
    return jax.make_mesh(shape, axes, axis_types=(kind,) * len(axes))


def data_sharding(n: int | None = None):
    """``NamedSharding`` splitting a leading axis across ``n`` local devices.

    Built on a 1-D ``("data",)`` mesh through :func:`make_mesh`.  This is
    the sharding the streaming sweep applies to each fixed-shape chunk
    batch (the leading axis is the LSU-group dimension, ``2 * chunk_size``
    entries).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    n = int(n if n is not None else jax.local_device_count())
    mesh = make_mesh((n,), ("data",))
    return NamedSharding(mesh, PartitionSpec("data"))


#: The axis of :func:`chip_mesh`.
CHIP_AXIS = "chip"


def chip_mesh(devices):
    """A one-axis :data:`CHIP_AXIS` mesh over ``devices``, in their order."""
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), (CHIP_AXIS,),
                axis_types=(AxisType.Auto,))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` of ``f`` over ``mesh``, each device running ``f``
    on its own blocks.  Varying-manual-axes checking is off: the bodies
    mapped here carry per-device state through ``lax`` loops whose initial
    values are replicated constants, which that check refuses."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

_CACHE_DIR: str | None = None


def enable_compilation_cache() -> str:
    """Turn on jax's persistent (on-disk) compilation cache. Idempotent.

    The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
    :data:`DEFAULT_CACHE_DIR` otherwise.  The min-compile-time /
    min-entry-size thresholds are lowered so even fast compiles (the
    per-chunk-size streaming step) are cached.  Returns the directory.
    """
    global _CACHE_DIR
    if _CACHE_DIR is None:
        path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or str(DEFAULT_CACHE_DIR))
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _CACHE_DIR = path
    return _CACHE_DIR
