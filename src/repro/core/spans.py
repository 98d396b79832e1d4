"""Program spans and counters on the profiler's clock.

``span(name, prof)`` opens ``jax.profiler.TraceAnnotation("repro." +
name)``, so a profiler trace holds each stage of the program on the same
clock as the device's work.  When ``prof`` is a dict, the span also adds
its host seconds to ``prof[<last part of name> + "_s"]``; that dict is what
``Session.sweep(profile=True)`` returns as ``report.profile``.
``count(prof, key, n)`` adds to a counter of the same dict.  Neither
synchronizes with the device: a span measures host time as the host
experiences it, and device time is the trace's.

The annotation is opened only once jax is imported (a trace cannot be
recorded before), so a numpy-only process never imports jax for a span.
"""
from __future__ import annotations

import sys
import threading
from time import perf_counter

# Profiles are filled from the numpy backend's chunk threads too.
_LOCK = threading.Lock()


class span:
    """Context manager for one stage; see the module docstring."""

    __slots__ = ("_prof", "_key", "_ann", "_t0")

    def __init__(self, name: str, prof: dict | None = None, **args):
        self._prof = prof
        self._key = name.rpartition(".")[2] + "_s"
        jax_profiler = sys.modules.get("jax.profiler")
        self._ann = (None if jax_profiler is None else
                     jax_profiler.TraceAnnotation("repro." + name, **args))

    def __enter__(self) -> "span":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._prof is not None:
            dt = perf_counter() - self._t0
            with _LOCK:
                self._prof[self._key] = self._prof.get(self._key, 0.0) + dt
        if self._ann is not None:
            self._ann.__exit__(*exc)

    def annotate(self, **args) -> None:
        """Add ``args`` to the trace event (known only once the span ran)."""
        if self._ann is not None:
            self._ann.set_metadata(**args)


def count(prof: dict | None, key: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``prof[key]`` (nothing when ``prof`` is
    None)."""
    if prof is not None:
        with _LOCK:
            prof[key] = prof.get(key, 0) + n
