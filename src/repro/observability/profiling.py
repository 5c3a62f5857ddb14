"""Host-side profiler annotations.

:func:`annotate` opens a ``jax.profiler.TraceAnnotation`` around a phase of
the serving loop (admission, dispatch, readback): a host span on the
profiler's clock, beside the device ops, so an idle gap of the device can be
put down to what the host was doing.  About 1 us per enter / exit, nothing
device-side; it adds no ``named_scope``.  Names inside jitted code are
``jax.named_scope``s, written where the code is (DESIGN.md §9).
"""
from __future__ import annotations

import contextlib

import jax

__all__ = ["annotate"]


def annotate(name: str):
    """Host-side profiler annotation context (no-op without an active
    trace; never raises if the profiler backend is unavailable)."""
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:  # pragma: no cover - profiler backend missing
        return contextlib.nullcontext()
