"""Correct timing of jitted decode steps + recompile detection.

JAX dispatch is asynchronous: the time to *return* from a jitted call is the
host dispatch cost, not the step latency, and the first call after any
structural change pays tracing + XLA compilation.  Naive
``time.perf_counter`` around a call therefore mixes three different numbers.
:class:`StepTimer` separates them:

  * **dispatch** — wall time until the call returns (host-side enqueue);
  * **wall** — wall time until ``jax.block_until_ready`` on *every* output
    leaf (the number benchmarks must report; blocking on one output of a
    multi-output step under-measures);
  * **warmup vs steady state** — warmup iterations absorb compilation;
    compile events observed *during the timed trials* mean the function is
    retracing per call, which invalidates the measurement (and, in serving,
    violates the zero-recompile hot-swap guarantee).

Recompile detection rides on ``jax.monitoring``'s ``backend_compile``
duration events — the same signal the test suite's zero-recompile
assertions use — counted by one process-global listener
(:func:`compile_events`).  Serving engines read its deltas around each
batch to turn the DESIGN.md §4 "hot swaps never recompile" *test
assertion* into a *monitored invariant*: every compile observed outside an
expected window (first batch, cold swap) increments an
``unexpected``-labeled counter that should read 0 forever.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional

import numpy as np

__all__ = ["StepTimer", "StepStats", "compile_events"]

_compile_lock = threading.Lock()
_compile_events = 0
_listener_registered = False


def _on_event(name, *args, **kwargs) -> None:
    if "backend_compile" in name:
        global _compile_events
        with _compile_lock:
            _compile_events += 1


def _ensure_listener() -> None:
    """Register the process-global compile-event listener exactly once.

    jax.monitoring offers no unregister API, so ONE module-level listener
    feeding a counter is the only shape that composes with the test suite's
    own ad-hoc listeners (each of which also stays registered for the
    process lifetime).
    """
    global _listener_registered
    with _compile_lock:
        if _listener_registered:
            return
        _listener_registered = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_event)


def compile_events() -> int:
    """Backend compilations observed process-wide since the first probe."""
    _ensure_listener()
    with _compile_lock:
        return _compile_events


@dataclasses.dataclass
class StepStats:
    """Result of one :meth:`StepTimer.measure` run (times in seconds)."""

    name: str
    wall_s: np.ndarray  # (trials,) blocked wall time per trial
    dispatch_s: np.ndarray  # (trials,) time-to-return per trial
    warmup_compiles: int  # compiles absorbed by warmup (first-call cost)
    steady_compiles: int  # compiles DURING trials: >0 == retracing per call

    @property
    def trials(self) -> int:
        return int(self.wall_s.shape[0])

    @property
    def median(self) -> float:
        return float(np.median(self.wall_s))

    @property
    def p50(self) -> float:
        return self.median

    @property
    def p90(self) -> float:
        return float(np.quantile(self.wall_s, 0.9))

    @property
    def p99(self) -> float:
        return float(np.quantile(self.wall_s, 0.99))

    @property
    def std(self) -> float:
        return float(np.std(self.wall_s))

    @property
    def dispatch_median(self) -> float:
        return float(np.median(self.dispatch_s))

    def summary(self) -> dict:
        return dict(
            name=self.name, trials=self.trials, median_s=self.median,
            p50_s=self.p50, p90_s=self.p90, p99_s=self.p99, std_s=self.std,
            dispatch_median_s=self.dispatch_median,
            warmup_compiles=self.warmup_compiles,
            steady_compiles=self.steady_compiles,
        )


class StepTimer:
    """Measure a jitted step the right way (see module docstring).

    With a ``registry``, every trial lands in
    ``step_wall_seconds{step=name}`` / ``step_dispatch_seconds{step=name}``
    histograms and compile events in ``step_compiles_total{step,phase}`` —
    so live serving and offline benchmarks share one metric catalog.
    All accounting is host-side, AROUND the compiled call; the measured
    function's device work is untouched.
    """

    def __init__(self, name: str = "step", registry=None, *,
                 warmup: int = 3, trials: int = 30):
        if warmup < 0 or trials < 1:
            raise ValueError("need warmup >= 0 and trials >= 1")
        self.name = name
        self.registry = registry
        self.warmup = warmup
        self.trials = trials

    def measure(self, fn, *args, trials: Optional[int] = None,
                warmup: Optional[int] = None) -> StepStats:
        import jax

        trials = self.trials if trials is None else trials
        warmup = self.warmup if warmup is None else warmup
        c0 = compile_events()
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
        c1 = compile_events()
        wall = np.empty(trials)
        dispatch = np.empty(trials)
        for i in range(trials):
            t0 = time.perf_counter()
            out = fn(*args)
            dispatch[i] = time.perf_counter() - t0
            jax.block_until_ready(out)
            wall[i] = time.perf_counter() - t0
        c2 = compile_events()
        stats = StepStats(
            name=self.name, wall_s=wall, dispatch_s=dispatch,
            warmup_compiles=c1 - c0, steady_compiles=c2 - c1,
        )
        if self.registry is not None:
            h_wall = self.registry.histogram(
                "step_wall_seconds",
                "blocked wall time of a timed step (block_until_ready)")
            h_disp = self.registry.histogram(
                "step_dispatch_seconds",
                "host dispatch time of a timed step (time-to-return)")
            for w, d in zip(wall, dispatch):
                h_wall.observe(float(w), step=self.name)
                h_disp.observe(float(d), step=self.name)
            c = self.registry.counter(
                "step_compiles_total",
                "backend compiles seen while timing (steady>0 == retracing)")
            if stats.warmup_compiles:
                c.inc(stats.warmup_compiles, step=self.name, phase="warmup")
            if stats.steady_compiles:
                c.inc(stats.steady_compiles, step=self.name, phase="steady")
        return stats
