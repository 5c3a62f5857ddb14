"""The one traffic generator: reads a traffic file and drives an engine.

The file's ``loop`` names the loop; ``closed`` is the one there is.  It
keeps ``outstanding_per_slot`` x slots requests outstanding: whenever the
engine has drained the queue, it is refilled and served again, so a full
batch is always waiting.  The window opens when the first timed request is
submitted and closes at the first completion at or after ``--seconds``
(:func:`bench.stats.whole_request_rate`).

Every request carries a fresh ``history``-token prompt drawn from the seed
(request ``i`` of stream ``s`` from ``default_rng([seed, s, i])``), so the
same seed gives the same prompts in the same order.  Each returned record
holds the request's sent, admitted and done times on the host's monotonic
clock.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np

WINDOW_STREAM, WARMUP_STREAM = 1, 2


def _span(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def history(seed: int, stream: int, i: int, width: int, vocab: int):
    rng = np.random.default_rng([seed, stream, i])
    return rng.integers(0, vocab, width).astype(np.int32)


@dataclasses.dataclass
class Record:
    """One request of the window, on the host's monotonic clock."""

    index: int
    prompt: np.ndarray
    sent: float
    admitted: float = float("nan")
    done: float = float("nan")
    sids: Optional[np.ndarray] = None
    scores: Optional[np.ndarray] = None

    @property
    def ok(self) -> bool:
        return self.sids is not None


@dataclasses.dataclass
class Window:
    """What a run's window produced."""

    t0: float  # window start
    t_end: float  # window end: the closing completion
    records: list  # the window's requests, in submission order
    batches: list  # (admitted, done) of every batch served
    attempted: int  # requests the window counts
    failed: int  # of those, requests with no result
    trace_span: tuple = (float("nan"), float("nan"))  # traced interval


def _finish(rec: Record, res: dict) -> None:
    if "sids" not in res:
        return
    rec.admitted = rec.sent + res["queue_s"]
    rec.done = rec.sent + res["latency_s"]
    rec.sids, rec.scores = res["sids"], res["scores"]


def warm_up(engine, queue_cls, n: int, seed: int, width: int, vocab: int,
            sid_length: int) -> None:
    """Serve ``n`` requests from the warm-up stream: compiles the shapes
    the window uses, and no others."""
    q = queue_cls()
    for i in range(n):
        q.submit(history(seed, WARMUP_STREAM, i, width, vocab), sid_length)
    res = engine.serve(q)
    bad = [r for r in res.values() if "sids" not in r]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad[:2]}")


def closed_loop(engine, queue_cls, traffic: dict, *, slots: int, seed: int,
                seconds: float, width: int, vocab: int, sid_length: int,
                on_start: Callable = None, on_stop: Callable = None):
    outstanding = int(traffic["outstanding_per_slot"]) * slots
    records: list[Record] = []
    batches = []
    if on_start:
        on_start()
    t0 = time.monotonic()
    while True:
        q = queue_cls()
        sent = {}
        while len(q) < outstanding:
            i = len(records)
            rec = Record(i, history(seed, WINDOW_STREAM, i, width, vocab),
                         sent=time.monotonic())
            sent[q.submit(rec.prompt, sid_length)] = rec
            records.append(rec)
        with _span("bench.serve"):
            res = engine.serve(q)
        for rid, rec in sent.items():
            _finish(rec, res.get(rid, {}))
        # the engine takes the queue in submission order, ``slots`` at a
        # time, and a batch's requests complete together
        mine = list(sent.values())
        for k in range(0, len(mine), slots):
            chunk = [r for r in mine[k:k + slots] if r.ok]
            if chunk:
                span = (min(r.admitted for r in chunk),
                        max(r.done for r in chunk))
                for r in chunk:
                    r.admitted, r.done = span
                batches.append(span)
        if not all(r.ok for r in sent.values()):
            break  # a failed request ends the window; it counts as failed
        if max(r.done for r in sent.values()) >= t0 + seconds:
            break
    t_stop = time.monotonic()
    if on_stop:
        on_stop()
    late = [r.done for r in records if r.ok and r.done >= t0 + seconds]
    t_end = min(late) if late else t_stop
    # the window's requests: those done by its end, and any that failed
    records = [r for r in records if not r.ok or r.done <= t_end]
    return Window(t0, t_end, records, batches, len(records),
                  sum(not r.ok for r in records), (t0, t_stop))
