"""Operations and bytes that the served algorithm needs, from shapes alone.

These are the yardstick's counts, not the program's: they count what any
implementation of constrained beam search over this decoder has to do, so a
faster implementation reads as a larger share of the same work.

The decoder's own counts depend on its family and live in a file of their
own, ``bench/counts/<reference>.py``, found by the name of the
configuration's ``reference``; its ``counts(decoder)`` returns an object
with the members of :class:`DecoderCounts`.  Around them:

* A pass reads the weights that its rows reach, once
  (``weight_bytes_read``).
* A decode level reads each request's history K/V once, whatever the beam
  width, plus the per-beam K/V of the SID tokens decoded so far.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Protocol


class DecoderCounts(Protocol):
    """What :class:`Retrieval` asks of a family's counts."""

    kv_bytes_per_token: int  # what one position keeps, over every layer

    def weight_bytes_read(self, rows: int) -> int:
        """Weight bytes one pass over ``rows`` token rows must read."""

    def token_flops(self, context: int, logits: bool, sid_vocab: int) -> int:
        """FLOPs of one token attending to ``context`` positions, plus the
        head over ``sid_vocab`` tokens when ``logits``."""


@dataclasses.dataclass(frozen=True)
class Retrieval:
    """One request: a ``history``-token prompt, then beam search of width
    ``beam`` over ``sid_length`` levels of a ``vocab``-token SID alphabet."""

    dec: DecoderCounts
    history: int
    sid_length: int
    beam: int
    vocab: int

    def prefill_flops(self) -> int:
        """The prompt's pass; only its last position needs logits (they
        are level 0's)."""
        S = self.history
        return sum(self.dec.token_flops(p + 1, p == S - 1, self.vocab)
                   for p in range(S))

    def level_flops(self, level: int) -> int:
        """Decode level ``level`` >= 1 feeds each beam's last SID token."""
        ctx = self.history + level
        return self.beam * self.dec.token_flops(ctx, True, self.vocab)

    def flops(self) -> int:
        """Model FLOPs of one whole request."""
        return self.prefill_flops() + sum(
            self.level_flops(l) for l in range(1, self.sid_length))

    def history_kv_bytes(self) -> int:
        return self.history * self.dec.kv_bytes_per_token

    def level_bytes(self, level: int, batch: int) -> int:
        """Least bytes of decode level ``level`` >= 1 for ``batch`` requests:
        the weights its ``batch * beam`` rows reach, each history once, each
        beam's SID K/V once."""
        kv = self.dec.kv_bytes_per_token
        per_request = self.history_kv_bytes() + self.beam * level * kv
        return (self.dec.weight_bytes_read(batch * self.beam)
                + batch * per_request)

    def prefill_bytes(self, batch: int) -> int:
        """The weights its ``batch * history`` rows reach, and each
        history's K/V written once."""
        return (self.dec.weight_bytes_read(batch * self.history)
                + batch * self.history_kv_bytes())

    def decoder_least_seconds(self, batch: int, peak_flops: float,
                              peak_bytes: float) -> float:
        """Least time of every decoder pass of one batch on a chip with
        these peaks: each pass bound by the larger of its two limits."""
        t = max(batch * self.prefill_flops() / peak_flops,
                self.prefill_bytes(batch) / peak_bytes)
        for l in range(1, self.sid_length):
            t += max(batch * self.level_flops(l) / peak_flops,
                     self.level_bytes(l, batch) / peak_bytes)
        return t


def retrieval(cfg: dict) -> Retrieval:
    """The counts for a configuration file's dict, its decoder's by the
    family of its ``reference``."""
    family = importlib.import_module(f"bench.counts.{cfg['reference']}")
    return Retrieval(family.counts(cfg["decoder"]), cfg["history"],
                     cfg["sid_length"], cfg["beam"], cfg["vocab"])
