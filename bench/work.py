"""Operations and bytes that the served algorithm needs, from shapes alone.

These are the yardstick's counts, not the program's: they count what any
implementation of constrained beam search over this decoder has to do, so a
faster implementation reads as a larger share of the same work.

* A forward pass reads every weight once (the tied embedding doubles as the
  output head, so it is read once as well).
* A decode level reads each request's history K/V once, whatever the beam
  width, plus the per-beam K/V of the SID tokens decoded so far.
* Model FLOPs count two per multiply-accumulate of every matmul that touches
  a token, plus attention's two score/value contractions over the context.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Decoder:
    """Widths of a dense GQA decoder with a tied embedding."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    bytes_per_param: int = 2  # bfloat16

    @classmethod
    def from_config(cls, dec: dict) -> "Decoder":
        bpp = {"bfloat16": 2, "float32": 4}[dec.get("dtype", "bfloat16")]
        return cls(dec["n_layers"], dec["d_model"], dec["n_heads"],
                   dec["n_kv_heads"], dec["head_dim"], dec["d_ff"],
                   dec["vocab_size"], bpp)

    @property
    def layer_params(self) -> int:
        D, hd = self.d_model, self.head_dim
        attn = D * self.n_heads * hd * 2 + D * self.n_kv_heads * hd * 2
        return attn + 3 * D * self.d_ff + 2 * D

    @property
    def params(self) -> int:
        return (self.n_layers * self.layer_params
                + self.vocab_size * self.d_model + self.d_model)

    @property
    def weight_bytes(self) -> int:
        return self.params * self.bytes_per_param

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position over every layer."""
        return (2 * self.n_layers * self.n_kv_heads * self.head_dim
                * self.bytes_per_param)

    def token_flops(self, context: int, logits: bool, sid_vocab: int) -> int:
        """FLOPs of one token through every layer, attending to ``context``
        positions, plus the SID head when ``logits``."""
        D = self.d_model
        matmul = 2 * (self.layer_params - 2 * D)
        attn = 4 * self.n_heads * self.head_dim * context
        head = 2 * D * sid_vocab if logits else 0
        return self.n_layers * (matmul + attn) + head


@dataclasses.dataclass(frozen=True)
class Retrieval:
    """One request: a ``history``-token prompt, then beam search of width
    ``beam`` over ``sid_length`` levels of a ``vocab``-token SID alphabet."""

    dec: Decoder
    history: int
    sid_length: int
    beam: int
    vocab: int

    def prefill_flops(self) -> int:
        """The prompt's pass; only its last position needs logits (they
        are level 0's)."""
        S = self.history
        body = sum(self.dec.token_flops(p + 1, False, 0) for p in range(S))
        return body + 2 * self.dec.d_model * self.vocab

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
        the weights once, each history once, each beam's SID K/V once."""
        kv = self.dec.kv_bytes_per_token
        per_request = self.history_kv_bytes() + self.beam * level * kv
        return self.dec.weight_bytes + batch * per_request

    def prefill_bytes(self, batch: int) -> int:
        """The weights once, and each history's K/V written once."""
        return self.dec.weight_bytes + batch * self.history_kv_bytes()

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
    """The counts for a configuration file's dict."""
    return Retrieval(Decoder.from_config(cfg["decoder"]), cfg["history"],
                     cfg["sid_length"], cfg["beam"], cfg["vocab"])
