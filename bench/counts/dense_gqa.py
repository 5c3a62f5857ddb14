"""Work counts of a dense GQA decoder with a tied embedding, from its widths.

* A forward pass reads every weight once, however many rows it carries (the
  tied embedding doubles as the output head, so it is read once as well).
* One position's K and V are kept for every layer and every KV head.
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

    def weight_bytes_read(self, rows: int) -> int:
        """Every weight, whatever the rows: a dense pass touches them all."""
        return self.weight_bytes

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


def counts(dec: dict) -> Decoder:
    """The counts of a configuration file's ``decoder`` group."""
    bpp = {"bfloat16": 2, "float32": 4}[dec.get("dtype", "bfloat16")]
    return Decoder(dec["n_layers"], dec["d_model"], dec["n_heads"],
                   dec["n_kv_heads"], dec["head_dim"], dec["d_ff"],
                   dec["vocab_size"], bpp)
