"""Work counts of a DeepSeek-V2 decoder (latent attention, sparse experts),
from its widths: what any implementation must do.

* A pass reads every weight outside the vocabulary once; of each expert
  layer's held experts, only those its rows reach under uniform routing
  (a row picks ``top_k`` of the router's ``n_routed``).  The embedding is
  gathered row by row and the head needs only the SID columns, so neither
  is counted whole; the head's SID columns are left out of the bytes too,
  as ``weight_bytes_read`` is not told the SID vocabulary (8.4 MB a pass at
  2,048 columns; the FLOPs count them).
* One position keeps its latent and its rotated key for every layer.
* Model FLOPs count two per multiply-accumulate of every matmul that
  touches a token: the attention projections (``W_kv_b`` once per token),
  attention's two contractions over the context in their expanded form
  (``nope + rope`` for scores, ``v_head_dim`` for values), the dense FFN,
  the router, the shared expert, and the routed experts at
  ``top_k * n_experts / n_routed`` held experts per row.

Besides the members :class:`bench.work.Retrieval` asks for, the counts of
the two pieces that the cell's rooflines read: the routed experts
(``routed_work``) and the absorbed latent attention of a decode level
(``latent_attention_work``).
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Counts:
    dec: dict

    @property
    def _bpp(self) -> int:
        return {"bfloat16": 2, "float32": 4}[self.dec["dtype"]]

    @property
    def _moe(self) -> dict:
        return self.dec["moe"]

    @property
    def _routed(self) -> int:
        return self._moe.get("n_routed") or self._moe["n_experts"]

    @property
    def dense_layers(self) -> int:
        return self._moe["first_dense_layers"]

    @property
    def moe_layers(self) -> int:
        return self.dec["n_layers"] - self.dense_layers

    def _attn_params(self) -> int:
        d = self.dec
        D, H, r = d["d_model"], d["n_heads"], d["kv_lora_rank"]
        nope, rope, v = (d["qk_nope_head_dim"], d["qk_rope_head_dim"],
                         d["v_head_dim"])
        return (D * H * (nope + rope) + D * (r + rope) + r * H * (nope + v)
                + H * v * D)

    def _expert_params(self) -> int:
        return 3 * self.dec["d_model"] * self._moe["d_expert"]

    def _router_params(self) -> int:
        return self.dec["d_model"] * self._routed

    @property
    def params(self) -> int:
        """Parameters of the program's tree (norm scales included)."""
        d, m = self.dec, self._moe
        D, V = d["d_model"], d["vocab_size"]
        per_layer = self._attn_params() + 2 * D + d["kv_lora_rank"]
        dense = 3 * D * m["d_ff_dense"]
        sparse = (self._router_params()
                  + m["n_experts"] * self._expert_params()
                  + 3 * D * m["d_shared"])
        return (2 * V * D + D + d["n_layers"] * per_layer
                + self.dense_layers * dense + self.moe_layers * sparse)

    @property
    def weight_bytes(self) -> int:
        """Bytes of the program's tree: the router in float32."""
        router = self.moe_layers * self._router_params()
        return (self.params - router) * self._bpp + router * 4

    def _expert_bytes(self) -> int:
        return self._expert_params() * self._bpp

    def experts_reached(self, rows: int) -> float:
        """Held experts of one layer that ``rows`` rows reach, expected
        under uniform routing."""
        m = self._moe
        return m["n_experts"] * (1 - (1 - m["top_k"] / self._routed) ** rows)

    def weight_bytes_read(self, rows: int) -> int:
        """Every weight outside the vocabulary and the held experts, and
        the held experts that ``rows`` rows reach."""
        d, m = self.dec, self._moe
        vocab = 2 * d["vocab_size"] * d["d_model"] * self._bpp
        experts = self.moe_layers * m["n_experts"] * self._expert_bytes()
        return (self.weight_bytes - vocab - experts
                + int(self.moe_layers * self.experts_reached(rows)
                      * self._expert_bytes()))

    @property
    def kv_bytes_per_token(self) -> int:
        """The latent and the rotated key of one position, every layer."""
        d = self.dec
        return (d["n_layers"] * (d["kv_lora_rank"] + d["qk_rope_head_dim"])
                * self._bpp)

    def routed_rows(self, rows: int) -> float:
        """(row, held expert) pairs of one expert layer, expected."""
        m = self._moe
        return rows * m["top_k"] * m["n_experts"] / self._routed

    def token_flops(self, context: int, logits: bool, sid_vocab: int) -> int:
        """FLOPs of one token through every layer, attending to ``context``
        positions, plus the SID head when ``logits``."""
        d, m = self.dec, self._moe
        D, H = d["d_model"], d["n_heads"]
        attn = 2 * self._attn_params() + 2 * H * context * (
            d["qk_nope_head_dim"] + d["qk_rope_head_dim"] + d["v_head_dim"])
        dense = 2 * 3 * D * m["d_ff_dense"]
        sparse = 2 * (self._router_params() + 3 * D * m["d_shared"]
                      + self._expert_params() * m["top_k"] * m["n_experts"]
                      / self._routed)
        head = 2 * D * sid_vocab if logits else 0
        return int(d["n_layers"] * attn + self.dense_layers * dense
                   + self.moe_layers * sparse + head)

    def routed_work(self, rows: int) -> tuple[int, int]:
        """(FLOPs, bytes) of the routed experts of one pass over ``rows``
        rows, every expert layer: the held experts' weights that the rows
        reach, read once, and two FLOPs per multiply-accumulate of each
        expected (row, held expert) pair."""
        layers = self.moe_layers
        flops = 2 * self._expert_params() * self.routed_rows(rows) * layers
        nbytes = self.experts_reached(rows) * self._expert_bytes() * layers
        return int(flops), int(nbytes)

    def latent_attention_work(self, requests: int, beam: int, history: int,
                              level: int) -> tuple[int, int]:
        """(FLOPs, bytes) of decode level ``level`` >= 1's attention,
        absorbed, every layer: each beam's query scores ``history + level``
        latents and rotated keys and sums the latents back
        (``2 H ctx (2 kv_lora + rope)`` per row); each request's history
        state is read once, each beam's ``level`` suffix columns once."""
        d = self.dec
        r, rope = d["kv_lora_rank"], d["qk_rope_head_dim"]
        rows = requests * beam
        ctx = history + level
        flops = (d["n_layers"] * rows * 2 * d["n_heads"] * ctx
                 * (2 * r + rope))
        nbytes = self.kv_bytes_per_token * (requests * history
                                            + rows * level)
        return flops, nbytes


def counts(dec: dict) -> Counts:
    """The counts of a configuration file's ``decoder`` group."""
    return Counts(dec)


def least_seconds(work: tuple[int, int], peaks: dict) -> float:
    """The larger of a piece's two limits at the chip's peaks."""
    flops, nbytes = work
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
