"""A decoder family the benchmark has no files for, kept with the tests.

Latent attention (MLA) in every layer, a first dense layer, then layers of
sparse experts with a shared one: two of the program's layer stacks whose
attention weights share their names, and an untied output head.  The module
declares what a family's files declare, the map of its drawn weights into
the program's tree (``placement``) and its work counts (``counts``), and
draws its weights (``weights_from_key``); it has no forward pass.  Tests
register it under :data:`NAME` as both a reference and a counts file, so
the harness finds it by the configuration's ``reference`` alone.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math

import jax
import jax.numpy as jnp

NAME = "tiny_mla_moe"

CONFIG = {
    "decoder": {"base": "deepseek-v2-lite-16b", "n_layers": 2, "d_model": 64,
                "n_heads": 4, "n_kv_heads": 4, "d_ff": 96, "vocab_size": 34,
                "kv_lora_rank": 32, "qk_nope_head_dim": 16,
                "qk_rope_head_dim": 8, "v_head_dim": 16,
                "tie_embeddings": False, "dtype": "bfloat16",
                "moe": {"n_experts": 8, "top_k": 2, "d_expert": 32,
                        "n_shared": 1, "d_shared": 32,
                        "first_dense_layers": 1, "d_ff_dense": 96}},
    "reference": NAME, "vocab": 32, "sid_length": 4, "beam": 8,
    "history": 16,
}

ATTN = ("wq", "w_kv_a", "w_kv_b", "wo")
FFN = ("w1", "w3", "w2")


def _stacks(dec):
    """(program stack, drawn group, layers) of each stack that exists."""
    n_dense = dec["moe"]["first_dense_layers"]
    return [s for s in (("dense_layers", "dense", n_dense),
                        ("moe_layers", "moe", dec["n_layers"] - n_dense))
            if s[2]]


def _shapes(dec, group):
    D, H, m = dec["d_model"], dec["n_heads"], dec["moe"]
    r, nope, rope = (dec["kv_lora_rank"], dec["qk_nope_head_dim"],
                     dec["qk_rope_head_dim"])
    v = dec["v_head_dim"]
    out = {"wq": (D, H * (nope + rope)), "w_kv_a": (D, r + rope),
           "w_kv_b": (r, H * (nope + v)), "wo": (H * v, D)}
    if group == "dense":
        F = m["d_ff_dense"]
        return dict(out, w1=(D, F), w3=(D, F), w2=(F, D))
    E, F, S = m["n_experts"], m["d_expert"], m["d_shared"]
    return dict(out, router=(D, E), w1=(E, D, F), w3=(E, D, F),
                w2=(E, F, D), shared_w1=(D, S), shared_w3=(D, S),
                shared_w2=(S, D))


def placement(dec):
    leaves = {("emb",): ("emb",), ("unemb",): ("unemb",)}
    ones = {("final_norm", "scale")}
    for stack, group, _ in _stacks(dec):
        leaves.update({(stack, "attn", n): (group, n) for n in ATTN})
        ones |= {(stack, "ln_attn", "scale"), (stack, "ln_ffn", "scale"),
                 (stack, "attn", "kv_norm", "scale")}
        if group == "dense":
            leaves.update({(stack, "ffn", n): (group, n) for n in FFN})
        else:
            leaves.update({(stack, "moe", n): (group, n)
                           for n in ("router",) + FFN})
            leaves.update({(stack, "moe", "shared", n): (group, "shared_" + n)
                           for n in FFN})
    return leaves, ones


def weights_from_key(dec, key):
    """Every weight, He-normal over its rows (the router in float32, the
    rest in the served dtype), each stack's stacked over its layers, in one
    jitted call."""
    return _weights(key, json.dumps(dec, sort_keys=True))


@functools.partial(jax.jit, static_argnums=1)
def _weights(key, dec_json):
    dec = json.loads(dec_json)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dec["dtype"]]

    def draw(k, shape, dt):
        return (jax.random.normal(k, shape)
                * (2.0 / shape[-2]) ** 0.5).astype(dt)

    V, D = dec["vocab_size"], dec["d_model"]
    w = {"emb": draw(jax.random.fold_in(key, 0), (V, D), dtype),
         "unemb": draw(jax.random.fold_in(key, 1), (D, V), dtype)}
    for g, (_, group, n) in enumerate(_stacks(dec)):
        kg = jax.random.fold_in(key, 2 + g)
        w[group] = {
            name: draw(jax.random.fold_in(kg, i), (n,) + s,
                       jnp.float32 if name == "router" else dtype)
            for i, (name, s) in enumerate(_shapes(dec, group).items())}
    return w


@dataclasses.dataclass(frozen=True)
class Counts:
    """Counts of this family: dense weights are read by every pass; of each
    expert layer's routed experts, those its rows reach under uniform
    routing; one position keeps its latent and its rotary key."""

    dec: dict

    @property
    def _bpp(self):
        return {"bfloat16": 2, "float32": 4}[self.dec["dtype"]]

    def _layers(self, group):
        return {g: n for _, g, n in _stacks(self.dec)}.get(group, 0)

    @property
    def params(self) -> int:
        D, V = self.dec["d_model"], self.dec["vocab_size"]
        r = self.dec["kv_lora_rank"]
        total = 2 * V * D + D
        for _, group, n in _stacks(self.dec):
            per = sum(math.prod(s) for s in _shapes(self.dec, group).values())
            total += n * (per + 2 * D + r)
        return total

    def _routed_bytes_per_expert(self):
        D, F = self.dec["d_model"], self.dec["moe"]["d_expert"]
        return 3 * D * F * self._bpp

    @property
    def weight_bytes(self) -> int:
        E, D = self.dec["moe"]["n_experts"], self.dec["d_model"]
        router = self._layers("moe") * D * E
        return (self.params - router) * self._bpp + router * 4

    def weight_bytes_read(self, rows: int) -> int:
        m = self.dec["moe"]
        E, k = m["n_experts"], m["top_k"]
        reached = math.ceil(E * (1 - (1 - k / E) ** rows))
        unread = self._layers("moe") * (E - reached)
        return self.weight_bytes - unread * self._routed_bytes_per_expert()

    @property
    def kv_bytes_per_token(self) -> int:
        d = self.dec
        return (d["n_layers"] * (d["kv_lora_rank"] + d["qk_rope_head_dim"])
                * self._bpp)

    def token_flops(self, context: int, logits: bool, sid_vocab: int) -> int:
        d, m = self.dec, self.dec["moe"]
        D, H = d["d_model"], d["n_heads"]
        attn_w = sum(math.prod(_shapes(d, "dense")[n]) for n in ATTN)
        attn = 2 * attn_w + 2 * H * context * (
            d["qk_nope_head_dim"] + d["qk_rope_head_dim"] + d["v_head_dim"])
        dense = 2 * 3 * D * m["d_ff_dense"]
        sparse = 2 * (D * m["n_experts"] + 3 * D * m["d_expert"] * m["top_k"]
                      + 3 * D * m["d_shared"])
        head = 2 * D * sid_vocab if logits else 0
        return (d["n_layers"] * attn + self._layers("dense") * dense
                + self._layers("moe") * sparse + head)


def counts(dec: dict) -> Counts:
    return Counts(dec)
