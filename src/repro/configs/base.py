"""Config dataclasses for every architecture family + input-shape specs.

Each assigned architecture gets one ``configs/<id>.py`` exposing ``CONFIG``
(the exact published hyper-parameters) and ``SHAPES`` (its assigned
input-shape set).  ``smoke_config()`` returns the reduced same-family config
used by CPU smoke tests; the full config is exercised only via the dry-run
(ShapeDtypeStructs, no allocation).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

# --------------------------------------------------------------------------
# Transformer LM family
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """An expert layer.  The router scores ``n_routed`` experts and keeps
    ``top_k`` of them per token; this chip holds ``n_experts`` of those,
    ids ``expert_offset .. expert_offset + n_experts - 1``, and computes
    only their part of the result (expert parallelism without the
    exchange: the other chips' experts add the rest)."""

    n_experts: int  # experts held here
    top_k: int
    d_expert: int  # expert FFN hidden width
    n_routed: int = 0  # experts the router scores (0 => n_experts)
    expert_offset: int = 0  # id of the first expert held here
    # top-k weights renormalised to sum to one (Mixtral) or kept as the
    # softmax gives them (DeepSeek-V2)
    norm_topk_prob: bool = True
    n_shared: int = 0  # shared (always-on) experts
    d_shared: int = 0  # shared-expert hidden width (n_shared * d_expert if 0)
    first_dense_layers: int = 0  # leading layers that use a dense FFN
    d_ff_dense: int = 0  # width of those dense FFNs
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # GShard-style dispatch groups PER SEQUENCE. 0 = flat global dispatch
    # (position cumsum runs over the full sharded token axis — forces
    # cross-shard prefix sums). g >= 1 splits (B, S) into B*g groups so the
    # cumsum/scatter stay shard-local (EXPERIMENTS.md §Perf hillclimb B).
    dispatch_groups: int = 0

    @property
    def router_width(self) -> int:
        return self.n_routed or self.n_experts


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (arXiv 2309.00071), as DeepSeek-V2 configures
    it (``rope_scaling`` with ``type: yarn``)."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``: 0.1 * mscale * ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 => d_model // n_heads
    qkv_bias: bool = False
    attention: str = "gqa"  # "gqa" (covers MHA/MQA/SWA) | "mla"
    sliding_window: Optional[int] = None  # SWA window (Mixtral: 4096)
    # --- MLA (DeepSeek-V2) ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # --- MoE ---
    moe: Optional[MoEConfig] = None
    # --- misc ---
    rope_theta: float = 10_000.0
    rope_scaling: Optional[YarnScaling] = None  # MLA's rotary dims only
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    remat: bool = True
    attn_chunk_q: int = 512
    attn_chunk_kv: int = 1024
    # sequence parallelism: dp axis names for activation sharding constraints
    # (set by the launcher per mesh; () = off). The residual stream between
    # layers is sharded (batch=sp_axes, seq="model") so per-chip activation
    # storage under remat scales 1/TP.
    sp_axes: tuple = ()
    use_sp: bool = True  # launcher hint: allow setting sp_axes for train
    train_microbatches: int = 1  # grad-accumulation inside the train cell
    # roofline accounting: XLA cost_analysis counts a while-loop body ONCE,
    # not x trip-count. layer_unroll=k inlines k layer bodies per iteration;
    # the roofline runner lowers k=1 and k=2 and extrapolates exact totals.
    # inner_unroll=True fully unrolls the attention-chunk and CE-chunk scans
    # so their flops are inside the (counted) layer body.
    layer_unroll: int = 1
    inner_unroll: bool = False
    ce_chunk: int = 256  # sequence-chunked CE loss (see transformer.lm_loss)
    # Deferred KV commit: decode does NOT dynamic-update-slice into the
    # sequence-sharded cache (which forces GSPMD "involuntary full
    # rematerialization" = a full cache all-gather). Instead attention runs
    # over [read-only cache | fresh k/v] and the per-layer k/v are returned
    # for the serving layer to commit in blocks (EXPERIMENTS.md §Perf C).
    defer_cache_write: bool = False
    # GR beam caches as (L, B, M, S, KV, hd) instead of flat (L, B*M, ...):
    # the beam-permute gather becomes batch-local (take_along_axis over M)
    # instead of a gather across the dp-sharded flat axis, which GSPMD can
    # only serve by all-gathering the whole beam cache (§Perf hillclimb A).
    gr_batched_beams: bool = False
    # Flash-decoding split-K: constrain decode q/k/v projections to be
    # replicated over `model` so GSPMD keeps the KV cache sequence-sharded
    # and contracts shard-locally (partial softmax + tiny combine), instead
    # of resharding the whole cache to head sharding every step (§Perf C).
    # Uses sp_axes as the batch sharding of the small per-token tensors.
    decode_split_k: bool = False
    # Weight-replicated serving: for models whose weights fit one chip
    # (static-gr 3B = 6 GB bf16), replicate params and shard the request
    # batch over EVERY mesh axis — all TP psums vanish from the serve step
    # (§Perf hillclimb A, iteration 2).
    serve_replicate_weights: bool = False

    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def mla_softmax_scale(self) -> float:
        """DeepSeek-V2's attention scale: (nope + rope)^-1/2, times
        ``yarn_mscale(factor, mscale_all_dim)`` squared under YaRN."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        y = self.rope_scaling
        if y is not None and y.mscale_all_dim:
            scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
        return scale

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + layers)."""
        D, V, L = self.d_model, self.vocab_size, self.n_layers
        emb = V * D * (1 if self.tie_embeddings else 2)
        if self.attention == "mla":
            hd = self.qk_nope_head_dim + self.qk_rope_head_dim
            attn = (
                D * self.n_heads * hd  # q proj
                + D * (self.kv_lora_rank + self.qk_rope_head_dim)  # kv down
                + self.kv_lora_rank * self.n_heads
                * (self.qk_nope_head_dim + self.v_head_dim)  # kv up
                + self.n_heads * self.v_head_dim * D  # o proj
            )
        else:
            hd = self.resolved_head_dim()
            attn = D * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * D
        if self.moe is None:
            ffn = 3 * D * self.d_ff
            layers = L * (attn + ffn)
        else:
            m = self.moe
            moe_ffn = 3 * D * m.d_expert * m.n_experts + D * m.router_width
            shared = 3 * D * (m.d_shared or m.n_shared * m.d_expert) if m.n_shared else 0
            dense = 3 * D * (m.d_ff_dense or self.d_ff)
            layers = (
                m.first_dense_layers * (attn + dense)
                + (L - m.first_dense_layers) * (attn + moe_ffn + shared)
            )
        return emb + layers

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: routed top-k only)."""
        if self.moe is None:
            return self.param_count()
        D, L = self.d_model, self.n_layers
        m = self.moe
        full = self.param_count()
        moe_total = 3 * D * m.d_expert * m.n_experts
        moe_active = 3 * D * m.d_expert * m.top_k
        return full - (L - m.first_dense_layers) * (moe_total - moe_active)


@dataclasses.dataclass(frozen=True)
class LMShape:
    name: str
    kind: str  # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


LM_SHAPES = (
    LMShape("train_4k", "train", 4_096, 256),
    LMShape("prefill_32k", "prefill", 32_768, 32),
    LMShape("decode_32k", "decode", 32_768, 128),
    LMShape("long_500k", "decode", 524_288, 1),
)


# --------------------------------------------------------------------------
# GNN family
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str
    n_layers: int = 15
    d_hidden: int = 128
    mlp_layers: int = 2
    aggregator: str = "sum"
    node_feat_dim: int = 16
    edge_feat_dim: int = 8
    out_dim: int = 3
    dtype: str = "bfloat16"
    remat: bool = True
    layer_unroll: int = 1  # see TransformerConfig.layer_unroll


@dataclasses.dataclass(frozen=True)
class GraphShape:
    name: str
    kind: str  # "full" | "sampled" | "batched"
    n_nodes: int
    n_edges: int
    d_feat: int
    batch: int = 1
    batch_nodes: int = 0
    fanout: tuple = ()


GNN_SHAPES = (
    GraphShape("full_graph_sm", "full", 2_708, 10_556, 1_433),
    GraphShape(
        "minibatch_lg", "sampled", 232_965, 114_615_892, 602,
        batch_nodes=1_024, fanout=(15, 10),
    ),
    GraphShape("ogb_products", "full", 2_449_029, 61_859_140, 100),
    GraphShape("molecule", "batched", 30, 64, 16, batch=128),
)


# --------------------------------------------------------------------------
# RecSys family
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    model: str  # "wide_deep" | "mind" | "dlrm" | "fm"
    n_dense: int = 0
    n_sparse: int = 26
    embed_dim: int = 32
    vocab_sizes: tuple = ()  # per-sparse-feature rows
    bot_mlp: tuple = ()
    top_mlp: tuple = ()
    mlp: tuple = ()
    interaction: str = "concat"
    # MIND-specific
    n_interests: int = 4
    capsule_iters: int = 3
    hist_len: int = 50
    multi_hot: int = 1  # indices per sparse feature (bag arity K)
    dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class RecsysShape:
    name: str
    kind: str  # "train" | "serve" | "retrieval"
    batch: int
    n_candidates: int = 0


RECSYS_SHAPES = (
    RecsysShape("train_batch", "train", 65_536),
    RecsysShape("serve_p99", "serve", 512),
    RecsysShape("serve_bulk", "serve", 262_144),
    RecsysShape("retrieval_cand", "retrieval", 1, n_candidates=1_000_000),
)


# --------------------------------------------------------------------------
# RQ-VAE (Semantic-ID tokenizer for the paper's generative retrieval stack)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RQVAEConfig:
    feat_dim: int = 64
    latent_dim: int = 32
    n_levels: int = 4  # SID length L
    codebook_size: int = 256  # |V|
    enc_hidden: tuple = (128, 64)
    commitment_weight: float = 0.25


@dataclasses.dataclass(frozen=True)
class ArchBundle:
    """What the registry hands to the launcher: config + shapes + family."""

    arch_id: str
    family: str  # "lm" | "gnn" | "recsys" | "gr"
    config: object
    shapes: tuple
    notes: str = ""
