"""deepseek-v2-lite-16b [moe] — 27L d_model=2048 16H d_ff(expert)=1408
vocab=102400; MLA kv_lora=512 (no query LoRA), YaRN rotary scaling
(factor 40 over 4096 positions); 2 shared + 64 routed experts, softmax
top-6 kept unnormalised; first layer dense (d_ff=10944); RMSNorm eps 1e-6.
[arXiv:2405.04434; hf config.json]"""
from repro.configs.base import (
    ArchBundle, LM_SHAPES, MoEConfig, TransformerConfig, YarnScaling,
)

CONFIG = TransformerConfig(
    name="deepseek-v2-lite-16b",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,  # dense-layer FFN width
    vocab_size=102400,
    attention="mla",
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_scaling=YarnScaling(
        factor=40.0,
        original_max_position_embeddings=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        mscale=0.707,
        mscale_all_dim=0.707,
    ),
    norm_eps=1e-6,
    moe=MoEConfig(
        n_experts=64,
        top_k=6,
        d_expert=1408,
        norm_topk_prob=False,  # routed_scaling_factor 1: no scale
        n_shared=2,
        d_shared=2 * 1408,
        first_dense_layers=1,
        d_ff_dense=10944,
    ),
)

SHAPES = LM_SHAPES

BUNDLE = ArchBundle(
    arch_id="deepseek-v2-lite-16b",
    family="lm",
    config=CONFIG,
    shapes=SHAPES,
    notes=(
        "MLA's latent cache is 7x smaller than full K/V (27 layers x 576 "
        "latents x 2 B = 31 KB a position, ~16.3 GB at 524,288 positions) "
        "— long_500k run as a BONUS cell; per the shape "
        "rules MLA is still full attention, so the cell is marked bonus in "
        "EXPERIMENTS.md rather than a sub-quadratic substitute."
    ),
)
