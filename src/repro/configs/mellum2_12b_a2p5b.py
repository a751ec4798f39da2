"""mellum2-12b-a2.5b [moe, sliding window] — 28L d_model=2304 32H (GQA kv=4)
head_dim 128, every MLP sparse: 64 experts of width 896, top-8, no shared
expert; layers repeat (window, window, window, full) with a 1024-position
window; full layers YaRN RoPE (theta 5e5, factor 16 from 8192), window
layers plain RoPE; vocab 98304, untied head.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]"""

import jax.numpy as jnp
from repro.models import ATTN, ATTN_WINDOW, MOE_MLP, ModelConfig
from repro.models.layers import YaRN

PATTERN = ((ATTN_WINDOW, MOE_MLP),) * 3 + ((ATTN, MOE_MLP),)

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    n_layers=28, d_model=2304, n_heads=32, n_kv=4, d_ff=896,
    vocab=98304, head_dim=128,
    pattern=PATTERN,
    n_experts=64, top_k=8,
    sliding_window=1024,
    rope_theta=5e5,
    rope_yarn=YaRN(factor=16.0, original_max_position=8192, beta_fast=32.0,
                   beta_slow=1.0, attention_factor=1.2772588722239782),
    norm_eps=1e-6,
    tie_embeddings=False,
    dtype=jnp.bfloat16,
    attn_chunk=512,
    decode_kv_splits=16,
)

SMOKE = ModelConfig(
    name="mellum2-12b-a2.5b-smoke",
    n_layers=8, d_model=64, n_heads=4, n_kv=2, d_ff=32,
    vocab=256, head_dim=16,
    pattern=PATTERN,
    n_experts=8, top_k=2,
    sliding_window=8,
    rope_theta=5e5,
    rope_yarn=YaRN(factor=4.0, original_max_position=16, beta_fast=32.0,
                   beta_slow=1.0, attention_factor=1.138629436111989),
    norm_eps=1e-6,
    tie_embeddings=False,
    dtype=jnp.float32, attn_chunk=64, logit_chunk=64,
)
