"""granite-moe-3b-a800m [moe] — llama-arch GQA + 40-expert top-8 MoE.

32L d_model=1536 24H (GQA kv=8, head 64) expert width 512, MoE 40e top-8
(top-k of the router's logits, softmax over the k chosen), vocab=49155,
tied embedding and head, RMSNorm eps 1e-6, rope theta 10000.  Granite's
muP multipliers: embeddings x 12, every residual branch x 0.22, attention
scale 1/64 (in place of 1/sqrt(64)), logits / 6.
[hf:ibm-granite/granite-3.0-3b-a800m-base]
"""

import jax.numpy as jnp

from repro.configs.base import ModelConfig

_GRANITE = dict(
    arch_type="moe",
    tie_embeddings=True,
    attn_scale=0.015625,
    embed_multiplier=12.0,
    residual_multiplier=0.22,
    logits_divisor=6.0,
    router_aux_coef=0.001,
    source="hf:ibm-granite/granite-3.0-3b-a800m-base",
)

CONFIG = ModelConfig(
    name="granite-moe-3b-a800m",
    n_layers=32,
    d_model=1536,
    n_heads=24,
    n_kv_heads=8,
    d_ff=512,
    vocab_size=49155,
    n_experts=40,
    top_k=8,
    d_expert=512,
    dtype=jnp.bfloat16,
    param_dtype=jnp.bfloat16,
    **_GRANITE,
)

SMOKE = ModelConfig(
    name="granite-moe-smoke",
    n_layers=2,
    d_model=128,
    n_heads=4,
    n_kv_heads=2,
    d_ff=64,
    vocab_size=256,
    n_experts=4,
    top_k=2,
    d_expert=64,
    **_GRANITE,
)
