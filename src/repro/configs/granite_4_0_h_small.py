"""Granite-4.0-H-Small (ibm-granite, 32B-A9B), a hybrid of Mamba-2 and
attention layers with sparse experts in every layer
[hf:ibm-granite/granite-4.0-h-small config.json, model_type
granitemoehybrid].

* 40 layers at hidden 4096, RMSNorm eps 1e-5; tied embedding over 100352.
* ``layer_types``: Mamba-2 mixers, with attention at layers 5, 15, 25 and
  35 (36 : 4).
* Mamba-2: 128 heads of 64 (expand 2: 8192 channels), d_state 128, one
  group of B and C, a causal depthwise conv of 4 taps with bias over x, B
  and C, no projection bias; the gated RMSNorm over all 8192 channels;
  chunk 256 in prefill.
* Attention: 32 query heads and 8 KV heads of 128, no positional encoding
  (``position_embedding_type`` nope), scale ``attention_multiplier``
  1/128, no bias.
* Every layer: 72 routed experts of 768, 10 a token, gated by the softmax
  over the 10 largest router logits (no bias, no scale), and one shared
  SwiGLU of 1536.
* Granite multipliers: embedding 12, residual 0.22, logits / 16.

As registered the layer holds all 72 experts. One chip of an 8-way
expert-parallel deployment holds 9 of them: ``experts_held=9``,
``expert_offset=9c`` (the benchmark's configuration sets chip 0 and cuts
the depth to the first 20 layers).
"""

from repro.models.common import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="moe",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    d_ff=768,
    vocab_size=100352,
    use_rope=False,
    norm_eps=1e-5,
    mlp_act="silu",
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
    logits_scaling=16.0,
    layer_types=_PERIOD * 4,
    ssm_heads=128,
    ssm_head_dim=64,
    ssm_state=128,
    ssm_groups=1,
    ssm_conv=4,
    ssm_chunk=256,
    num_experts=72,
    num_experts_per_tok=10,
    moe_impl="ragged",
    moe_score="softmax",
    moe_d_ff=768,
    shared_d_ff=1536,
    attn_impl="chunked",
    attn_sharding="heads",
    moe_sharding="expert",
)
