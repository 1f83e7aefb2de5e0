"""Moonlight-16B-A3B (moonshotai), deepseek-v3 layers with q_lora_rank
null [hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3].

* 27 layers at hidden 2048, RMSNorm eps 1e-5; untied head over 163840.
* Latent attention: 16 heads, q = x W_q of 128 (nope) + 64 (rope) a head;
  [c, k_pe] = x W_kv_a of 512 + 64, c through its RMSNorm; [k_nope, v] =
  c W_kv_b of 128 + 128 a head; rope theta 50000 over interleaved pairs,
  softmax scale 192^-1/2 (no rope scaling). The cache holds [c, k_pe].
* first_k_dense_replace 1: layer 0 has a dense SwiGLU MLP of 11264.
* Layers 1-26: 64 routed experts of 1408, 6 a token, by sigmoid scores
  selected with a correction bias (noaux_tc; one group), gated by the
  unbiased scores normalised over the 6 and scaled by 2.446; 2 shared
  experts as one MLP of 2816.

As registered the layer holds all 64 experts. One chip of an 8-way
expert-parallel deployment holds 8 of them: ``experts_held=8``,
``expert_offset=8c`` (the benchmark's configuration sets chip 0).
"""

from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    mlp_act="silu",
    tie_embeddings=False,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    latent_norm_eps=1e-6,
    first_dense_layers=1,
    num_experts=64,
    num_experts_per_tok=6,
    moe_impl="ragged",
    moe_d_ff=1408,
    shared_d_ff=2816,
    moe_routed_scale=2.446,
    attn_impl="chunked",
    attn_sharding="heads",
    moe_sharding="expert",
)
