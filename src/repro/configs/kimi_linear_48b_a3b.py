"""Kimi-Linear-48B-A3B-Instruct (moonshotai), a hybrid of gated
delta-rule (KDA) layers and NoPE latent attention with sparse experts
[hf:moonshotai/Kimi-Linear-48B-A3B-Instruct config.json, model_type
kimi_linear; the Kimi Linear report, arXiv:2510.26692].

* 27 layers at hidden 2304, RMSNorm eps 1e-5; untied head over 163840.
* ``linear_attn_config``: KDA at the 1-indexed layers ``kda_layers`` and
  MLA at ``full_attn_layers`` 4, 8, 12, 16, 20, 24, 27 (0-indexed 3, 7,
  ..., 23, 26): 3 KDA : 1 MLA.
* KDA: 32 heads of 128 (d_k = d_v), a causal depthwise conv of 4 taps on
  q, k and v without bias; per-channel decay -exp(A_log) softplus(x W_fa
  W_fb + dt_bias) at a low rank of 128; write strength sigmoid(x W_b); an
  output RMSNorm per head gated by sigmoid(x W_ga W_gb).
* MLA: 32 heads, q = x W_q of 128 (nope) + 64 (rope part) a head; [c,
  k_pe] = x W_kv_a of 512 + 64, c through its RMSNorm; [k_nope, v] = c
  W_kv_b of 128 + 128 a head; ``mla_use_nope``: no rotation of q_pe or
  k_pe; softmax scale 192^-1/2. The config's head_dim 72 (2304 / 32) is
  used by neither mixer.
* first_k_dense_replace 1: layer 0 (a KDA layer) has a dense SwiGLU MLP of
  9216.
* Layers 1-26: 256 routed experts of 1024, 8 a token, by sigmoid scores
  selected with a correction bias (one group), gated by the unbiased
  scores renormalised over the 8 and scaled by 2.446; one shared expert
  of 1024.

As registered the layer holds all 256 experts. One chip of a 16-way
expert-parallel deployment holds 16 of them: ``experts_held=16``,
``expert_offset=16c`` (the benchmark's configuration sets chip 0).
"""

from repro.models.common import ModelConfig

_FULL_ATTN = (4, 8, 12, 16, 20, 24, 27)          # 1-indexed, as published

CONFIG = ModelConfig(
    name="kimi-linear-48b-a3b",
    family="moe",
    num_layers=27,
    d_model=2304,
    num_heads=32,
    num_kv_heads=32,
    d_ff=9216,
    vocab_size=163840,
    use_rope=False,
    norm_eps=1e-5,
    mlp_act="silu",
    tie_embeddings=False,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    latent_norm_eps=1e-6,
    first_dense_layers=1,
    layer_types=tuple("attention" if i + 1 in _FULL_ATTN else "kda"
                      for i in range(27)),
    kda_heads=32,
    kda_head_dim=128,
    kda_conv=4,
    num_experts=256,
    num_experts_per_tok=8,
    moe_impl="ragged",
    moe_d_ff=1024,
    shared_d_ff=1024,
    moe_routed_scale=2.446,
    attn_impl="chunked",
    # at the TPU compiler's default scoped VMEM of 16 MiB the prefill of
    # 1536 tokens is refused: the expert layer's row gather (12288 rows of
    # 2304) asks for 16.41 MiB. Only this configuration sets it: on a v5e
    # 17 MiB slows most of moonlight's and granite-4.0-h's prefills by
    # 11-17 %.
    prefill_vmem_kib=17 * 1024,
    attn_sharding="heads",
    moe_sharding="expert",
)
