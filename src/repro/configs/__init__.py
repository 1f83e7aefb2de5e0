"""Assigned architecture configs. ``get_config('<arch-id>')`` accepts the
public ids with dashes (e.g. ``deepseek-67b``)."""

from __future__ import annotations

import importlib

from repro.configs.devices import (enable_compile_cache, make_mesh,  # noqa: F401
                                   make_serving_mesh, setup_devices)
from repro.models.common import ModelConfig

ARCH_IDS = [
    "deepseek-67b",
    "qwen3-4b",
    "granite-3-2b",
    "qwen2-0.5b",
    "grok-1-314b",
    "moonshot-v1-16b-a3b",
    "moonlight-16b-a3b",
    "granite-4.0-h-small",
    "kimi-linear-48b-a3b",
    "recurrentgemma-2b",
    "llava-next-34b",
    "whisper-large-v3",
    "xlstm-350m",
    # the paper's own demo config (small LM used by examples/)
    "aiida-demo-110m",
]


def _module_name(arch_id: str) -> str:
    return arch_id.replace("-", "_").replace(".", "_")


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; available: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_module_name(arch_id)}")
    return mod.CONFIG


def list_configs() -> list[str]:
    return list(ARCH_IDS)


# ---------------------------------------------------------------------------
# Reduced configs for CPU smoke tests (same family/topology, tiny sizes)
# ---------------------------------------------------------------------------

def reduced_config(arch_id: str) -> ModelConfig:
    cfg = get_config(arch_id)
    kw: dict = dict(
        d_model=128,
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,
        d_ff=256,
        vocab_size=512,
        num_layers=2,
        attn_impl="direct",
        kv_repeat=1,
        moe_group_size=64,
        mlstm_chunk=32,
    )
    if cfg.family == "moe":
        kw.update(num_experts=4, num_experts_per_tok=2)
    if cfg.kv_lora_rank:
        # latent attention, one dense layer, then expert layers that hold
        # 4 of 8 routed experts (the second half), with shared experts
        kw.update(num_layers=3, num_kv_heads=4, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  moe_d_ff=64, shared_d_ff=128, num_experts=8,
                  num_experts_per_tok=3, experts_held=4, expert_offset=4)
    if "mamba" in cfg.layer_types:
        # Mamba-2 layers around one attention layer; 4 heads of 16 with
        # d_state 16 and chunks of 8; expert layers that hold 4 of 8
        # routed experts (the first half), with a shared expert
        kw.update(num_layers=4, num_kv_heads=2,
                  layer_types=("mamba", "mamba", "attention", "mamba"),
                  ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_chunk=8,
                  moe_d_ff=64, shared_d_ff=128, num_experts=8,
                  num_experts_per_tok=3, experts_held=4, expert_offset=0)
    if "kda" in cfg.layer_types:
        # on top of the latent attention above: a KDA layer with a dense
        # MLP, a KDA and an MLA layer with the expert layer, and a KDA
        # layer after them; 4 KDA heads of 16
        kw.update(num_layers=4, layer_types=("kda", "kda", "attention",
                                             "kda"),
                  kda_heads=4, kda_head_dim=16)
    if cfg.family == "hybrid":
        kw.update(num_layers=3, d_rnn=128, local_window=32)
    if cfg.family == "ssm":
        # keep >= 8 layers so at least one sLSTM position exists
        kw.update(num_layers=8, num_kv_heads=4, d_ff=0)
    if cfg.family == "audio":
        kw.update(num_kv_heads=4, encoder_layers=2, num_frames=16)
    if cfg.family == "vlm":
        kw.update(num_patches=8)
    if cfg.name == "xlstm-350m":
        kw["head_dim"] = 0
    return cfg.replace(**kw)
