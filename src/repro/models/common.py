"""Common building blocks shared by every architecture family.

Everything here is pure JAX (no flax): parameters are plain pytrees of
``jnp.ndarray`` leaves, and each parameter tree has a parallel *logical-axis*
tree (tuples of axis names) consumed by :mod:`repro.distributed.sharding` to
derive ``PartitionSpec`` trees for any mesh.

Design notes
------------
* Parameters are stored in ``param_dtype`` (fp32 master copies) and cast to
  ``dtype`` (bf16) at use — the standard mixed-precision recipe. A leaf the
  forward reads in float32 instead (norm scales, the MoE router) says so in
  its spec (``ParamSpec.f32_at_use``); serving stores every other leaf in
  ``dtype`` once (``registry.serving_params``).
* Homogeneous layer stacks carry a leading ``layers`` dimension and are
  executed with ``jax.lax.scan`` so the HLO contains one layer body
  regardless of depth, which keeps compile time flat in depth.
* ``shard(x, *axes)`` inserts ``with_sharding_constraint`` with *logical*
  axes; it is a no-op outside a mesh context, so CPU unit tests run the
  exact same code path.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

VOCAB_PAD_MULTIPLE = 256


def pad_vocab(vocab_size: int, multiple: int = VOCAB_PAD_MULTIPLE) -> int:
    """Pad the embedding table so it divides any reasonable model axis."""
    return int(math.ceil(vocab_size / multiple) * multiple)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A single config type covering all assigned architecture families."""

    name: str
    family: str                      # dense | moe | hybrid | vlm | audio | ssm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads

    # --- attention options -------------------------------------------------
    qk_norm: bool = False            # qwen3-style per-head RMSNorm on q/k
    qkv_bias: bool = False           # qwen2-style bias on qkv projections
    rope_theta: float = 10_000.0
    use_rope: bool = True
    attn_impl: str = "direct"        # direct | chunked | pallas
    attn_q_block: int = 512          # chunked/pallas q tile
    attn_kv_block: int = 512         # chunked/pallas kv tile
    attn_softcap: float = 0.0        # grok-style logit soft-capping

    # --- mlp ---------------------------------------------------------------
    mlp_act: str = "silu"            # silu (SwiGLU) | gelu (GeGLU) | gelu_mlp

    # --- scalar multipliers (granite) ---------------------------------------
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0   # 0 -> default 1/sqrt(head_dim)
    logits_scaling: float = 1.0

    tie_embeddings: bool = False
    norm_eps: float = 1e-6

    # --- MoE ----------------------------------------------------------------
    num_experts: int = 0             # the router's width: every routed expert
    num_experts_per_tok: int = 0
    moe_group_size: int = 1024       # GShard-style dispatch group size
    moe_capacity_factor: float = 1.25
    # 'gshard': softmax routing, capacity-bounded dispatch over all
    # experts (drops tokens past capacity). 'ragged': deepseek-v3's layer,
    # sigmoid scores selected with a correction bias (noaux_tc) and gated
    # without it, the k gates normalised; sorted (token, choice) pairs
    # through a grouped matmul over the experts held here, dropping nothing
    moe_impl: str = "gshard"
    moe_d_ff: int = 0                # routed expert width (0 -> d_ff)
    shared_d_ff: int = 0             # shared experts as one MLP (0 -> none)
    moe_routed_scale: float = 1.0    # routed_scaling_factor (ragged)
    # expert parallelism as one chip sees it: this layer holds routed
    # experts [expert_offset, expert_offset + experts_held); 0 -> all
    experts_held: int = 0
    expert_offset: int = 0
    first_dense_layers: int = 0      # leading layers with a dense MLP of d_ff

    # softmax top-k scores (granitemoe: the gate is the softmax over the k
    # largest router logits) or sigmoid scores with a correction bias
    # (deepseek-v3); ragged layer only
    moe_score: str = "sigmoid"

    # --- layer kinds in order (granitemoehybrid, kimi_linear) -----------------
    # one of 'attention' | 'mamba' | 'kda' per layer, the first
    # ``num_layers`` read; () -> every layer attention
    layer_types: tuple[str, ...] = ()

    # --- Mamba-2 mixer (models/mamba2.py) --------------------------------------
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0               # d_state: columns of B and C
    ssm_groups: int = 1              # groups of heads that share B and C
    ssm_conv: int = 4                # causal depthwise conv taps
    ssm_chunk: int = 256             # chunk of the SSD prefill

    # --- gated delta-rule mixer (KDA, models/kda.py) --------------------------
    kda_heads: int = 0
    kda_head_dim: int = 0            # d_k = d_v of a head
    kda_conv: int = 4                # short causal depthwise conv taps

    # --- latent attention (MLA, deepseek-v2/v3; q_lora_rank null) -----------
    kv_lora_rank: int = 0            # > 0: latent attention, latent cache
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0        # one rope key head shared by all heads
    v_head_dim: int = 0
    latent_norm_eps: float = 1e-6    # the latent's RMSNorm (kv_a_layernorm)

    # --- hybrid (recurrentgemma / griffin) ----------------------------------
    block_pattern: tuple[str, ...] = ()   # e.g. ('rglru', 'rglru', 'attn')
    local_window: int = 0
    d_rnn: int = 0
    conv_width: int = 4
    rnn_blocks: int = 16            # block-diagonal RG-LRU gate blocks

    # --- xlstm ---------------------------------------------------------------
    mlstm_proj_factor: float = 2.0
    slstm_conv_width: int = 4
    mlstm_chunk: int = 128

    # --- enc-dec (whisper backbone) ------------------------------------------
    encoder_layers: int = 0
    num_frames: int = 0              # stub conv-frontend output length

    # --- vlm (llava backbone) -------------------------------------------------
    num_patches: int = 0             # stub anyres patch-embedding count

    # --- numerics / infra -----------------------------------------------------
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat_policy: str = "nothing_saveable"
    use_pallas: bool = False
    # decode-attention inner product: 'direct' (einsum over the full cache)
    # or 'pallas' (the flash-decode kernel, ragged per-row kv lengths).
    decode_impl: str = "direct"
    # the TPU compiler's scoped VMEM for the serving prefill, in KiB; 0
    # keeps the compiler's default (16 MiB)
    prefill_vmem_kib: int = 0
    # Number of physical replications of KV heads so the KV-head dim divides
    # the model axis. 1 means no repetition. Set by the sharding resolver.
    kv_repeat: int = 1
    # attention sharding strategy: 'heads' (TP) or 'sequence' (context-parallel)
    attn_sharding: str = "heads"
    # MoE sharding strategy: 'expert' (EP) or 'ffn' (TP-in-expert)
    moe_sharding: str = "expert"

    # ------------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size)

    @property
    def held_experts(self) -> int:
        """Routed experts whose weights this layer holds."""
        return self.experts_held or self.num_experts

    @property
    def ssm_inner(self) -> int:
        """Channels of the Mamba-2 mixer: heads x head width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the causal conv runs over: x, then B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def kda_width(self) -> int:
        """Channels of each of the KDA mixer's q, k and v: heads x head
        width."""
        return self.kda_heads * self.kda_head_dim

    @property
    def latent_width(self) -> int:
        """Columns of a latent cache row: the latent, then the rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_heads_eff(self) -> int:
        """KV heads after physical repetition for shardability."""
        return self.num_kv_heads * self.kv_repeat

    @property
    def activation_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    @property
    def kv_cache_dtype(self) -> str:
        """The KV cache's storage dtype, which is the activation dtype
        (read-only: configuration files that state it are checked
        against it)."""
        return self.dtype

    @property
    def weight_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Logical-axis annotated parameter trees
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Shape + logical axes + init for one parameter leaf."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones | rglru_lambda
    scale: float = 1.0
    # the forward reads this leaf in float32, not at the activation dtype;
    # declared for the LM families, whose trees serving stores in ``dtype``
    f32_at_use: bool = False

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


ParamTree = Any      # pytree of jnp.ndarray
SpecTree = Any       # pytree of ParamSpec


def spec_shapes(spec_tree: SpecTree, dtype: jnp.dtype) -> Any:
    """ShapeDtypeStruct tree for a spec tree (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def spec_axes(spec_tree: SpecTree) -> Any:
    return jax.tree.map(
        lambda s: s.axes, spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def init_params(rng: jax.Array, spec_tree: SpecTree, dtype: jnp.dtype,
                keep: Callable | None = None) -> ParamTree:
    """Materialise a parameter tree (only used for real, small runs).

    ``keep(spec, leaf)``, when given, maps each leaf as it is drawn to the
    array the tree holds, before the next one is drawn: serving keeps its
    cast copy, so the whole tree never exists in ``dtype``."""
    leaves, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, s in zip(keys, leaves):
        if s.init == "zeros":
            out.append(jnp.zeros(s.shape, dtype))
        elif s.init == "ones":
            out.append(jnp.ones(s.shape, dtype))
        elif s.init == "rglru_lambda":
            # Initialise so that a = sigmoid(lambda)^(8*r) lands in (0.9, 0.999)
            u = jax.random.uniform(key, s.shape, dtype, 0.9, 0.999)
            a2 = u ** (1.0 / 8.0)
            out.append(jnp.log(a2 / (1.0 - a2)))
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            std = s.scale / math.sqrt(max(1, fan_in))
            out.append(std * jax.random.normal(key, s.shape, dtype))
        if keep is not None:
            out[-1] = keep(s, out[-1])
    return jax.tree.unflatten(treedef, out)


def stacked(spec: ParamSpec, layers: int) -> ParamSpec:
    """Add a leading scanned-layer dimension to a spec."""
    return ParamSpec(
        shape=(layers, *spec.shape),
        axes=("layers", *spec.axes),
        init=spec.init,
        scale=spec.scale,
        f32_at_use=spec.f32_at_use,
    )


def stack_specs(specs: Mapping[str, Any], layers: int) -> Any:
    return jax.tree.map(
        lambda s: stacked(s, layers), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


# ---------------------------------------------------------------------------
# Logical sharding constraints
# ---------------------------------------------------------------------------

class _AxisRulesState:
    """Thread-global logical→mesh axis rules; no-op when not installed."""

    def __init__(self) -> None:
        self.rules: dict[str, tuple[str, ...] | str | None] | None = None
        self.mesh = None

    def install(self, mesh, rules) -> None:
        self.mesh = mesh
        self.rules = dict(rules)

    def clear(self) -> None:
        self.mesh = None
        self.rules = None


_AXIS_RULES = _AxisRulesState()


def install_axis_rules(mesh, rules) -> None:
    _AXIS_RULES.install(mesh, rules)


def clear_axis_rules() -> None:
    _AXIS_RULES.clear()


class axis_rules:
    """Context manager installing logical axis rules for `shard()`."""

    def __init__(self, mesh, rules):
        self.mesh, self.rules = mesh, rules

    def __enter__(self):
        install_axis_rules(self.mesh, self.rules)
        return self

    def __exit__(self, *exc):
        clear_axis_rules()
        return False


def logical_to_spec(axes: Sequence[str | None]):
    """Translate logical axis names into a PartitionSpec via active rules."""
    from jax.sharding import PartitionSpec as P

    rules = _AXIS_RULES.rules or {}
    parts = []
    for ax in axes:
        r = rules.get(ax) if ax is not None else None
        parts.append(r)
    return P(*parts)


def active_mesh():
    """The mesh of the installed axis rules; None when no rules are active."""
    return _AXIS_RULES.mesh if _AXIS_RULES.rules is not None else None


def shard(x: jax.Array, *axes: str | None) -> jax.Array:
    """Apply a logical sharding constraint; identity when no rules active."""
    if _AXIS_RULES.rules is None or _AXIS_RULES.mesh is None:
        return x
    spec = logical_to_spec(axes)
    from jax.sharding import NamedSharding

    return lax.with_sharding_constraint(x, NamedSharding(_AXIS_RULES.mesh, spec))


# ---------------------------------------------------------------------------
# Primitive layers
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def rope(q: jax.Array, k: jax.Array, positions: jax.Array, theta: float):
    """Rotary embeddings. q: (..., S, H, hd), positions: (..., S)."""
    hd = q.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        xr1 = x1 * cos - x2 * sin
        xr2 = x2 * cos + x1 * sin
        return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    """Whisper-style fixed sinusoidal position embeddings."""
    log_timescale = math.log(10_000.0) / (dim // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(dim // 2, dtype=jnp.float32))
    scaled = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1)


_ACTS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "silu": jax.nn.silu,
    "gelu": partial(jax.nn.gelu, approximate=True),
    "gelu_exact": partial(jax.nn.gelu, approximate=False),
}


def act_fn(name: str) -> Callable[[jax.Array], jax.Array]:
    return _ACTS[name]


# ---------------------------------------------------------------------------
# Remat policy resolution
# ---------------------------------------------------------------------------

def remat_policy(name: str):
    """Map a policy name onto a jax.checkpoint policy (None = save nothing)."""
    cp = jax.checkpoint_policies
    table = {
        "none": None,                         # plain jax.checkpoint default
        "nothing_saveable": cp.nothing_saveable,
        "dots_saveable": cp.dots_saveable,
        "dots_with_no_batch_dims_saveable": cp.dots_with_no_batch_dims_saveable,
        "everything_saveable": cp.everything_saveable,
    }
    if name not in table:
        raise ValueError(f"unknown remat policy {name!r}; options {sorted(table)}")
    return table[name]


def maybe_remat(fn, policy_name: str):
    if policy_name == "off":
        return fn
    policy = remat_policy(policy_name)
    if policy is None:
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# Cross-entropy loss with padded-vocab masking
# ---------------------------------------------------------------------------

def softmax_cross_entropy(
    logits: jax.Array,       # (B, S, Vp) any float dtype
    labels: jax.Array,       # (B, S) int32
    mask: jax.Array | None,  # (B, S) float/bool, 1 = contributes
    vocab_size: int,
) -> tuple[jax.Array, jax.Array]:
    """Mean CE over masked tokens; padded vocab entries are neutralised."""
    logits = logits.astype(jnp.float32)
    vp = logits.shape[-1]
    if vp != vocab_size:
        pad_bias = jnp.where(
            jnp.arange(vp) < vocab_size, 0.0, -1e30
        ).astype(jnp.float32)
        logits = logits + pad_bias
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    total = jnp.sum(nll * mask)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return total / denom, denom
