"""Decoder-only LM covering the dense, MoE and VLM families, and the
hybrid layers that interleave Mamba-2 (granitemoehybrid) or gated
delta-rule (KDA, kimi_linear) mixers with attention.

The stack is a list of *runs* (``layer_runs``): maximal stretches of
consecutive layers of one kind, in published order. A layer's kind is its
mixer (attention, a Mamba-2 or a KDA mixer) and its feed-forward part (a
dense MLP, or the expert layer). Each run executes with ``jax.lax.scan``
over parameters stacked along a leading ``layers`` dimension: the lowered
HLO contains one layer body per run regardless of depth, which keeps
compile time flat in depth and is the standard production pattern
(MaxText et al.).

* A model with neither ``layer_types`` nor ``first_dense_layers`` is one
  run, ``layers``, and its cache is that run's tree.
* ``first_dense_layers`` alone (deepseek-v3, moonlight) makes two runs:
  ``dense_layers`` with a dense MLP, then ``layers`` with the expert layer.
* ``layer_types`` (granite-4.0-h, kimi-linear) names each layer's mixer;
  a run is keyed by mixer and MLP kind, and named by its mixer and the
  index of its first layer (``mamba0``, ``attention5``, ``mamba6`` ...;
  with ``first_dense_layers`` 1, ``kda0`` with a dense MLP, then ``kda1``,
  ``attention3`` ... with the expert layer).

With more than one run, the parameter tree and the cache hold one stacked
tree per run under the run's name: the KV or latent cache of an attention
run, the recurrent state of a Mamba run (``models/mamba2.py``: conv
inputs and the SSM state) or of a KDA run (``models/kda.py``: conv inputs
and the matrix state), all with batch on axis 1. Prefill and decode carry
each run's stacked cache or state through its scan and update it in
place, layer by layer. Decode with ``decode_impl='pallas'`` keeps the
held-expert layer's matrices whole the same way: the ``moe_decode``
kernel reads each layer's routed experts from the run's stacks, and
nothing slices a layer out.

Remat (activation checkpointing) wraps the scanned body in training;
``cfg.remat_policy`` names the policy.
"""

from __future__ import annotations

import itertools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import attention as attn
from repro.models import kda, mamba2
from repro.models import mlp as mlp_mod
from repro.models.common import (
    ModelConfig,
    ParamSpec,
    maybe_remat,
    rms_norm,
    shard,
    softmax_cross_entropy,
    stack_specs,
)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

class Run(NamedTuple):
    """A run of consecutive layers of one kind."""

    name: str           # its key in the parameter and cache trees
    mixer: str          # 'attention' | 'mamba' | 'kda'
    moe: bool           # the expert layer, or a dense MLP
    layers: int


#: each mixer's (norm key, parameter key) in a layer's tree
MIXERS = {"attention": ("ln_attn", "attn"), "mamba": ("ln_ssm", "ssm"),
          "kda": ("ln_kda", "kda")}
#: each mixer's parameter specs
_MIXER_SPECS = {"attention": attn.make_attn_specs,
                "mamba": mamba2.make_ssm_specs, "kda": kda.make_kda_specs}
#: the cache leaves that hold a slot's recurrent state
STATE_LEAVES = frozenset(mamba2.STATE_LEAVES + kda.STATE_LEAVES)


def layer_runs(cfg: ModelConfig) -> list[Run]:
    """The stack's runs in the order they run."""
    moe = cfg.family == "moe"
    n0 = cfg.first_dense_layers
    if cfg.layer_types:
        kinds = [(mixer, moe and i >= n0) for i, mixer in
                 enumerate(cfg.layer_types[:cfg.num_layers])]
        runs, first = [], 0
        for (mixer, is_moe), group in itertools.groupby(kinds):
            n = len(tuple(group))
            runs.append(Run(f"{mixer}{first}", mixer, is_moe, n))
            first += n
        return runs
    runs = [Run("dense_layers", "attention", False, n0)] if n0 else []
    return runs + [Run("layers", "attention", moe, cfg.num_layers - n0)]


def _one_run(runs: list[Run]) -> bool:
    """The stack is the single run ``layers``, whose cache is its tree."""
    return len(runs) == 1 and runs[0].name == "layers"


def make_layer_specs(cfg: ModelConfig, *, dense: bool = False,
                     mixer: str = "attention") -> dict[str, Any]:
    """One layer; ``dense`` for a layer with a dense MLP in an MoE
    model."""
    norm, key = MIXERS[mixer]
    specs: dict[str, Any] = {
        norm: ParamSpec((cfg.d_model,), ("embed",), init="ones",
                        f32_at_use=True),
        "ln_mlp": ParamSpec((cfg.d_model,), ("embed",), init="ones",
                            f32_at_use=True),
        key: _MIXER_SPECS[mixer](cfg),
    }
    if cfg.family == "moe" and not dense:
        specs["moe"] = mlp_mod.make_moe_specs(cfg)
    else:
        specs["mlp"] = mlp_mod.make_mlp_specs(cfg)
    return specs


def make_lm_specs(cfg: ModelConfig) -> dict[str, Any]:
    vp = cfg.padded_vocab
    specs: dict[str, Any] = {
        "embedding": ParamSpec((vp, cfg.d_model), ("vocab", "embed")),
        "ln_final": ParamSpec((cfg.d_model,), ("embed",), init="ones",
                              f32_at_use=True),
    }
    for run in layer_runs(cfg):
        specs[run.name] = stack_specs(
            make_layer_specs(cfg, dense=not run.moe, mixer=run.mixer),
            run.layers)
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, vp), ("embed", "vocab"))
    if cfg.family == "vlm":
        specs["mm_projector"] = ParamSpec(
            (cfg.d_model, cfg.d_model), ("embed", "embed_out"))
    return specs


# ---------------------------------------------------------------------------
# Layer body
# ---------------------------------------------------------------------------

def _layer_forward(cfg: ModelConfig, p: dict[str, Any], x: jax.Array,
                   positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Pre-norm block. Returns (x, aux_loss)."""
    rm = cfg.residual_multiplier
    if "ssm" in p:
        h = rms_norm(x, p["ln_ssm"], cfg.norm_eps)
        a, _, _ = mamba2.ssm_forward(cfg, p["ssm"], h)
    elif "kda" in p:
        h = rms_norm(x, p["ln_kda"], cfg.norm_eps)
        a, _, _ = kda.kda_forward(cfg, p["kda"], h)
    else:
        h = rms_norm(x, p["ln_attn"], cfg.norm_eps)
        a = attn.attn_forward(cfg, p["attn"], h, positions, causal=True)
    x = x + rm * a
    h = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    m, aux = _ffn(cfg, p, h)
    x = x + rm * m
    x = shard(x, "batch", "act_seq", None)
    return x, aux


def _ffn(cfg: ModelConfig, p: dict[str, Any], h: jax.Array
         ) -> tuple[jax.Array, jax.Array]:
    """The layer's MLP or expert layer; (output, aux loss)."""
    if "moe" in p:
        return mlp_mod.moe_forward(cfg, p["moe"], h)
    return mlp_mod.mlp_forward(cfg, p["mlp"], h), jnp.zeros((), jnp.float32)


def _stack_forward(cfg: ModelConfig, params: dict[str, Any], x: jax.Array,
                   positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    def body(carry, layer_params):
        h, aux = carry
        h, a = _layer_forward(cfg, layer_params, h, positions)
        return (h, aux + a), None

    body = maybe_remat(body, cfg.remat_policy)
    carry = (x, jnp.zeros((), jnp.float32))
    for run in layer_runs(cfg):
        carry, _ = lax.scan(body, carry, params[run.name])
    return carry


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params: dict[str, Any], tokens: jax.Array
                 ) -> jax.Array:
    emb = params["embedding"].astype(cfg.activation_dtype)
    x = jnp.take(emb, tokens, axis=0)
    return x * cfg.embedding_multiplier


def lm_logits(cfg: ModelConfig, params: dict[str, Any], x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["ln_final"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embedding"].astype(x.dtype).T
    else:
        w = params["lm_head"].astype(x.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    logits = shard(logits, "batch", "act_seq", "vocab_sharded")
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


# ---------------------------------------------------------------------------
# Full forward / loss
# ---------------------------------------------------------------------------

def _maybe_prepend_patches(cfg: ModelConfig, params: dict[str, Any],
                           x: jax.Array, batch: dict[str, jax.Array]):
    """VLM family: prepend (projected) precomputed patch embeddings (stub)."""
    if cfg.family != "vlm":
        return x
    patches = batch["patches"].astype(x.dtype)          # (B, P, D) stub
    proj = jnp.einsum("bpd,de->bpe", patches,
                      params["mm_projector"].astype(x.dtype))
    return jnp.concatenate([proj, x], axis=1)


def lm_forward(cfg: ModelConfig, params: dict[str, Any],
               batch: dict[str, jax.Array]) -> tuple[jax.Array, jax.Array]:
    """Returns (logits over the text region, aux_loss)."""
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    x = _maybe_prepend_patches(cfg, params, x, batch)
    x = shard(x, "batch", "act_seq", None)
    s_total = x.shape[1]
    positions = jnp.arange(s_total, dtype=jnp.int32)
    x, aux = _stack_forward(cfg, params, x, positions)
    if cfg.family == "vlm":
        x = x[:, cfg.num_patches:]                       # loss on text only
    logits = lm_logits(cfg, params, x)
    return logits, aux


def lm_loss(cfg: ModelConfig, params: dict[str, Any],
            batch: dict[str, jax.Array]) -> tuple[jax.Array, dict[str, jax.Array]]:
    logits, aux = lm_forward(cfg, params, batch)
    loss, denom = softmax_cross_entropy(logits, batch["labels"],
                                        batch.get("mask"), cfg.vocab_size)
    total = loss + 0.01 * aux
    return total, {"ce_loss": loss, "aux_loss": aux, "tokens": denom}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

def init_lm_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict[str, Any]:
    def one(run: Run):
        if run.mixer == "mamba":
            return mamba2.init_state(cfg, batch, run.layers)
        if run.mixer == "kda":
            return kda.init_state(cfg, batch, run.layers)
        return attn.init_kv_cache(cfg, batch, max_len, layers=run.layers)

    runs = layer_runs(cfg)
    if _one_run(runs):
        return one(runs[0])
    return {run.name: one(run) for run in runs}


def lm_cache_axes(cfg: ModelConfig) -> dict[str, Any]:
    def one(run: Run):
        if run.mixer == "mamba":
            return mamba2.state_axes(cfg)
        if run.mixer == "kda":
            return kda.state_axes(cfg)
        return attn.kv_cache_axes(cfg, layers=True)

    runs = layer_runs(cfg)
    if _one_run(runs):
        return one(runs[0])
    return {run.name: one(run) for run in runs}


#: the expert layer's matrices that decode reads in place from the stack
EXPERT_MATRICES = ("w_gate", "w_up", "w_down")


def expert_layers(cfg: ModelConfig) -> int:
    """Layers with the held-expert layer (``moe_impl='ragged'``), 0 for a
    model without one."""
    if cfg.moe_impl != "ragged":
        return 0
    return sum(run.layers for run in layer_runs(cfg) if run.moe)


def _run_stacks(cfg: ModelConfig, params: dict[str, Any], cache, bodies, x,
                *, whole_experts: bool = False):
    """``bodies[mixer]((h, cache), (layer_params, layer))``, a scan body
    that returns ((h, cache), y), over every run of layers. The run's
    whole stacked cache rides in the scan's carry, and ``layer`` (the
    layer's index, beside its parameters) tells the body which layer of it
    to write and read in place: the donated buffer stays one buffer, and
    no layer is sliced out or stacked back. With ``whole_experts`` the
    expert matrices of a run with the expert layer stay out of the scan's
    xs the same way: the body finds the run's whole stacks in the layer's
    ``moe`` tree and reads layer ``layer`` of them. Returns (x, the new
    cache, each run's stacked ys by name)."""
    runs = layer_runs(cfg)
    caches = {"layers": cache} if _one_run(runs) else dict(cache)
    ys = {}
    for run in runs:
        p, whole = params[run.name], {}
        if whole_experts and run.moe:
            whole = {k: p["moe"][k] for k in EXPERT_MATRICES}
            p = {**p, "moe": {k: v for k, v in p["moe"].items()
                              if k not in whole}}

        def step(carry, xs, body=bodies[run.mixer], whole=whole):
            if whole:
                layer_params, layer = xs
                xs = ({**layer_params,
                       "moe": {**layer_params["moe"], **whole}}, layer)
            return body(carry, xs)

        n = jax.tree.leaves(p)[0].shape[0]
        (x, caches[run.name]), ys[run.name] = lax.scan(
            step, (x, caches[run.name]), (p, np.arange(n, dtype=np.int32)))
    return x, (caches["layers"] if _one_run(runs) else caches), ys


def _after_mixer(cfg: ModelConfig, layer_params, h, a, *, prefill,
                 layer=None):
    """The rest of a layer after its mixer's output ``a``: the residual,
    then the MLP or expert layer and its residual. Returns (h, the held
    experts the expert layer read, or None without one). ``layer`` is
    given where the held-expert layer's matrices are the run's whole
    stacks (``_run_stacks``' ``whole_experts``): the layer to read."""
    h = h + cfg.residual_multiplier * a
    hn = rms_norm(h, layer_params["ln_mlp"], cfg.norm_eps)
    if "moe" in layer_params and cfg.moe_impl == "ragged":
        m, read = mlp_mod.moe_ragged_forward(cfg, layer_params["moe"], hn,
                                             layer)
    else:
        (m, _), read = _ffn(cfg, layer_params, hn), None
    h = h + cfg.residual_multiplier * m
    if prefill:
        h = shard(h, "batch", "act_seq", None)
    return h, read


def lm_prefill(cfg: ModelConfig, params: dict[str, Any],
               batch: dict[str, jax.Array], cache: dict[str, Any]
               ) -> tuple[jax.Array, dict[str, Any]]:
    """Run the prompt through the stack, filling the cache.

    Returns (last-position logits, cache).
    """
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    x = _maybe_prepend_patches(cfg, params, x, batch)
    x = shard(x, "batch", "act_seq", None)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    def body(carry, xs):
        h, cache = carry
        layer_params, layer = xs
        hn = rms_norm(h, layer_params["ln_attn"], cfg.norm_eps)
        a, cache = attn.prefill_into_cache(
            cfg, layer_params["attn"], hn, positions, cache, layer=layer)
        h, _ = _after_mixer(cfg, layer_params, h, a, prefill=True)
        return (h, cache), None

    def body_ssm(carry, xs):
        h, cache = carry
        layer_params, layer = xs
        hn = rms_norm(h, layer_params["ln_ssm"], cfg.norm_eps)
        a, cache = mamba2.ssm_prefill(cfg, layer_params["ssm"], hn, cache,
                                      layer)
        h, _ = _after_mixer(cfg, layer_params, h, a, prefill=True)
        return (h, cache), None

    def body_kda(carry, xs):
        h, cache = carry
        layer_params, layer = xs
        hn = rms_norm(h, layer_params["ln_kda"], cfg.norm_eps)
        a, cache = kda.kda_prefill(cfg, layer_params["kda"], hn, cache,
                                   layer)
        h, _ = _after_mixer(cfg, layer_params, h, a, prefill=True)
        return (h, cache), None

    bodies = {"attention": maybe_remat(body, cfg.remat_policy),
              "mamba": maybe_remat(body_ssm, cfg.remat_policy),
              "kda": maybe_remat(body_kda, cfg.remat_policy)}
    x, new_cache, _ = _run_stacks(cfg, params, cache, bodies, x)
    logits = lm_logits(cfg, params, x[:, -1:])
    return logits, new_cache


def lm_decode_step(cfg: ModelConfig, params: dict[str, Any],
                   cache: dict[str, Any], tokens: jax.Array, pos: jax.Array,
                   *, experts_read: bool = False):
    """One decode step. tokens: (B, 1); pos: scalar current position.
    Returns (logits, cache), and with ``experts_read`` (a model with the
    held-expert layer) the held experts its expert layers read, summed
    (int32). Under ``decode_impl='pallas'`` the ``moe_decode`` kernel
    reads only the routed ones, in place from each run's stacks; the
    direct path reads every held expert of every layer."""
    x = embed_tokens(cfg, params, tokens)
    x = shard(x, "batch", None, None)
    in_place = cfg.decode_impl == "pallas" and expert_layers(cfg) > 0

    def body(carry, xs):
        h, cache = carry
        layer_params, layer = xs
        hn = rms_norm(h, layer_params["ln_attn"], cfg.norm_eps)
        a, cache = attn.attn_decode(cfg, layer_params["attn"], hn, cache, pos,
                                    layer=layer)
        h, read = _after_mixer(cfg, layer_params, h, a, prefill=False,
                               layer=layer if in_place else None)
        return (h, cache), read

    def body_ssm(carry, xs):
        h, cache = carry
        layer_params, layer = xs
        hn = rms_norm(h, layer_params["ln_ssm"], cfg.norm_eps)
        a, cache = mamba2.ssm_decode(cfg, layer_params["ssm"], hn, cache,
                                     layer)
        h, read = _after_mixer(cfg, layer_params, h, a, prefill=False,
                               layer=layer if in_place else None)
        return (h, cache), read

    def body_kda(carry, xs):
        h, cache = carry
        layer_params, layer = xs
        hn = rms_norm(h, layer_params["ln_kda"], cfg.norm_eps)
        a, cache = kda.kda_decode(cfg, layer_params["kda"], hn, cache, layer)
        h, read = _after_mixer(cfg, layer_params, h, a, prefill=False,
                               layer=layer if in_place else None)
        return (h, cache), read

    x, new_cache, reads = _run_stacks(
        cfg, params, cache,
        {"attention": body, "mamba": body_ssm, "kda": body_kda}, x,
        whole_experts=in_place)
    logits = lm_logits(cfg, params, x)
    if not experts_read:
        return logits, new_cache
    read = sum(jnp.sum(r) for r in jax.tree.leaves(reads))
    return logits, new_cache, jnp.asarray(read, jnp.int32)
