"""Uniform model interface over all architecture families.

Every family exposes the same five entry points so the training loop,
serving loop and launcher treat architectures opaquely (the same
way AiiDA's engine treats simulation codes opaquely — criterion (ii) of the
paper):

    loss_fn(params, batch)                  -> (loss, metrics)
    prefill_fn(params, batch, cache)        -> (logits, cache)
    decode_fn(params, cache, tokens, pos)   -> (logits, cache)
    init_cache(batch_size, max_len)         -> cache pytree
    cache_axes()                            -> logical-axis pytree for cache

An LM's ``decode_fn`` also takes ``experts_read=True``, and then returns
the held experts its expert layers read as a third output
(``transformer.lm_decode_step``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import encdec, rglru, transformer, xlstm
from repro.models.common import ModelConfig, ParamSpec, spec_axes, spec_shapes

LM_FAMILIES = ("dense", "moe", "vlm")
#: why each family that is not an LM family is not served by the
#: scheduler (the LM families serve attention, Mamba-2 and KDA layers)
REFUSED = {
    "hybrid": "the recurrent family 'hybrid' (RG-LRU) keeps per-layer state "
              "lists with batch on axis 0, and its windowed ring buffers "
              "take one position for all rows",
    "ssm": "the recurrent family 'ssm' (xLSTM) keeps per-layer state lists "
           "with batch on axis 0",
    "audio": "the family 'audio' (whisper) cross-attends to an encoder's "
             "output, and the scheduler has no cross-attention cache",
}


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    """One assigned (input-shape) cell."""

    name: str
    kind: str            # train | prefill | decode
    seq_len: int
    global_batch: int

    @property
    def is_train(self) -> bool:
        return self.kind == "train"


SHAPES: dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeCell("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeCell("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeCell("long_500k", "decode", 524288, 1),
}

# Families whose attention cost is sub-quadratic (may run long_500k).
SUBQUADRATIC_FAMILIES = ("hybrid", "ssm")


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    specs: Any
    loss_fn: Callable
    prefill_fn: Callable
    decode_fn: Callable
    init_cache: Callable
    cache_axes: Callable

    # -- parameter helpers ---------------------------------------------------
    def param_shapes(self):
        return spec_shapes(self.specs, self.cfg.weight_dtype)

    def param_axes(self):
        return spec_axes(self.specs)

    def init_params(self, rng: jax.Array):
        from repro.models.common import init_params
        return init_params(rng, self.specs, self.cfg.weight_dtype)

    def init_serving_params(self, rng: jax.Array):
        """``serving_params(cfg, init_params(rng))``, each leaf cast as it
        is drawn, so the device never holds both trees."""
        from repro.models.common import init_params
        _check_served(self.cfg)

        def keep(spec, leaf):
            # wait for the cast: with asynchronous dispatch the host would
            # draw the next leaves while this one's float32 buffers are
            # still held, and several float32 leaves would be live at once
            return jax.block_until_ready(_serving_leaf(self.cfg, spec, leaf))

        return init_params(rng, self.specs, self.cfg.weight_dtype, keep=keep)

    # -- input specs (ShapeDtypeStruct stand-ins, no allocation) -------------
    def batch_struct(self, cell: ShapeCell) -> dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        b, s = cell.global_batch, cell.seq_len
        i32 = jnp.int32
        bf = cfg.activation_dtype
        if cell.kind == "decode":
            return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
        if cfg.family == "vlm":
            s_text = max(s - cfg.num_patches, 16)
            return {
                "tokens": jax.ShapeDtypeStruct((b, s_text), i32),
                "labels": jax.ShapeDtypeStruct((b, s_text), i32),
                "patches": jax.ShapeDtypeStruct((b, cfg.num_patches,
                                                 cfg.d_model), bf),
            }
        if cfg.family == "audio":
            return {
                "tokens": jax.ShapeDtypeStruct((b, s), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32),
                "frames": jax.ShapeDtypeStruct((b, cfg.num_frames,
                                                cfg.d_model), bf),
            }
        return {
            "tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32),
        }

    def batch_axes(self, cell: ShapeCell) -> dict[str, tuple]:
        cfg = self.cfg
        if cell.kind == "decode":
            return {"tokens": ("batch", None)}
        out: dict[str, tuple] = {"tokens": ("batch", None),
                                 "labels": ("batch", None)}
        if cfg.family == "vlm":
            out["patches"] = ("batch", None, None)
        if cfg.family == "audio":
            out["frames"] = ("batch", None, None)
        return out

    def supports_cell(self, cell: ShapeCell) -> tuple[bool, str]:
        if cell.name == "long_500k" and \
                self.cfg.family not in SUBQUADRATIC_FAMILIES:
            return False, "full attention is O(S^2); long_500k assigned to " \
                          "sub-quadratic families only"
        return True, ""


# ---------------------------------------------------------------------------
# Serving storage
# ---------------------------------------------------------------------------

def refused_reason(cfg: ModelConfig) -> str:
    """Why ``cfg``'s family is not served: not the stacked cache tree with
    batch on axis 1 that admission splices."""
    return (f"{REFUSED.get(cfg.family, f'unknown family {cfg.family!r}')}, "
            f"not the stacked tree with batch on axis 1 that admission "
            f"splices")


def _check_served(cfg: ModelConfig) -> None:
    if cfg.family not in LM_FAMILIES:
        raise ValueError(f"serving parameters are declared for {LM_FAMILIES} "
                         f"(attention, Mamba-2 and KDA layers), not "
                         f"{cfg.family!r}: {refused_reason(cfg)}")


def _serving_leaf(cfg: ModelConfig, spec: ParamSpec, leaf: jax.Array):
    dt = cfg.activation_dtype
    if spec.f32_at_use or leaf.dtype == dt:
        return leaf
    return leaf.astype(dt)


def serving_params(cfg: ModelConfig, params: Any) -> Any:
    """The tree a served step reads: every leaf the forward uses at
    ``cfg.activation_dtype`` (embedding, head, attention and MLP/expert
    matrices and biases, projector) stored in it; the leaves it reads in
    float32 (``ParamSpec.f32_at_use``: norm scales, the MoE router) as
    given. The forward casts each matrix at use, so it multiplies the same
    rounded values either way, but no longer converts every weight in
    every step. A leaf already in its dtype is passed through, not copied."""
    _check_served(cfg)
    return jax.tree.map(functools.partial(_serving_leaf, cfg),
                        transformer.make_lm_specs(cfg), params,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Family wiring
# ---------------------------------------------------------------------------

def build(cfg: ModelConfig) -> ModelBundle:
    if cfg.family in LM_FAMILIES:
        return ModelBundle(
            cfg=cfg,
            specs=transformer.make_lm_specs(cfg),
            loss_fn=lambda p, b: transformer.lm_loss(cfg, p, b),
            prefill_fn=lambda p, b, c: transformer.lm_prefill(cfg, p, b, c),
            decode_fn=lambda p, c, t, pos, **kw: transformer.lm_decode_step(
                cfg, p, c, t, pos, **kw),
            init_cache=lambda bsz, ml: transformer.init_lm_cache(cfg, bsz, ml),
            cache_axes=lambda: transformer.lm_cache_axes(cfg),
        )
    if cfg.family == "hybrid":
        return ModelBundle(
            cfg=cfg,
            specs=rglru.make_griffin_specs(cfg),
            loss_fn=lambda p, b: rglru.griffin_loss(cfg, p, b),
            prefill_fn=lambda p, b, c: rglru.griffin_prefill(cfg, p, b, c),
            decode_fn=lambda p, c, t, pos: rglru.griffin_decode_step(
                cfg, p, c, t, pos),
            init_cache=lambda bsz, ml: rglru.init_griffin_state(cfg, bsz, ml),
            cache_axes=lambda: rglru.griffin_state_axes(cfg),
        )
    if cfg.family == "ssm":
        return ModelBundle(
            cfg=cfg,
            specs=xlstm.make_xlstm_specs(cfg),
            loss_fn=lambda p, b: xlstm.xlstm_loss(cfg, p, b),
            prefill_fn=lambda p, b, c: xlstm.xlstm_prefill(cfg, p, b, c),
            decode_fn=lambda p, c, t, pos: xlstm.xlstm_decode_step(
                cfg, p, c, t, pos),
            init_cache=lambda bsz, ml: xlstm.init_xlstm_state(cfg, bsz, ml),
            cache_axes=lambda: xlstm.xlstm_state_axes(cfg),
        )
    if cfg.family == "audio":
        return ModelBundle(
            cfg=cfg,
            specs=encdec.make_whisper_specs(cfg),
            loss_fn=lambda p, b: encdec.whisper_loss(cfg, p, b),
            prefill_fn=lambda p, b, c: encdec.whisper_prefill(cfg, p, b, c),
            decode_fn=lambda p, c, t, pos: encdec.whisper_decode_step(
                cfg, p, c, t, pos),
            init_cache=lambda bsz, ml: encdec.init_whisper_cache(cfg, bsz, ml),
            cache_axes=lambda: encdec.whisper_cache_axes(cfg),
        )
    raise ValueError(f"unknown family {cfg.family!r}")
