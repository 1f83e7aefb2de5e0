"""The jitted training step: microbatched grad accumulation, clipping,
AdamW/Adafactor update. Works for every architecture family via the
ModelBundle interface."""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.registry import ModelBundle
from repro.training import optim as optim_mod
from repro.training.optim import OptimConfig


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optim: OptimConfig = OptimConfig()
    microbatches: int = 1
    seed: int = 0


def init_train_state(bundle: ModelBundle, tcfg: TrainConfig,
                     rng: jax.Array) -> dict[str, Any]:
    params = bundle.init_params(rng)
    return {
        "step": jnp.zeros((), jnp.int32),
        "params": params,
        "opt": optim_mod.opt_init(tcfg.optim, params),
    }


def train_state_shapes(bundle: ModelBundle, tcfg: TrainConfig) -> dict[str, Any]:
    """ShapeDtypeStruct tree (no allocation)."""
    pshapes = bundle.param_shapes()
    opt = jax.eval_shape(
        lambda p: optim_mod.opt_init(tcfg.optim, p), pshapes)
    return {"step": jax.ShapeDtypeStruct((), jnp.int32),
            "params": pshapes, "opt": opt}


def train_state_axes(bundle: ModelBundle, tcfg: TrainConfig) -> dict[str, Any]:
    paxes = bundle.param_axes()
    return {"step": (), "params": paxes,
            "opt": optim_mod.opt_state_axes(tcfg.optim, paxes)}


def _split_microbatches(batch: dict[str, jax.Array], n: int):
    def sp(x):
        b = x.shape[0]
        return x.reshape(n, b // n, *x.shape[1:])
    return jax.tree.map(sp, batch)


def make_train_step(bundle: ModelBundle, tcfg: TrainConfig):
    ocfg = tcfg.optim

    def loss_fn(params, mb):
        loss, metrics = bundle.loss_fn(params, mb)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: dict[str, Any], batch: dict[str, Any]):
        params = state["params"]
        n = tcfg.microbatches
        if n > 1:
            mbs = _split_microbatches(batch, n)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (loss, metrics), grads = grad_fn(params, mb)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / n, g_acc, grads)
                return (g_acc, l_acc + loss / n), metrics

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), metrics_seq = lax.scan(acc_body, (g0, 0.0), mbs)
            metrics = jax.tree.map(lambda m: m[-1], metrics_seq)
        else:
            (loss, metrics), grads = grad_fn(params, batch)

        grads, grad_norm = optim_mod.clip_by_global_norm(grads, ocfg.grad_clip)
        new_params, new_opt, lr = optim_mod.opt_update(
            ocfg, grads, state["opt"], params, state["step"])
        new_state = {"step": state["step"] + 1, "params": new_params,
                     "opt": new_opt}
        out_metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": grad_norm,
            "lr": lr,
            **{k: v.astype(jnp.float32) for k, v in metrics.items()},
        }
        return new_state, out_metrics

    return train_step
