"""Dense gated MLPs and Mixture-of-Experts layers.

The MoE layer uses the GShard/Switch grouped-einsum dispatch so it lowers to
clean ``all_to_all`` collectives under GSPMD:

* tokens are reshaped into groups of ``moe_group_size``;
* per group, each expert has capacity ``C = ceil(g·k/E · capacity_factor)``;
* dispatch/combine tensors are (G, g, E, C) one-hots — their memory is
  ``O(tokens · E · C / g)`` which stays modest for the group sizes used.

Two sharding strategies (resolved per architecture):

* ``expert`` (EP): the expert dim of the weights maps to the model axis
  (moonshot: 64 experts / 16). Dispatch einsums induce all_to_alls.
* ``ffn`` (TP-in-expert): experts replicated, each expert's d_ff sharded
  (grok: 8 experts do not divide a 16-way axis, but d_ff=32768 does).

``moe_impl='ragged'`` is the no-drop layer of one expert-parallel chip
(deepseek-v3 / moonlight, granite-4.0-h; ``moe_score`` picks the
router's score function): it routes over all ``num_experts``, computes
only the experts it holds (``experts_held`` from ``expert_offset``) with
a grouped matmul over the (token, choice) pairs sorted by expert, and adds
the shared experts whole. Pairs routed to experts held elsewhere add
nothing here; on one chip the layer runs without its exchange. In decode
with ``decode_impl='pallas'`` the ``moe_decode`` kernel takes the place of
the grouped matmul: it reads only the held experts some row routes to,
in place from the run's stacked matrices.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import ModelConfig, ParamSpec, act_fn, shard


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def make_mlp_specs(cfg: ModelConfig, width: int = 0) -> dict[str, ParamSpec]:
    d, f = cfg.d_model, width or cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), ("embed", "ffn")),
        "w_up": ParamSpec((d, f), ("embed", "ffn")),
        "w_down": ParamSpec((f, d), ("ffn", "embed")),
    }


def mlp_forward(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array) -> jax.Array:
    dt = x.dtype
    act = act_fn(cfg.mlp_act)
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    h = act(g) * u
    h = shard(h, "batch", None, "ffn_sharded")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

def make_moe_specs(cfg: ModelConfig) -> dict[str, ParamSpec]:
    if cfg.moe_impl == "ragged":
        return _make_ragged_specs(cfg)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    if cfg.moe_sharding == "expert":
        # EP: the expert dim takes the model axis; per-expert ffn replicated.
        ax = ("expert_sharded", "embed", "moe_ffn")
        ax_down = ("expert_sharded", "moe_ffn", "embed")
    else:  # TP-in-expert: experts replicated, per-expert ffn takes model axis
        ax = ("expert", "embed", "moe_ffn")
        ax_down = ("expert", "moe_ffn", "embed")
    return {
        "router": ParamSpec((d, e), ("embed", None), f32_at_use=True),
        "w_gate": ParamSpec((e, d, f), ax),
        "w_up": ParamSpec((e, d, f), ax),
        "w_down": ParamSpec((e, f, d), ax_down),
    }


def _capacity(cfg: ModelConfig, group: int) -> int:
    c = int(math.ceil(group * cfg.num_experts_per_tok / cfg.num_experts
                      * cfg.moe_capacity_factor))
    return max(4, min(group, c))


def moe_forward(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """Returns (output, aux_load_balance_loss). x: (B, S, D)."""
    if cfg.moe_impl == "ragged":
        return moe_ragged_forward(cfg, p, x)[0], jnp.zeros((), jnp.float32)
    dt = x.dtype
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    tokens = b * s
    g = min(cfg.moe_group_size, tokens)
    while tokens % g:
        g //= 2
    n_groups = tokens // g
    cap = _capacity(cfg, g)

    xt = x.reshape(n_groups, g, d)
    xt = shard(xt, "moe_groups", None, None)

    router_logits = jnp.einsum(
        "gtd,de->gte", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)            # (G, g, E)

    # --- aux loss (Switch-style load balancing) -----------------------------
    density = jnp.mean(probs, axis=1)                          # (G, E)
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32)
    frac = jnp.mean(top1, axis=1)                              # (G, E)
    aux_loss = jnp.mean(jnp.sum(density * frac, axis=-1)) * e

    # --- top-k selection -----------------------------------------------------
    topw, topi = lax.top_k(probs, k)                           # (G, g, k)
    topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)

    # position of each (token, choice) inside its expert's capacity buffer
    sel = jax.nn.one_hot(topi, e, dtype=jnp.float32)           # (G, g, k, E)
    # rank tokens per expert: flatten (g, k) in priority order (token-major)
    sel_flat = sel.reshape(n_groups, g * k, e)
    pos_in_expert = jnp.cumsum(sel_flat, axis=1) - sel_flat    # (G, g*k, E)
    pos_in_expert = pos_in_expert.reshape(n_groups, g, k, e)
    within_cap = pos_in_expert < cap
    cap_slot = jax.nn.one_hot(
        jnp.sum(pos_in_expert * sel, axis=-1).astype(jnp.int32),
        cap, dtype=jnp.float32)                                # (G, g, k, C)
    # One-hot routing tensors are piecewise constant: their cotangents are
    # zero a.e. but, if left differentiable, XLA materialises fp32
    # (G,g,E,C)-shaped gradient paths (44 GB/layer/device of all-reduce for
    # grok-1 — measured). Router gradient flows through `topw` only.
    sel_live = lax.stop_gradient(sel * within_cap)             # (G, g, k, E)
    cap_slot = lax.stop_gradient(cap_slot)                     # (G, g, k, C)
    dispatch = jnp.einsum("gtke,gtkc->gtec", sel_live, cap_slot)
    combine = jnp.einsum("gtke,gtkc,gtk->gtec", sel_live, cap_slot, topw)

    dispatch = dispatch.astype(dt)
    expert_in = jnp.einsum("gtec,gtd->egcd", dispatch, xt)     # (E, G, C, D)
    expert_in = shard(expert_in, "expert_sharded", "moe_groups", None, None)

    act = act_fn(cfg.mlp_act)
    hg = jnp.einsum("egcd,edf->egcf", expert_in, p["w_gate"].astype(dt))
    hu = jnp.einsum("egcd,edf->egcf", expert_in, p["w_up"].astype(dt))
    h = act(hg) * hu
    h = shard(h, "expert_sharded", "moe_groups", None, "moe_ffn_act")
    expert_out = jnp.einsum("egcf,efd->egcd", h, p["w_down"].astype(dt))
    # NO sharding constraint on expert_out: under TP-in-expert its f-
    # contraction leaves per-shard partial sums, and constraining it here
    # forces an all-reduce of the fat (E,G,C,D) capacity tensor (measured:
    # 44 GB/layer/device fp32 on grok-1). Leaving it unconstrained lets
    # GSPMD carry the partial sums through the combine einsum and reduce
    # the (G,g,D) token tensor instead — ~5x fewer wire bytes.

    out = jnp.einsum("gtec,egcd->gtd", combine.astype(dt), expert_out)
    out = shard(out, "moe_groups", None, None)
    return out.reshape(b, s, d), aux_loss.astype(jnp.float32)


# ---------------------------------------------------------------------------
# No-drop expert layer over the experts held here
# ---------------------------------------------------------------------------

def _make_ragged_specs(cfg: ModelConfig) -> dict:
    d, e = cfg.d_model, cfg.num_experts
    f, n = cfg.moe_d_ff or cfg.d_ff, cfg.held_experts
    specs = {
        "router": ParamSpec((d, e), ("embed", None), f32_at_use=True),
        "w_gate": ParamSpec((n, d, f), ("expert_sharded", "embed", "moe_ffn")),
        "w_up": ParamSpec((n, d, f), ("expert_sharded", "embed", "moe_ffn")),
        "w_down": ParamSpec((n, f, d), ("expert_sharded", "moe_ffn", "embed")),
    }
    if cfg.moe_score == "sigmoid":
        specs["router_bias"] = ParamSpec((e,), (None,), init="zeros",
                                         f32_at_use=True)
    if cfg.shared_d_ff:
        specs["shared"] = make_mlp_specs(cfg, cfg.shared_d_ff)
    return specs


def route(cfg: ModelConfig, p: dict[str, jax.Array], xt: jax.Array
          ) -> tuple[jax.Array, jax.Array]:
    """(experts (T, k), gate weights (T, k) float32) of tokens xt (T, D),
    computed in float32. ``moe_score`` 'sigmoid': sigmoid scores, the k
    chosen by score plus the correction bias, gated by their unbiased
    scores normalised over the k and scaled by ``moe_routed_scale``.
    'softmax': the k largest logits, gated by their softmax. The router's
    product runs at ``HIGHEST``: a TPU's default precision would round its
    float32 operands to bfloat16 and flip choices that are no
    near-ties."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if cfg.moe_score == "softmax":
        top, idx = lax.top_k(logits, cfg.num_experts_per_tok)
        return idx, jax.nn.softmax(top, axis=-1)
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                       cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.moe_routed_scale


def moe_routed(cfg: ModelConfig, p: dict[str, jax.Array], xt: jax.Array
               ) -> jax.Array:
    """The held experts' part of the routed output, (T, D) float32.

    Every (token, choice) pair is sorted by expert, the held experts'
    pairs first, so each held expert's rows are one contiguous group of
    a grouped matmul (``lax.ragged_dot``); the pairs of experts held
    elsewhere trail the groups and are not multiplied. No capacity: a
    group is as long as its expert's pairs."""
    dt = xt.dtype
    t, d = xt.shape
    k, n = cfg.num_experts_per_tok, cfg.held_experts
    idx, w = route(cfg, p, xt)
    local = idx.reshape(-1) - cfg.expert_offset
    held = (local >= 0) & (local < n)
    group = jnp.where(held, local, n)                  # elsewhere sorts last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(group[None, :] == jnp.arange(n)[:, None], axis=1,
                    dtype=jnp.int32)
    tok = order // k
    rows = xt[tok]                                     # (T*k, D)
    act = act_fn(cfg.mlp_act)
    g = lax.ragged_dot(rows, p["w_gate"].astype(dt), sizes)
    u = lax.ragged_dot(rows, p["w_up"].astype(dt), sizes)
    y = lax.ragged_dot(act(g) * u, p["w_down"].astype(dt), sizes)
    gate = w.reshape(-1)[order]
    # rows past the groups are not defined by the grouped matmul: select
    y = jnp.where(held[order][:, None], y.astype(jnp.float32) * gate[:, None],
                  0.0)
    return jnp.zeros((t, d), jnp.float32).at[tok].add(y)


def moe_ragged_forward(cfg: ModelConfig, p: dict[str, jax.Array],
                       x: jax.Array, layer=None) -> tuple[jax.Array, jax.Array]:
    """No-drop expert layer: the held experts' part plus the shared
    experts. x: (B, S, D). Returns (output, the held experts read, int32).

    Without ``layer``, p's expert matrices are the layer's and
    ``moe_routed`` reads all of them. With ``layer`` (decode), they are the
    run's whole stacks, and the ``moe_decode`` kernel reads, in place at
    ``layer``, only the held experts that some row routes to."""
    from repro.kernels.moe_decode import ops as md_ops

    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe"):
        if layer is None:
            out, read = moe_routed(cfg, p, xt), jnp.int32(cfg.held_experts)
        else:
            idx, w = route(cfg, p, xt)
            out, read = md_ops.moe_decode(
                xt, idx - cfg.expert_offset, w, p["w_gate"], p["w_up"],
                p["w_down"], layer, act=cfg.mlp_act)
        out = out.reshape(b, s, d).astype(x.dtype)
        if cfg.shared_d_ff:
            out = out + mlp_forward(cfg, p["shared"], x)
    return out, read
