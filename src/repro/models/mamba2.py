"""Mamba-2 mixer (SSD) of the granitemoehybrid family, with its per-slot
recurrent state for serving.

Per head h and position t, with ``x, B, C`` the causal depthwise conv of
their projections through SiLU, ``dt = softplus(dt_raw + dt_bias)`` and
``A = -exp(A_log)``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        (P, N)
    y_t = S_t C_t + D x_t
    out = out_proj(RMSNorm(y * silu(z)))                 (gated, per group)

Prefill computes the sequence in the chunked SSD form: within a chunk of
``ssm_chunk`` positions, matrix products over the chunk's decay matrix;
between chunks, the state passed on by a scan. A prompt that is no
multiple of the chunk is padded at its end with ``dt = 0``, which leaves
the state as it is. Decode is the single-step update
(``kernels/ssm_decode`` with ``decode_impl='pallas'``).

The state of a slot, carried for every Mamba layer of a run in one
stacked tree beside the attention runs' KV caches:

* ``conv``: the last ``ssm_conv - 1`` inputs of the conv, (L, B, K-1,
  conv channels), in the activation dtype (the conv's inputs are the
  projections' outputs);
* ``ssm``: S in float32, laid out ``(L, B, N, H*P)`` (the kernel's layout:
  ``ssm[n, h*P + p]`` is ``S[h, p, n]``). Rounding it to bfloat16 at every
  token would compound over a long prompt.

The decay sums and their exponentials are float32 throughout. The
projections' leaves are separate (z, x, B, C, dt), so no drawn leaf is
larger than the mixer's widest matrix.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import ModelConfig, ParamSpec, shard

#: the cache leaves that hold a slot's recurrent state
STATE_LEAVES = ("conv", "ssm")


def make_ssm_specs(cfg: ModelConfig) -> dict[str, ParamSpec]:
    d, h, n = cfg.d_model, cfg.ssm_heads, cfg.ssm_groups * cfg.ssm_state
    inner, conv = cfg.ssm_inner, cfg.ssm_conv_dim
    return {
        "w_z": ParamSpec((d, inner), ("embed", None)),
        "w_x": ParamSpec((d, inner), ("embed", None)),
        "w_B": ParamSpec((d, n), ("embed", None)),
        "w_C": ParamSpec((d, n), ("embed", None)),
        "w_dt": ParamSpec((d, h), ("embed", None)),
        "conv_w": ParamSpec((cfg.ssm_conv, conv), (None, None)),
        "conv_b": ParamSpec((conv,), (None,), init="zeros"),
        "dt_bias": ParamSpec((h,), (None,), init="zeros", f32_at_use=True),
        "A_log": ParamSpec((h,), (None,), init="zeros", f32_at_use=True),
        "D": ParamSpec((h,), (None,), init="ones", f32_at_use=True),
        "norm": ParamSpec((inner,), (None,), init="ones", f32_at_use=True),
        "out_proj": ParamSpec((inner, d), (None, "embed")),
    }


def init_state(cfg: ModelConfig, batch: int, layers: int) -> dict[str, Any]:
    return {
        "conv": jnp.zeros((layers, batch, cfg.ssm_conv - 1, cfg.ssm_conv_dim),
                          cfg.activation_dtype),
        "ssm": jnp.zeros((layers, batch, cfg.ssm_state, cfg.ssm_inner),
                         jnp.float32),
    }


def state_axes(cfg: ModelConfig) -> dict[str, tuple]:
    ax = ("layers", "kv_batch", None, None)
    return {"conv": ax, "ssm": ax}


# ---------------------------------------------------------------------------
# Pieces shared by prefill and decode
# ---------------------------------------------------------------------------

def _project(p, x):
    """z, the conv's input [x, B, C], and dt_raw of x (B, S, D), in x's
    dtype."""
    dt = x.dtype

    def proj(name):
        return jnp.einsum("bsd,de->bse", x, p[name].astype(dt))

    return (proj("w_z"), jnp.concatenate(
        [proj("w_x"), proj("w_B"), proj("w_C")], axis=-1), proj("w_dt"))


def _conv(cfg: ModelConfig, p, xbc, prev):
    """Causal depthwise conv of xbc (B, S, C) after the inputs ``prev`` (B,
    K-1, C), through SiLU, in float32. Returns (x (B, S, H, P), B and C
    (B, S, G, N), the last K-1 inputs)."""
    k, s = cfg.ssm_conv, xbc.shape[1]
    full = jnp.concatenate([prev.astype(xbc.dtype), xbc], axis=1)

    def f32(name):       # rounded to the compute dtype at use, as a matrix
        return p[name].astype(xbc.dtype).astype(jnp.float32)

    w = f32("conv_w")
    out = f32("conv_b") + sum(
        full[:, i:i + s].astype(jnp.float32) * w[i] for i in range(k))
    out = jax.nn.silu(out)
    inner, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    b, s = out.shape[:2]
    xs = out[..., :inner].reshape(b, s, cfg.ssm_heads, cfg.ssm_head_dim)
    bm = out[..., inner:inner + gn].reshape(b, s, cfg.ssm_groups,
                                            cfg.ssm_state)
    cm = out[..., inner + gn:].reshape(b, s, cfg.ssm_groups, cfg.ssm_state)
    return xs, bm, cm, full[:, full.shape[1] - (k - 1):]


def _dt(p, dt_raw):
    """(dt after softplus, A), float32."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(p["A_log"].astype(jnp.float32))


def _gated_out(cfg: ModelConfig, p, y, z):
    """out_proj(RMSNorm(y * silu(z))): the norm over each group's channels.
    y float32 (B, S, H*P); z in the activation dtype."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    b, s, inner = g.shape
    gg = g.reshape(b, s, cfg.ssm_groups, inner // cfg.ssm_groups)
    var = jnp.mean(jnp.square(gg), axis=-1, keepdims=True)
    g = (gg * lax.rsqrt(var + cfg.norm_eps)).reshape(b, s, inner)
    g = (g * p["norm"].astype(jnp.float32)).astype(z.dtype)
    return jnp.einsum("bse,ed->bsd", g, p["out_proj"].astype(z.dtype))


# ---------------------------------------------------------------------------
# Prefill: chunked SSD
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, a, bm, cm, chunk: int):
    """The SSD recurrence over a sequence in chunks, from a zero state.
    x (B, S, H, P), dt (B, S, H), bm and cm (B, S, G, N), a (H,), all
    float32. Returns (y (B, S, H, P) without the D term, the final state
    (B, H, P, N))."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    j = h // g                                          # heads in a group
    pad = (-s) % chunk
    if pad:
        def padded(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, bm, cm = map(padded, (x, dt, bm, cm))
    nc = (s + pad) // chunk

    def chunks(t):
        return jnp.moveaxis(t.reshape(b, nc, chunk, *t.shape[2:]), 1, 0)

    xs = chunks(x.reshape(b, s + pad, g, j, p))
    dts = chunks(dt.reshape(b, s + pad, g, j))
    bs, cs_ = chunks(bm), chunks(cm)
    ag = a.reshape(g, j)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(st, inp):
        xc, dtc, bc, cc = inp                # (b,l,g,j,p) (b,l,g,j) (b,l,g,n)
        acum = jnp.cumsum(dtc * ag, axis=1)              # (b, l, g, j)
        seg = acum[:, :, None] - acum[:, None]           # (b, t, s, g, j)
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg,
                                  -jnp.inf))
        xdt = xc * dtc[..., None]
        cb = jnp.einsum("btgn,bsgn->btsg", cc, bc)
        y = jnp.einsum("btsg,btsgj,bsgjp->btgjp", cb, decay, xdt)
        sg = st.reshape(b, g, j, p, n)
        y = y + jnp.einsum("btgn,bgjpn->btgjp", cc, sg) * \
            jnp.exp(acum)[..., None]
        last = acum[:, -1]                               # (b, g, j)
        tail = jnp.exp(last[:, None] - acum)             # (b, l, g, j)
        sg = sg * jnp.exp(last)[..., None, None] + jnp.einsum(
            "bsgn,bsgjp->bgjpn", bc, xdt * tail[..., None])
        return sg.reshape(b, h, p, n), y

    state, ys = lax.scan(body, jnp.zeros((b, h, p, n), jnp.float32),
                         (xs, dts, bs, cs_))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * chunk, h, p)[:, :s]
    return y, state


def ssm_forward(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array):
    """The mixer over a whole sequence x (B, S, D) from an empty state.
    Returns (out (B, S, D), the last conv inputs (B, K-1, C), the final
    state (B, H, P, N))."""
    with jax.named_scope("ssm"):
        z, xbc, dt_raw = _project(p, x)
        empty = jnp.zeros((x.shape[0], cfg.ssm_conv - 1, xbc.shape[-1]),
                          xbc.dtype)
        xs, bm, cm, conv_last = _conv(cfg, p, xbc, empty)
        dt, a = _dt(p, dt_raw)
        y, final = ssd_chunked(xs, dt, a, bm, cm, cfg.ssm_chunk)
        y = y + p["D"].astype(jnp.float32)[:, None] * xs
        out = _gated_out(cfg, p, y.reshape(*y.shape[:2], -1), z)
    return shard(out, "batch", "act_seq", None), conv_last, final


def to_kernel_layout(state: jax.Array) -> jax.Array:
    """(B, H, P, N) -> the cache's (B, N, H*P)."""
    b, h, p, n = state.shape
    return state.transpose(0, 3, 1, 2).reshape(b, n, h * p)


def ssm_prefill(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                cache: dict[str, jax.Array], layer):
    """Prefill from an empty state, writing the final conv inputs and
    state into layer ``layer`` of the stacked state in place."""
    out, conv_last, final = ssm_forward(cfg, p, x)
    with jax.named_scope("ssm"):
        cache = {
            "conv": lax.dynamic_update_index_in_dim(
                cache["conv"], conv_last.astype(cache["conv"].dtype), layer,
                0),
            "ssm": lax.dynamic_update_index_in_dim(
                cache["ssm"], to_kernel_layout(final), layer, 0),
        }
    return out, cache


# ---------------------------------------------------------------------------
# Decode: one step
# ---------------------------------------------------------------------------

def ssm_decode(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
               cache: dict[str, jax.Array], layer):
    """One token for every slot, x (B, 1, D), against layer ``layer`` of
    the stacked state, which is updated in place."""
    from repro.kernels.ssm_decode import ops as sd_ops
    from repro.kernels.ssm_decode.ref import ssm_decode_ref

    with jax.named_scope("ssm"):
        z, xbc, dt_raw = _project(p, x)
        prev = lax.dynamic_index_in_dim(cache["conv"], layer, keepdims=False)
        xs, bm, cm, conv_last = _conv(cfg, p, xbc, prev)
        conv = lax.dynamic_update_index_in_dim(
            cache["conv"], conv_last.astype(cache["conv"].dtype), layer, 0)
        dt, a = _dt(p, dt_raw[:, 0])
        step = sd_ops.ssm_decode if cfg.decode_impl == "pallas" \
            else ssm_decode_ref
        y, ssm = step(xs[:, 0], dt, a, p["D"].astype(jnp.float32), bm[:, 0],
                      cm[:, 0], cache["ssm"], layer)
        out = _gated_out(cfg, p, y.reshape(y.shape[0], 1, -1), z)
    return out, {"conv": conv, "ssm": ssm}
