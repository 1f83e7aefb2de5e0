"""Kimi Delta Attention (KDA): the gated delta-rule mixer of the
kimi_linear family, with its per-slot recurrent state for serving.

Per head, with ``d_k = d_v`` the head width and x the normed input:

    q, k, v = silu(conv4(x W_q)), silu(conv4(x W_k)), silu(conv4(x W_v))
    q, k    = L2-normalised; q times d_k^-1/2
    g_t     = -exp(A_log) * softplus(x W_fa W_fb + dt_bias)    (d_k,) <= 0
    beta_t  = sigmoid(x W_b)                                     one a head
    S_t     = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t     = S_t^T q_t
    out     = W_o (RMSNorm_head(o) * sigmoid(x W_ga W_gb))

Each conv is causal and depthwise over 4 taps, without bias. ``W_fa``
and ``W_ga`` project to the head width, ``W_fb`` and ``W_gb`` back to
every channel (a low rank of ``d_k``).

Prefill computes the sequence in chunks of ``CHUNK`` positions, in
float32. Within a chunk, with ``G`` the decays' cumulative sum from the
chunk's start (non-increasing), the delta rule's pseudo-values ``U`` solve
a unit lower-triangular system (the WY/UT form):

    (I + A) U = diag(beta) (V - (exp(G) * K) S_0),
        A[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])   (s < t)
    O   = (exp(G) * Q) S_0 + P U,
        P[t, s] = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])         (s <= t)
    S_C = exp(G_C) * S_0 + (exp(G_C - G) * K)^T U

so the decay enters only as ``exp(G_i - G_j)`` with ``i >= j`` and as
``exp(G)`` (both at most 1), never as ``exp(-G)``, which overflows at
these decays. The state passes from chunk to chunk by a scan. A prompt
that is no multiple of the chunk is padded at its end with ``beta = 0``
and ``g = 0``, which leaves the state as it is. Decode is the single-step
update (``kernels/kda_decode`` with ``decode_impl='pallas'``).

The state of a slot, carried for every KDA layer of a run in one stacked
tree beside the attention runs' latent caches:

* ``conv``: the last ``kda_conv - 1`` inputs of the three convs, (L, B,
  K-1, 3 x heads x d_k) in the activation dtype (q's channels, then k's,
  then v's);
* ``state``: each head's S in float32, stored transposed, (L, B, H, d_v,
  d_k): ``state[h, j, i]`` is ``S[h, i, j]``, the keys' channels on the
  lanes (the kernel's layout). Rounding it to bfloat16 at every token
  would compound over a long prompt.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import ModelConfig, ParamSpec, shard

#: the cache leaves that hold a slot's recurrent state
STATE_LEAVES = ("conv", "state")
#: the L2 norm's epsilon on q and k (fla's ``l2norm``)
L2_EPS = 1e-6
#: positions in a chunk of the prefill's delta rule
CHUNK = 64
HI = lax.Precision.HIGHEST


def make_kda_specs(cfg: ModelConfig) -> dict[str, ParamSpec]:
    d, h, w, r = cfg.d_model, cfg.kda_heads, cfg.kda_width, cfg.kda_head_dim
    k = cfg.kda_conv
    return {
        "w_q": ParamSpec((d, w), ("embed", None)),
        "w_k": ParamSpec((d, w), ("embed", None)),
        "w_v": ParamSpec((d, w), ("embed", None)),
        "conv_q": ParamSpec((k, w), (None, None)),
        "conv_k": ParamSpec((k, w), (None, None)),
        "conv_v": ParamSpec((k, w), (None, None)),
        "w_fa": ParamSpec((d, r), ("embed", None)),
        "w_fb": ParamSpec((r, w), (None, None)),
        "dt_bias": ParamSpec((w,), (None,), init="zeros", f32_at_use=True),
        "A_log": ParamSpec((h,), (None,), init="zeros", f32_at_use=True),
        "w_b": ParamSpec((d, h), ("embed", None)),
        "w_ga": ParamSpec((d, r), ("embed", None)),
        "w_gb": ParamSpec((r, w), (None, None)),
        "o_norm": ParamSpec((r,), (None,), init="ones", f32_at_use=True),
        "w_o": ParamSpec((w, d), (None, "embed")),
    }


def init_state(cfg: ModelConfig, batch: int, layers: int) -> dict[str, Any]:
    hd = cfg.kda_head_dim
    return {
        "conv": jnp.zeros((layers, batch, cfg.kda_conv - 1,
                           3 * cfg.kda_width), cfg.activation_dtype),
        "state": jnp.zeros((layers, batch, cfg.kda_heads, hd, hd),
                           jnp.float32),
    }


def state_axes(cfg: ModelConfig) -> dict[str, tuple]:
    return {"conv": ("layers", "kv_batch", None, None),
            "state": ("layers", "kv_batch", None, None, None)}


# ---------------------------------------------------------------------------
# Pieces shared by prefill and decode
# ---------------------------------------------------------------------------

def _project(p, x):
    """The three convs' input [x W_q, x W_k, x W_v] of x (B, S, D), in x's
    dtype."""
    dt = x.dtype
    return jnp.concatenate([jnp.einsum("bsd,de->bse", x, p[n].astype(dt))
                            for n in ("w_q", "w_k", "w_v")], axis=-1)


def _conv(cfg: ModelConfig, p, qkv, prev):
    """Causal depthwise conv of qkv (B, S, 3W) after the inputs ``prev``
    (B, K-1, 3W), through SiLU, in float32; q and k L2-normalised per
    head, q scaled by d_k^-1/2. Returns (q, k, v (B, S, H, d), the last
    K-1 inputs)."""
    k, s = cfg.kda_conv, qkv.shape[1]
    full = jnp.concatenate([prev.astype(qkv.dtype), qkv], axis=1)
    # rounded to the compute dtype at use, as a matrix
    w = jnp.concatenate([p[n] for n in ("conv_q", "conv_k", "conv_v")],
                        axis=-1).astype(qkv.dtype).astype(jnp.float32)
    out = jax.nn.silu(sum(full[:, i:i + s].astype(jnp.float32) * w[i]
                          for i in range(k)))
    b = out.shape[0]
    q, kk, v = (t.reshape(b, s, cfg.kda_heads, cfg.kda_head_dim)
                for t in jnp.split(out, 3, axis=-1))

    def l2(t):
        return t * lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True)
                             + L2_EPS)

    q = l2(q) * cfg.kda_head_dim ** -0.5
    return q, l2(kk), v, full[:, full.shape[1] - (k - 1):]


def _low_rank(p, x, a: str, b: str):
    """x W_a W_b, in x's dtype."""
    dt = x.dtype
    return jnp.einsum("bsr,re->bse",
                      jnp.einsum("bsd,dr->bsr", x, p[a].astype(dt)),
                      p[b].astype(dt))


def _gates(cfg: ModelConfig, p, x):
    """The decays g (B, S, H, d_k) and write strengths beta (B, S, H),
    float32."""
    f = jax.nn.softplus(_low_rank(p, x, "w_fa", "w_fb").astype(jnp.float32)
                        + p["dt_bias"].astype(jnp.float32))
    b, s, _ = f.shape
    a = jnp.exp(p["A_log"].astype(jnp.float32))[:, None]
    g = -a * f.reshape(b, s, cfg.kda_heads, cfg.kda_head_dim)
    beta = jax.nn.sigmoid(jnp.einsum(
        "bsd,dh->bsh", x, p["w_b"].astype(x.dtype)).astype(jnp.float32))
    return g, beta


def _gated_out(cfg: ModelConfig, p, o, x):
    """W_o (RMSNorm_head(o) * sigmoid(x W_ga W_gb)). o float32 (B, S, H,
    d_v); x the mixer's input, in the activation dtype."""
    dt = x.dtype
    gate = _low_rank(p, x, "w_ga", "w_gb")
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o * lax.rsqrt(var + cfg.norm_eps) * p["o_norm"].astype(jnp.float32)
    b, s = o.shape[:2]
    o = o.reshape(b, s, -1) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return jnp.einsum("bse,ed->bsd", o.astype(dt), p["w_o"].astype(dt))


# ---------------------------------------------------------------------------
# Prefill: the chunked delta rule
# ---------------------------------------------------------------------------

def kda_chunked(q, k, v, g, beta, chunk: int):
    """The gated delta rule over a sequence in chunks, from a zero state.
    q, k, g (B, S, H, d_k), v (B, S, H, d_v), beta (B, S, H), all float32
    (q and k as the recurrence takes them). Returns (o (B, S, H, d_v), the
    final state (B, H, d_k, d_v))."""
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    pad = (-s) % chunk
    if pad:
        def padded(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        q, k, v, g, beta = map(padded, (q, k, v, g, beta))
    nc = (s + pad) // chunk

    def chunks(t):
        return jnp.moveaxis(t.reshape(b, nc, chunk, *t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def body(s0, inp):
        qc, kc, vc, gc, bc = inp       # (b,l,h,k) x2, (b,l,h,v), .., (b,l,h)
        cum = jnp.cumsum(gc, axis=1)                         # (b, l, h, k)
        seg = cum[:, :, None] - cum[:, None]                 # (b, t, s, h, k)
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg,
                                  -jnp.inf))
        kk = jnp.sum(kc[:, :, None] * decay * kc[:, None], axis=-1)
        qk = jnp.sum(qc[:, :, None] * decay * kc[:, None], axis=-1)
        a = jnp.where(strict[None, :, :, None], kk * bc[:, :, None], 0.0)
        eg = jnp.exp(cum)
        rhs = bc[..., None] * jnp.concatenate([vc, eg * kc], axis=-1)
        x = lax.linalg.triangular_solve(
            jnp.moveaxis(a, 3, 1), jnp.moveaxis(rhs, 2, 1), left_side=True,
            lower=True, unit_diagonal=True)                  # (b, h, l, v+k)
        u = x[..., :dv] - jnp.einsum("bhlk,bhkv->bhlv", x[..., dv:], s0,
                                     precision=HI)
        o = jnp.einsum("blhk,bhkv->blhv", qc * eg, s0, precision=HI) + \
            jnp.einsum("btsh,bhsv->bthv", qk, u, precision=HI)
        last = cum[:, -1]                                    # (b, h, k)
        tail = jnp.exp(last[:, None] - cum)                  # (b, l, h, k)
        s1 = jnp.exp(last)[..., None] * s0 + jnp.einsum(
            "blhk,bhlv->bhkv", kc * tail, u, precision=HI)
        return s1, o

    state, os_ = lax.scan(body, jnp.zeros((b, h, dk, dv), jnp.float32),
                          tuple(map(chunks, (q, k, v, g, beta))))
    o = jnp.moveaxis(os_, 0, 1).reshape(b, nc * chunk, h, dv)[:, :s]
    return o, state


def kda_forward(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array):
    """The mixer over a whole sequence x (B, S, D) from an empty state.
    Returns (out (B, S, D), the last conv inputs (B, K-1, 3W), the final
    state (B, H, d_k, d_v))."""
    with jax.named_scope("kda"):
        qkv = _project(p, x)
        empty = jnp.zeros((x.shape[0], cfg.kda_conv - 1, qkv.shape[-1]),
                          qkv.dtype)
        q, k, v, conv_last = _conv(cfg, p, qkv, empty)
        g, beta = _gates(cfg, p, x)
        o, final = kda_chunked(q, k, v, g, beta, CHUNK)
        out = _gated_out(cfg, p, o, x)
    return shard(out, "batch", "act_seq", None), conv_last, final


def kda_prefill(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                cache: dict[str, jax.Array], layer):
    """Prefill from an empty state, writing the final conv inputs and
    state into layer ``layer`` of the stacked state in place."""
    out, conv_last, final = kda_forward(cfg, p, x)
    with jax.named_scope("kda"):
        cache = {
            "conv": lax.dynamic_update_index_in_dim(
                cache["conv"], conv_last.astype(cache["conv"].dtype), layer,
                0),
            "state": lax.dynamic_update_index_in_dim(
                cache["state"], final.swapaxes(-1, -2), layer, 0),
        }
    return out, cache


# ---------------------------------------------------------------------------
# Decode: one step
# ---------------------------------------------------------------------------

def kda_decode(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
               cache: dict[str, jax.Array], layer):
    """One token for every slot, x (B, 1, D), against layer ``layer`` of
    the stacked state, which is updated in place."""
    from repro.kernels.kda_decode import ops as kd_ops
    from repro.kernels.kda_decode.ref import kda_decode_ref

    with jax.named_scope("kda"):
        qkv = _project(p, x)
        prev = lax.dynamic_index_in_dim(cache["conv"], layer, keepdims=False)
        q, k, v, conv_last = _conv(cfg, p, qkv, prev)
        conv = lax.dynamic_update_index_in_dim(
            cache["conv"], conv_last.astype(cache["conv"].dtype), layer, 0)
        g, beta = _gates(cfg, p, x)
        step = kd_ops.kda_decode if cfg.decode_impl == "pallas" \
            else kda_decode_ref
        o, state = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                        cache["state"], layer)
        out = _gated_out(cfg, p, o[:, None], x)
    return out, {"conv": conv, "state": state}
