"""xLSTM (arXiv:2405.04517): mLSTM (matrix memory) + sLSTM (scalar memory).

Layout follows the paper's xLSTM[7:1] recipe: every 8th block is an sLSTM,
the rest are mLSTM. ``d_ff = 0`` per the assignment — blocks carry their own
internal up/down projections and there is no separate transformer FFN.

* mLSTM training path uses the **chunkwise-parallel** formulation (intra-chunk
  MXU matmuls + inter-chunk recurrence), which is what the Pallas kernel
  (kernels/mlstm_chunk) implements; the exact sequential recurrence lives in
  the kernel's ref.py and in :func:`mlstm_recurrent_ref` below for tests.
* sLSTM has a recurrent dependency on h_{t-1} and is inherently sequential —
  a ``lax.scan`` over time (the paper's CUDA kernel has the same structure).

Linear recurrences make this arch sub-quadratic, so it runs ``long_500k``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import (
    ModelConfig,
    ParamSpec,
    layer_norm,
    maybe_remat,
    shard,
    softmax_cross_entropy,
)


def d_inner(cfg: ModelConfig) -> int:
    return int(cfg.d_model * cfg.mlstm_proj_factor)


def head_dim(cfg: ModelConfig) -> int:
    return d_inner(cfg) // cfg.num_heads


def slstm_positions(cfg: ModelConfig) -> set[int]:
    return {i for i in range(cfg.num_layers) if i % 8 == 7}


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def make_mlstm_block_specs(cfg: ModelConfig) -> dict[str, Any]:
    d, di, h = cfg.d_model, d_inner(cfg), cfg.num_heads
    hd = di // h
    w = cfg.slstm_conv_width
    return {
        "ln": ParamSpec((d,), ("embed",), init="ones"),
        "ln_b": ParamSpec((d,), ("embed",), init="zeros"),
        "w_up": ParamSpec((d, 2 * di), ("embed", "xlstm_inner")),
        "conv_w": ParamSpec((w, di), (None, "xlstm_inner")),
        "conv_b": ParamSpec((di,), ("xlstm_inner",), init="zeros"),
        "w_q": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "w_k": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "w_v": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "w_i": ParamSpec((di, h), ("xlstm_inner", None)),
        "b_i": ParamSpec((h,), (None,), init="zeros"),
        "w_f": ParamSpec((di, h), ("xlstm_inner", None)),
        "b_f": ParamSpec((h,), (None,), init="ones"),
        "gn_scale": ParamSpec((di,), ("xlstm_inner",), init="ones"),
        "w_down": ParamSpec((di, d), ("xlstm_inner", "embed")),
    }


def make_slstm_block_specs(cfg: ModelConfig) -> dict[str, Any]:
    d, h = cfg.d_model, cfg.num_heads
    hd = d // h
    w = cfg.slstm_conv_width
    dff = int(d * 4 / 3)
    return {
        "ln": ParamSpec((d,), ("embed",), init="ones"),
        "ln_b": ParamSpec((d,), ("embed",), init="zeros"),
        "conv_w": ParamSpec((w, d), (None, "embed")),
        "conv_b": ParamSpec((d,), ("embed",), init="zeros"),
        # gate input weights (block-diagonal per head) + recurrent weights
        "w_i": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "w_f": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "w_z": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "w_o": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "r_i": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "r_f": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "r_z": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "r_o": ParamSpec((h, hd, hd), (None, "xlstm_hd", "xlstm_hd_out")),
        "b_i": ParamSpec((d,), ("embed",), init="zeros"),
        "b_f": ParamSpec((d,), ("embed",), init="ones"),
        "b_z": ParamSpec((d,), ("embed",), init="zeros"),
        "b_o": ParamSpec((d,), ("embed",), init="zeros"),
        "gn_scale": ParamSpec((d,), ("embed",), init="ones"),
        "w_up1": ParamSpec((d, dff), ("embed", "ffn")),
        "w_up2": ParamSpec((d, dff), ("embed", "ffn")),
        "w_down": ParamSpec((dff, d), ("ffn", "embed")),
    }


def make_xlstm_specs(cfg: ModelConfig) -> dict[str, Any]:
    slstm = slstm_positions(cfg)
    layers = []
    for i in range(cfg.num_layers):
        if i in slstm:
            layers.append({"slstm": make_slstm_block_specs(cfg)})
        else:
            layers.append({"mlstm": make_mlstm_block_specs(cfg)})
    return {
        "embedding": ParamSpec((cfg.padded_vocab, cfg.d_model), ("vocab", "embed")),
        "layers": layers,
        "ln_final": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
        "ln_final_b": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
        "lm_head": ParamSpec((cfg.d_model, cfg.padded_vocab), ("embed", "vocab")),
    }


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------

def _causal_conv(w: jax.Array, b: jax.Array, x: jax.Array,
                 state: jax.Array | None):
    width = w.shape[0]
    dt = x.dtype
    pad = (jnp.zeros((x.shape[0], width - 1, x.shape[2]), dt)
           if state is None else state.astype(dt))
    xp = jnp.concatenate([pad, x], axis=1)
    out = jnp.zeros(x.shape, jnp.float32)
    for j in range(width):
        out = out + xp[:, j:j + x.shape[1], :].astype(jnp.float32) * \
            w[j].astype(jnp.float32)
    out = out + b.astype(jnp.float32)
    return out.astype(dt), xp[:, xp.shape[1] - (width - 1):, :]


def _group_norm(x: jax.Array, scale: jax.Array, heads: int, eps: float = 1e-6):
    """Per-head group norm over the head dim. x: (..., heads*hd)."""
    dt = x.dtype
    shp = x.shape
    xh = x.reshape(*shp[:-1], heads, shp[-1] // heads).astype(jnp.float32)
    mu = jnp.mean(xh, axis=-1, keepdims=True)
    var = jnp.var(xh, axis=-1, keepdims=True)
    xh = (xh - mu) * lax.rsqrt(var + eps)
    return (xh.reshape(shp) * scale.astype(jnp.float32)).astype(dt)


def _blockdiag(x: jax.Array, w: jax.Array) -> jax.Array:
    """Per-head linear. x: (..., H, hd); w: (H, hd, hd_out)."""
    return jnp.einsum("...hk,hko->...ho", x, w.astype(x.dtype))


# ---------------------------------------------------------------------------
# mLSTM cell — chunkwise parallel (training) and sequential (reference)
# ---------------------------------------------------------------------------

def mlstm_chunkwise(q, k, v, li, lf, C0, n0, m0, chunk: int):
    """Stabilised chunkwise mLSTM.

    q,k,v: (B, H, S, hd); li, lf: (B, H, S) log input / log forget gates.
    C0: (B, H, hd, hd); n0: (B, H, hd); m0: (B, H).
    Returns h: (B, H, S, hd) and final (C, n, m).
    """
    bsz, h, s, hd = q.shape
    L = min(chunk, s)
    while s % L:
        L //= 2
    n_chunks = s // L
    f32 = jnp.float32

    qc = q.reshape(bsz, h, n_chunks, L, hd).transpose(2, 0, 1, 3, 4)
    kc = k.reshape(bsz, h, n_chunks, L, hd).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(bsz, h, n_chunks, L, hd).transpose(2, 0, 1, 3, 4)
    lic = li.reshape(bsz, h, n_chunks, L).transpose(2, 0, 1, 3).astype(f32)
    lfc = lf.reshape(bsz, h, n_chunks, L).transpose(2, 0, 1, 3).astype(f32)

    tri = jnp.tril(jnp.ones((L, L), bool))          # s <= tau
    tri_strict = jnp.tril(jnp.ones((L, L), bool), -1)

    def body(carry, xs):
        C, n, m = carry
        qb, kb, vb, lib, lfb = xs
        b_cum = jnp.cumsum(lfb, axis=-1)                       # (B,H,L) inclusive
        total = b_cum[..., -1:]                                 # (B,H,1)
        # decay from s+1..tau = b_tau - b_s ; gate at s = li_s
        # intra-chunk scores D[tau, s] = b_tau - b_s + li_s  (s <= tau)
        D = (b_cum[..., :, None] - b_cum[..., None, :] + lib[..., None, :])
        D = jnp.where(tri[None, None], D, -jnp.inf)
        # but diagonal: decay from s+1..tau with tau==s is 0 => b_tau-b_s=0 ok
        m_intra = jnp.max(D, axis=-1)                           # (B,H,L)
        m_inter = b_cum + m[..., None]                          # (B,H,L)
        m_out = jnp.maximum(m_intra, m_inter)
        m_out = jnp.maximum(m_out, -1e30)

        qf = qb.astype(f32) * (1.0 / float(hd) ** 0.5)
        # inter-chunk contribution
        inter_scale = jnp.exp(m_inter - m_out)                  # (B,H,L)
        h_inter = jnp.einsum("bhld,bhdv->bhlv", qf, C.astype(f32))
        den_inter = jnp.einsum("bhld,bhd->bhl", qf, n.astype(f32))
        # intra-chunk contribution
        P = jnp.exp(D - m_out[..., None])                       # (B,H,L,L)
        att = jnp.einsum("bhld,bhsd->bhls", qf, kb.astype(f32)) * P
        h_intra = jnp.einsum("bhls,bhsv->bhlv", att, vb.astype(f32))
        den_intra = jnp.sum(att, axis=-1)
        num = h_inter * inter_scale[..., None] + h_intra
        den = den_inter * inter_scale + den_intra
        denom = jnp.maximum(jnp.abs(den), jnp.exp(-m_out))
        h_out = num / denom[..., None]

        # state update (per-chunk stabiliser)
        m_state_cand = jnp.max(lib + total - b_cum, axis=-1)    # (B,H)
        m_new = jnp.maximum(m + total[..., 0], m_state_cand)
        c_scale = jnp.exp(m + total[..., 0] - m_new)            # (B,H)
        k_scale = jnp.exp(lib + total - b_cum - m_new[..., None])  # (B,H,L)
        kv = jnp.einsum("bhsd,bhsv,bhs->bhdv", kb.astype(f32), vb.astype(f32),
                        k_scale)
        C_new = C.astype(f32) * c_scale[..., None, None] + kv
        n_new = n.astype(f32) * c_scale[..., None] + \
            jnp.einsum("bhsd,bhs->bhd", kb.astype(f32), k_scale)
        return (C_new, n_new, m_new), h_out

    init = (C0.astype(f32), n0.astype(f32), m0.astype(f32))
    (C, n, m), hs = lax.scan(body, init, (qc, kc, vc, lic, lfc))
    hs = hs.transpose(1, 2, 0, 3, 4).reshape(bsz, h, s, hd)
    return hs.astype(q.dtype), (C, n, m)


def mlstm_recurrent_ref(q, k, v, li, lf, C0, n0, m0):
    """Exact sequential recurrence (oracle for the chunkwise forms)."""
    f32 = jnp.float32
    bsz, h, s, hd = q.shape
    scale = 1.0 / float(hd) ** 0.5

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, lit, lft = xs
        m_new = jnp.maximum(lft + m, lit)
        fp = jnp.exp(lft + m - m_new)
        ip = jnp.exp(lit - m_new)
        C = fp[..., None, None] * C + ip[..., None, None] * \
            jnp.einsum("bhd,bhv->bhdv", kt.astype(f32), vt.astype(f32))
        n = fp[..., None] * n + ip[..., None] * kt.astype(f32)
        qf = qt.astype(f32) * scale
        num = jnp.einsum("bhd,bhdv->bhv", qf, C)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n)),
                          jnp.exp(-m_new))
        return (C, n, m_new), (num / den[..., None])

    xs = (q.transpose(2, 0, 1, 3), k.transpose(2, 0, 1, 3),
          v.transpose(2, 0, 1, 3), li.transpose(2, 0, 1).astype(f32),
          lf.transpose(2, 0, 1).astype(f32))
    (C, n, m), hs = lax.scan(step, (C0.astype(f32), n0.astype(f32),
                                    m0.astype(f32)), xs)
    return hs.transpose(1, 2, 0, 3).astype(q.dtype), (C, n, m)


# ---------------------------------------------------------------------------
# mLSTM block
# ---------------------------------------------------------------------------

def _mlstm_qkv_gates(cfg: ModelConfig, p: dict[str, Any], x: jax.Array,
                     conv_state=None):
    """x: (B, S, D) -> q,k,v (B,H,S,hd), gates (B,H,S), z, new conv state."""
    dt = x.dtype
    h = layer_norm(x, p["ln"], p["ln_b"], cfg.norm_eps)
    up = jnp.einsum("bsd,de->bse", h, p["w_up"].astype(dt))
    di = up.shape[-1] // 2
    xm, z = up[..., :di], up[..., di:]
    # inner activations stay replicated on the model axis: the (B,S,di) ->
    # (B,S,H,hd) head reshape does not commute with a di-sharding, and this
    # is the smallest assigned model (data parallelism carries it).
    xm = shard(xm, "batch", "act_seq_rnn", None)
    xc, new_conv = _causal_conv(p["conv_w"], p["conv_b"], xm, conv_state)
    xc = jax.nn.silu(xc)
    nh = cfg.num_heads
    hd = di // nh
    xch = xc.reshape(*xc.shape[:-1], nh, hd)
    xmh = xm.reshape(*xm.shape[:-1], nh, hd)
    q = _blockdiag(xch, p["w_q"]).transpose(0, 2, 1, 3)       # (B,H,S,hd)
    k = _blockdiag(xch, p["w_k"]).transpose(0, 2, 1, 3)
    v = _blockdiag(xmh, p["w_v"]).transpose(0, 2, 1, 3)
    f32 = jnp.float32
    ig = (xc.astype(f32) @ p["w_i"].astype(f32) + p["b_i"].astype(f32))
    fg = (xc.astype(f32) @ p["w_f"].astype(f32) + p["b_f"].astype(f32))
    li = ig.transpose(0, 2, 1)                                 # (B,H,S)
    lf = -jax.nn.softplus(-fg).transpose(0, 2, 1)              # log sigmoid
    return q, k, v, li, lf, z, new_conv


def mlstm_block_forward(cfg: ModelConfig, p: dict[str, Any], x: jax.Array,
                        state: dict | None = None):
    dt = x.dtype
    bsz, s, _ = x.shape
    di = d_inner(cfg)
    nh = cfg.num_heads
    hd = di // nh
    conv_state = state["conv"] if state is not None else None
    q, k, v, li, lf, z, new_conv = _mlstm_qkv_gates(cfg, p, x, conv_state)
    if state is not None:
        C0, n0, m0 = state["C"], state["n"], state["m"]
    else:
        C0 = jnp.zeros((bsz, nh, hd, hd), jnp.float32)
        n0 = jnp.zeros((bsz, nh, hd), jnp.float32)
        m0 = jnp.full((bsz, nh), -1e30, jnp.float32)
    if cfg.use_pallas and s > 1:
        from repro.kernels.mlstm_chunk import ops as ml_ops
        hs, (C, n, m) = ml_ops.mlstm_chunk(q, k, v, li, lf, C0, n0, m0,
                                           chunk=cfg.mlstm_chunk)
    elif s == 1:
        hs, (C, n, m) = mlstm_recurrent_ref(q, k, v, li, lf, C0, n0, m0)
    else:
        hs, (C, n, m) = mlstm_chunkwise(q, k, v, li, lf, C0, n0, m0,
                                        chunk=cfg.mlstm_chunk)
    hflat = hs.transpose(0, 2, 1, 3).reshape(bsz, s, di)
    hflat = _group_norm(hflat, p["gn_scale"], nh, cfg.norm_eps)
    out = hflat * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", out, p["w_down"].astype(dt))
    return out, {"C": C, "n": n, "m": m, "conv": new_conv}


# ---------------------------------------------------------------------------
# sLSTM block
# ---------------------------------------------------------------------------

def slstm_cell_scan(p, xi, xf, xz, xo, state, nh: int):
    """Sequential sLSTM. x*: (B, S, D) fp32 gate pre-activations (input part).

    state: dict c,n,m,h of (B, D) fp32. Returns hs (B,S,D) and new state.
    """
    f32 = jnp.float32
    bsz, s, d = xi.shape
    hd = d // nh

    def to_heads(t):
        return t.reshape(bsz, nh, hd)

    def step(carry, xs):
        c, n, m, h = carry
        xit, xft, xzt, xot = xs
        hh = h.reshape(bsz, nh, hd)
        ri = _blockdiag(hh, p["r_i"]).reshape(bsz, d)
        rf = _blockdiag(hh, p["r_f"]).reshape(bsz, d)
        rz = _blockdiag(hh, p["r_z"]).reshape(bsz, d)
        ro = _blockdiag(hh, p["r_o"]).reshape(bsz, d)
        li = xit + ri
        lf_ = -jax.nn.softplus(-(xft + rf))       # log sigmoid forget
        z = jnp.tanh(xzt + rz)
        o = jax.nn.sigmoid(xot + ro)
        m_new = jnp.maximum(lf_ + m, li)
        fp = jnp.exp(lf_ + m - m_new)
        ip = jnp.exp(li - m_new)
        c_new = fp * c + ip * z
        n_new = fp * n + ip
        h_new = o * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, m_new, h_new), h_new

    xs = (xi.transpose(1, 0, 2), xf.transpose(1, 0, 2),
          xz.transpose(1, 0, 2), xo.transpose(1, 0, 2))
    (c, n, m, h), hs = lax.scan(
        step, (state["c"], state["n"], state["m"], state["h"]), xs)
    return hs.transpose(1, 0, 2), {"c": c, "n": n, "m": m, "h": h}


def slstm_block_forward(cfg: ModelConfig, p: dict[str, Any], x: jax.Array,
                        state: dict | None = None):
    dt = x.dtype
    bsz, s, d = x.shape
    nh = cfg.num_heads
    hd = d // nh
    f32 = jnp.float32
    h = layer_norm(x, p["ln"], p["ln_b"], cfg.norm_eps)
    conv_state = state["conv"] if state is not None else None
    hc, new_conv = _causal_conv(p["conv_w"], p["conv_b"], h, conv_state)
    hc = jax.nn.silu(hc)
    hh = h.reshape(bsz, s, nh, hd)
    hch = hc.reshape(bsz, s, nh, hd)
    xi = _blockdiag(hch, p["w_i"]).reshape(bsz, s, d).astype(f32) + \
        p["b_i"].astype(f32)
    xf = _blockdiag(hch, p["w_f"]).reshape(bsz, s, d).astype(f32) + \
        p["b_f"].astype(f32)
    xz = _blockdiag(hh, p["w_z"]).reshape(bsz, s, d).astype(f32) + \
        p["b_z"].astype(f32)
    xo = _blockdiag(hh, p["w_o"]).reshape(bsz, s, d).astype(f32) + \
        p["b_o"].astype(f32)
    if state is None:
        zero = jnp.zeros((bsz, d), f32)
        cell = {"c": zero, "n": zero, "m": jnp.full((bsz, d), -1e30, f32),
                "h": zero}
    else:
        cell = {k2: state[k2] for k2 in ("c", "n", "m", "h")}
    hs, new_cell = slstm_cell_scan(p, xi, xf, xz, xo, cell, nh)
    hs = _group_norm(hs.astype(dt), p["gn_scale"], nh, cfg.norm_eps)
    # post up-projection (PF = 4/3), gated GeLU
    u1 = jnp.einsum("bsd,df->bsf", hs, p["w_up1"].astype(dt))
    u2 = jnp.einsum("bsd,df->bsf", hs, p["w_up2"].astype(dt))
    out = jax.nn.gelu(u1) * u2
    out = jnp.einsum("bsf,fd->bsd", out, p["w_down"].astype(dt))
    new_state = dict(new_cell)
    new_state["conv"] = new_conv
    return out, new_state


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def _forward_stack(cfg: ModelConfig, params, x, states=None):
    slstm = slstm_positions(cfg)
    new_states = []
    for i, p in enumerate(params["layers"]):
        st = states[i] if states is not None else None
        if i in slstm:
            fn = maybe_remat(
                lambda x, p, st: slstm_block_forward(cfg, p["slstm"], x, st),
                cfg.remat_policy)
            out, ns = fn(x, p, st)
        else:
            fn = maybe_remat(
                lambda x, p, st: mlstm_block_forward(cfg, p["mlstm"], x, st),
                cfg.remat_policy)
            out, ns = fn(x, p, st)
        x = x + out
        x = shard(x, "batch", "act_seq", None)
        new_states.append(ns)
    return x, new_states


def xlstm_forward(cfg: ModelConfig, params, batch):
    x = jnp.take(params["embedding"].astype(cfg.activation_dtype),
                 batch["tokens"], axis=0)
    x = shard(x, "batch", "act_seq", None)
    x, _ = _forward_stack(cfg, params, x)
    x = layer_norm(x, params["ln_final"], params["ln_final_b"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    return shard(logits, "batch", "act_seq", "vocab_sharded")


def xlstm_loss(cfg: ModelConfig, params, batch):
    logits = xlstm_forward(cfg, params, batch)
    loss, denom = softmax_cross_entropy(
        logits, batch["labels"], batch.get("mask"), cfg.vocab_size)
    return loss, {"ce_loss": loss, "tokens": denom,
                  "aux_loss": jnp.zeros((), jnp.float32)}


def init_xlstm_state(cfg: ModelConfig, batch: int, max_len: int):
    di = d_inner(cfg)
    nh = cfg.num_heads
    hd = di // nh
    d = cfg.d_model
    w = cfg.slstm_conv_width - 1
    f32 = jnp.float32
    states = []
    for i in range(cfg.num_layers):
        if i in slstm_positions(cfg):
            states.append({
                "c": jnp.zeros((batch, d), f32),
                "n": jnp.zeros((batch, d), f32),
                "m": jnp.full((batch, d), -1e30, f32),
                "h": jnp.zeros((batch, d), f32),
                "conv": jnp.zeros((batch, w, d), cfg.activation_dtype),
            })
        else:
            states.append({
                "C": jnp.zeros((batch, nh, hd, hd), f32),
                "n": jnp.zeros((batch, nh, hd), f32),
                "m": jnp.full((batch, nh), -1e30, f32),
                "conv": jnp.zeros((batch, w, di), cfg.activation_dtype),
            })
    return states


def xlstm_state_axes(cfg: ModelConfig):
    axes = []
    for i in range(cfg.num_layers):
        if i in slstm_positions(cfg):
            axes.append({"c": ("batch", None), "n": ("batch", None),
                         "m": ("batch", None), "h": ("batch", None),
                         "conv": ("batch", None, None)})
        else:
            axes.append({"C": ("batch", None, "xlstm_hd_sharded", None),
                         "n": ("batch", None, "xlstm_hd_sharded"),
                         "m": ("batch", None),
                         "conv": ("batch", None, "xlstm_inner_sharded")})
    return axes


def xlstm_prefill(cfg: ModelConfig, params, batch, states):
    x = jnp.take(params["embedding"].astype(cfg.activation_dtype),
                 batch["tokens"], axis=0)
    x, new_states = _forward_stack(cfg, params, x, states)
    x = layer_norm(x[:, -1:], params["ln_final"], params["ln_final_b"],
                   cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    return logits, new_states


def xlstm_decode_step(cfg: ModelConfig, params, states, tokens, pos):
    del pos  # recurrent state carries position implicitly
    x = jnp.take(params["embedding"].astype(cfg.activation_dtype),
                 tokens, axis=0)
    x, new_states = _forward_stack(cfg, params, x, states)
    x = layer_norm(x, params["ln_final"], params["ln_final_b"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    return logits, new_states
