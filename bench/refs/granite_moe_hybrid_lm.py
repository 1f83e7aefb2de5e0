"""Plain float32 reference of a GraniteMoeHybrid decoder (Granite-4.0-H),
as one chip of an expert-parallel deployment computes it. It follows the
published modeling code (``model_type`` granitemoehybrid), reads its
sizes from the configuration file, draws its weights from the seed
(``bench/benchlib/weights.py``) one layer at a time, and imports nothing
of the program under test. Every matrix product runs at ``HIGHEST``
precision.

A layer: ``h += r * mixer(RMSNorm(h))``, then ``h += r * (experts(n) +
shared(n))`` with ``n = RMSNorm(h)`` and r the residual multiplier. The
mixer is the one ``layer_types`` names:

* ``mamba``: Mamba-2. ``z, x, B, C, dt_raw`` are projections of the
  normed input (no bias); ``x, B, C`` go through a causal depthwise conv
  of ``mamba_d_conv`` taps with bias, then SiLU; ``dt = softplus(dt_raw +
  dt_bias)`` (``time_step_limit`` (0, inf): no clamp), ``A =
  -exp(A_log)``. Token by token, per head: ``S = exp(dt A) S + dt x (x) B``,
  ``y = S C + D x`` (a ``lax.scan`` of the recurrence, not the chunked
  form), then ``out_proj(RMSNorm(y * silu(z)))`` normed over each of
  ``mamba_n_groups`` groups of channels.
* ``attention``: grouped-query attention without positional encoding
  (``position_embedding_type`` nope), causal softmax at the scale
  ``attention_multiplier``, no bias.

The expert layer: router logits over every routed expert, the
``num_experts_per_tok`` largest chosen and gated by their softmax (no
bias, no scale); a shared SwiGLU of ``shared_intermediate_size``. Tied
head, logits divided by ``logits_scaling``, input times
``embedding_multiplier``.

The chip's share: the file's ``num_local_experts`` is the number of
experts this chip holds, ``published.num_local_experts`` the router's
width and ``expert_parallel.first_expert`` the first held. Only the held
experts' contributions are added, the shared expert whole, and that
partial result goes on to the next layer, as the program computes it on
one chip without the exchange. The depth is the first
``num_hidden_layers`` entries of ``layer_types``.

``precision="fp8"`` is the control: every operand of every matrix product
is rounded to float8 e4m3 with a per-tensor scale, accumulation and all
other arithmetic stay in float32.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights
from benchlib.weights import Leaf

HI = jax.lax.Precision.HIGHEST
#: ModelConfig field -> configuration-file key, for the program check
_FIELDS = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
           "num_heads": "num_attention_heads",
           "num_kv_heads": "num_key_value_heads", "vocab_size": "vocab_size",
           "tie_embeddings": "tie_word_embeddings", "norm_eps": "rms_norm_eps",
           "embedding_multiplier": "embedding_multiplier",
           "residual_multiplier": "residual_multiplier",
           "attention_multiplier": "attention_multiplier",
           "logits_scaling": "logits_scaling",
           "num_experts_per_tok": "num_experts_per_tok",
           "moe_d_ff": "intermediate_size",
           "shared_d_ff": "shared_intermediate_size",
           "experts_held": "num_local_experts",
           "ssm_heads": "mamba_n_heads", "ssm_head_dim": "mamba_d_head",
           "ssm_state": "mamba_d_state", "ssm_groups": "mamba_n_groups",
           "ssm_conv": "mamba_d_conv", "ssm_chunk": "mamba_chunk_size"}
#: what this module computes, as the file states it
_PUBLISHED = {"model_type": "granitemoehybrid", "hidden_act": "silu",
              "position_embedding_type": "nope", "attention_bias": False,
              "mamba_conv_bias": True, "mamba_proj_bias": False,
              "normalization_function": "rmsnorm"}
#: (family, mlp_act, moe_impl, moe_score, qk_norm, qkv_bias, attn_softcap,
#: use_rope, first_dense_layers)
_ARCH = ("moe", "silu", "ragged", "softmax", False, False, 0.0, False, 0)


def _sizes(m: dict):
    """(router width, experts held, first held)."""
    return (m["published"]["num_local_experts"], m["num_local_experts"],
            m["expert_parallel"]["first_expert"])


def runs(m: dict) -> list[tuple[str, str, int]]:
    """(name, mixer, layers) of each run of consecutive layers of one
    kind, named by the mixer and the index of its first layer, as the
    program's tree names them."""
    out, first = [], 0
    for mixer, group in itertools.groupby(
            m["layer_types"][:m["num_hidden_layers"]]):
        n = len(tuple(group))
        out.append((f"{mixer}{first}", mixer, n))
        first += n
    return out


def _mamba_sizes(m: dict):
    """(heads, head width, d_state, groups, conv taps, inner channels)."""
    return (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
            m["mamba_n_groups"], m["mamba_d_conv"],
            m["mamba_n_heads"] * m["mamba_d_head"])


def leaf_specs(m: dict) -> dict[str, Leaf]:
    """Every weight leaf of the program's tree, each run's leaves stacked
    over its layers. Matrices take the standard deviation of their true
    fan-in; q and k give unit-variance attention logits under the
    attention multiplier; the embedded input (times the embedding
    multiplier) has unit norm per row; norm scales sit near 1. The Mamba
    leaves the published init draws from ranges: ``A_log`` about log(4)
    (A in [1, 16] there), ``dt_bias`` about -4 (dt = softplus about 0.02,
    in [0.001, 0.1] there), so that the heads' memories range from a few
    tokens to the whole prompt; ``D`` near 1; the conv at the std of its 4
    taps."""
    d, h, kv = (m["hidden_size"], m["num_attention_heads"],
                m["num_key_value_heads"])
    hd = d // h
    width, held, _first = _sizes(m)
    f, fs = m["intermediate_size"], m["shared_intermediate_size"]
    mh, _p, n, g, k, inner = _mamba_sizes(m)
    conv = inner + 2 * g * n
    sd = 1 / math.sqrt(d)
    qk = (math.sqrt(hd) * m["attention_multiplier"]) ** -0.5 / math.sqrt(d)
    specs = {
        "embedding": Leaf((m["vocab_size"], d),
                          1 / (m["embedding_multiplier"] * math.sqrt(d)), 0.0,
                          vocab_axis=0),
        "ln_final": Leaf((d,), 0.05, 1.0),
    }
    for name, mixer, depth in runs(m):
        layer = {"ln_mlp": Leaf((d,), 0.05, 1.0),
                 "moe.router": Leaf((d, width), sd, 0.0),
                 "moe.w_gate": Leaf((held, d, f), sd, 0.0),
                 "moe.w_up": Leaf((held, d, f), sd, 0.0),
                 "moe.w_down": Leaf((held, f, d), 1 / math.sqrt(f), 0.0),
                 "moe.shared.w_gate": Leaf((d, fs), sd, 0.0),
                 "moe.shared.w_up": Leaf((d, fs), sd, 0.0),
                 "moe.shared.w_down": Leaf((fs, d), 1 / math.sqrt(fs), 0.0)}
        if mixer == "attention":
            layer.update({
                "ln_attn": Leaf((d,), 0.05, 1.0),
                "attn.wq": Leaf((d, h, hd), qk, 0.0),
                "attn.wk": Leaf((d, kv, hd), qk, 0.0),
                "attn.wv": Leaf((d, kv, hd), sd, 0.0),
                "attn.wo": Leaf((h, hd, d), 1 / math.sqrt(h * hd), 0.0)})
        else:
            layer.update({
                "ln_ssm": Leaf((d,), 0.05, 1.0),
                "ssm.w_z": Leaf((d, inner), sd, 0.0),
                "ssm.w_x": Leaf((d, inner), sd, 0.0),
                "ssm.w_B": Leaf((d, g * n), sd, 0.0),
                "ssm.w_C": Leaf((d, g * n), sd, 0.0),
                "ssm.w_dt": Leaf((d, mh), sd, 0.0),
                "ssm.conv_w": Leaf((k, conv), 1 / math.sqrt(k), 0.0),
                "ssm.conv_b": Leaf((conv,), 0.1, 0.0),
                "ssm.dt_bias": Leaf((mh,), 1.0, -4.0),
                "ssm.A_log": Leaf((mh,), 0.5, math.log(4.0)),
                "ssm.D": Leaf((mh,), 0.1, 1.0),
                "ssm.norm": Leaf((inner,), 0.05, 1.0),
                "ssm.out_proj": Leaf((inner, d), 1 / math.sqrt(inner),
                                     0.0)})
        specs.update({f"{name}.{key}": leaf._replace(depth=depth)
                      for key, leaf in layer.items()})
    return specs


def check_program(cfg, m: dict) -> dict:
    """The program's ``ModelConfig`` against the file: each field that
    differs, as (program's, file's); also each published setting this
    module does not compute."""
    wrong = {f: (getattr(cfg, f), m[k]) for f, k in _FIELDS.items()
             if getattr(cfg, f) != m[k]}
    width, _held, first = _sizes(m)
    kinds = tuple(m["layer_types"][:m["num_hidden_layers"]])
    for f, have, want in (
            ("num_experts", cfg.num_experts, width),
            ("expert_offset", cfg.expert_offset, first),
            ("layer_types", tuple(cfg.layer_types[:cfg.num_layers]), kinds),
            ("hd", cfg.hd, m["hidden_size"] // m["num_attention_heads"]),
            ("ssm_inner", cfg.ssm_inner,
             m["mamba_expand"] * m["hidden_size"])):
        if have != want:
            wrong[f] = (have, want)
    wrong.update({k: (v, m.get(k)) for k, v in _PUBLISHED.items()
                  if m.get(k) != v})
    arch = (cfg.family, cfg.mlp_act, cfg.moe_impl, cfg.moe_score,
            cfg.qk_norm, cfg.qkv_bias, cfg.attn_softcap, cfg.use_rope,
            cfg.first_dense_layers)
    if arch != _ARCH:
        wrong["architecture"] = (arch, _ARCH)
    return wrong


# ---------------------------------------------------------------------------
# Useful work
# ---------------------------------------------------------------------------

def _layer_counts(m: dict) -> tuple[int, int]:
    """(attention layers, Mamba layers)."""
    kinds = m["layer_types"][:m["num_hidden_layers"]]
    return kinds.count("attention"), kinds.count("mamba")


def _token_params(m: dict) -> float:
    """Weights each token multiplies once, over the whole stack (not the
    embedding or the head). Routed experts count at their expected share:
    ``num_experts_per_tok`` of the router's width are chosen and this chip
    holds ``num_local_experts`` of them, so a token reaches 10 x 9/72 =
    1.25 held experts on average."""
    d, h, kv = (m["hidden_size"], m["num_attention_heads"],
                m["num_key_value_heads"])
    hd = d // h
    width, held, _first = _sizes(m)
    mh, _p, n, g, _k, inner = _mamba_sizes(m)
    n_attn, n_ssm = _layer_counts(m)
    attn = 2 * d * h * hd + 2 * d * kv * hd
    ssm = d * (2 * inner + 2 * g * n + mh) + inner * d
    routed = m["num_experts_per_tok"] * held / width * 3 * d * \
        m["intermediate_size"]
    moe = d * width + routed + 3 * d * m["shared_intermediate_size"]
    return n_attn * attn + n_ssm * ssm + (n_attn + n_ssm) * moe


def _ssm_token_ops(m: dict) -> int:
    """Operations of one token's recurrence in every Mamba layer: the
    conv, then per state element its decay, the added dt x B and its part
    of the readout S C; the D term."""
    mh, p, n, g, k, inner = _mamba_sizes(m)
    per_layer = 2 * k * (inner + 2 * g * n) + 5 * mh * p * n + 2 * inner
    return per_layer * _layer_counts(m)[1]


def generate_flops(m: dict, prompt: int, new_tokens: int) -> int:
    """Model operations of one request, useful work only (a multiply-add
    is 2): prefill yields the first token, then ``new_tokens - 1`` decode
    steps. Attention scores and values over the causal keys of the
    attention layers; the Mamba layers' recurrence once per token
    (``_ssm_token_ops``); routed experts at their expected share; logits
    at the last prompt position and at each decode step over the real
    vocabulary."""
    h, hd = m["num_attention_heads"], m["hidden_size"] // \
        m["num_attention_heads"]
    n_attn = _layer_counts(m)[0]
    dense = 2 * _token_params(m) + _ssm_token_ops(m)
    head = 2 * m["hidden_size"] * m["vocab_size"]
    per_key = 4 * n_attn * h * hd
    pre = dense * prompt + per_key * prompt * (prompt + 1) // 2 + head
    dec = sum(dense + decode_attention_work(m, prompt + 1 + i)[0] + head
              for i in range(new_tokens - 1))
    return round(pre + dec)


def decode_attention_work(m: dict, context: int) -> tuple[int, int]:
    """(operations, bytes) of the decode-attention kernel for one sequence
    over ``context`` positions, in every attention layer: K and V of the
    KV heads read once (bfloat16), each query head scored and summed over
    them, its query read and output written."""
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // h
    n_attn = _layer_counts(m)[0]
    ops = 4 * h * hd * context
    nbytes = 2 * context * kv * hd * 2 + 2 * h * hd * 2
    return ops * n_attn, nbytes * n_attn


def decode_state_work(m: dict) -> tuple[int, int]:
    """(operations, bytes) of the Mamba decode kernel for one sequence's
    step, over every Mamba layer: its float32 state read and written once,
    each element decayed, given dt x B and read out against C; x, y, dt,
    B, C and D of the token (float32)."""
    mh, p, n, g, _k, inner = _mamba_sizes(m)
    n_ssm = _layer_counts(m)[1]
    ops = 5 * mh * p * n + 2 * inner
    nbytes = 4 * (2 * mh * p * n + 2 * inner + 2 * mh + 2 * g * n)
    return ops * n_ssm, nbytes * n_ssm


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------

def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, low: bool):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _attention(h, w, static):
    _eps, scale, _groups, low = static
    b, s, _ = h.shape
    q = _mm("bsd,dhk->bshk", h, w["attn.wq"], low)
    k = _mm("bsd,dhk->bshk", h, w["attn.wk"], low)
    v = _mm("bsd,dhk->bshk", h, w["attn.wv"], low)
    heads, kv = q.shape[2], k.shape[2]
    q = q.reshape(b, s, kv, heads // kv, -1)
    logits = _mm("bskgh,btkh->bkgst", q, k, low) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = _mm("bkgst,btkh->bskgh", p, v, low).reshape(b, s, heads, -1)
    return _mm("bshk,hkd->bsd", o, w["attn.wo"], low)


def _mamba(h, w, static):
    eps, _scale, groups, low = static
    b, s, _ = h.shape
    z = _mm("bsd,de->bse", h, w["ssm.w_z"], low)
    xbc = jnp.concatenate([_mm("bsd,de->bse", h, w[f"ssm.w_{n}"], low)
                           for n in ("x", "B", "C")], -1)
    k = w["ssm.conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["ssm.conv_b"] + sum(
        pad[:, i:i + s] * w["ssm.conv_w"][i] for i in range(k)))
    mh = w["ssm.A_log"].shape[0]
    inner = w["ssm.norm"].shape[0]
    gn = (xbc.shape[-1] - inner) // 2
    x = xbc[..., :inner].reshape(b, s, mh, inner // mh)
    bm = xbc[..., inner:inner + gn].reshape(b, s, groups, -1)
    cm = xbc[..., inner + gn:].reshape(b, s, groups, -1)
    bm = jnp.repeat(bm, mh // groups, axis=2)           # (b, s, H, N)
    cm = jnp.repeat(cm, mh // groups, axis=2)
    dt = jax.nn.softplus(_mm("bsd,dh->bsh", h, w["ssm.w_dt"], low)
                         + w["ssm.dt_bias"])
    a = -jnp.exp(w["ssm.A_log"])

    def step(state, inp):
        xt, dtt, bt, ct = inp          # (b,H,P) (b,H) (b,H,N) (b,H,N)
        state = state * jnp.exp(dtt * a)[..., None, None] + \
            (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct, precision=HI)

    state = jnp.zeros((b, mh, inner // mh, bm.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm)))
    y = jnp.moveaxis(y, 0, 1) + w["ssm.D"][:, None] * x
    g = y.reshape(b, s, inner) * jax.nn.silu(z)
    g = _rms(g.reshape(b, s, groups, -1), 1.0, eps).reshape(b, s, inner)
    return _mm("bse,ed->bsd", g * w["ssm.norm"], w["ssm.out_proj"], low)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, w, static, routing, mixer):
    k, first, resid = routing
    eps, low = static[0], static[-1]
    if mixer == "attention":
        x = x + resid * _attention(_rms(x, w["ln_attn"], eps), w, static)
    else:
        x = x + resid * _mamba(_rms(x, w["ln_ssm"], eps), w, static)
    h = _rms(x, w["ln_mlp"], eps)
    logits = _mm("bsd,de->bse", h, w["moe.router"], low)
    top, idx = jax.lax.top_k(logits, k)
    gate = jax.nn.softmax(top, axis=-1)
    held = w["moe.w_gate"].shape[0]
    # (B, S, held): each held expert's gate for each token, 0 if unchosen
    mine = idx[..., None] == first + jnp.arange(held)
    per_expert = jnp.sum(jnp.where(mine, gate[..., None], 0.0), axis=-2)
    g = _mm("bsd,edf->bsef", h, w["moe.w_gate"], low)
    u = _mm("bsd,edf->bsef", h, w["moe.w_up"], low)
    y = _mm("bsef,efd->bsed", jax.nn.silu(g) * u, w["moe.w_down"], low)
    routed = jnp.einsum("bse,bsed->bsd", per_expert, y, precision=HI)
    sg = _mm("bsd,df->bsf", h, w["moe.shared.w_gate"], low)
    su = _mm("bsd,df->bsf", h, w["moe.shared.w_up"], low)
    shared = _mm("bsf,fd->bsd", jax.nn.silu(sg) * su,
                 w["moe.shared.w_down"], low)
    return x + resid * (routed + shared)


@functools.partial(jax.jit, static_argnums=(4,))
def _logits(x, rows, ln, head, static):
    eps, scaling, low = static
    h = _rms(x[rows[:, 0], rows[:, 1]], ln, eps)
    return _mm("nd,vd->nv", h, head, low) / scaling


def logits_at(m: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              precision: str = "f32") -> np.ndarray:
    """Logits at ``rows`` ((n, 2) pairs of sequence and position) of the
    causal forward pass over ``tokens`` (B, S); (n, vocab) float32."""
    low = precision == "fp8"
    eps = float(m["rms_norm_eps"])
    static = (eps, float(m["attention_multiplier"]), m["mamba_n_groups"],
              low)
    _width, _held, first = _sizes(m)
    routing = (m["num_experts_per_tok"], first,
               float(m["residual_multiplier"]))
    specs = leaf_specs(m)
    emb = weights.draw(specs, seed, "embedding")
    x = jnp.take(emb, jnp.asarray(tokens, jnp.int32), axis=0) * \
        float(m["embedding_multiplier"])
    for name, mixer, depth in runs(m):
        names = [k for k in specs if k.startswith(name + ".")]
        for layer in range(depth):
            w = {k.removeprefix(name + "."): weights.draw(specs, seed, k,
                                                          layer)
                 for k in names}
            x = _layer(x, w, static, routing, mixer)
    out = _logits(x, jnp.asarray(rows, jnp.int32),
                  weights.draw(specs, seed, "ln_final"), emb,
                  (eps, float(m["logits_scaling"]), low))
    return np.asarray(out)
