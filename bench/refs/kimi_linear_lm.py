"""Plain float32 reference of a Kimi-Linear decoder (``model_type``
kimi_linear, Kimi-Linear-48B-A3B), as one chip of an expert-parallel
deployment computes it. It follows the published modeling code and fla's
``KimiDeltaAttention``, reads its sizes from the configuration file,
draws its weights from the seed (``bench/benchlib/weights.py``) one layer
at a time, and imports nothing of the program under test. Every matrix
product runs at ``HIGHEST`` precision.

A layer: ``h += mixer(RMSNorm(h))``, then ``h += ffn(RMSNorm(h))``. The
mixer is the one ``linear_attn_config`` names (1-indexed lists):

* KDA (``kda_layers``): ``q, k, v`` are projections of the normed input,
  each through a causal depthwise conv of ``short_conv_kernel_size`` taps
  without bias, then SiLU; q and k L2-normalised per head (epsilon 1e-6),
  q times ``head_dim^-1/2``; per channel ``g = -exp(A_log) * softplus(x
  W_fa W_fb + dt_bias)``, per head ``beta = sigmoid(x W_b)``. Token by
  token, per head: ``S = S * exp(g)`` over the keys' channels, ``S += k
  (beta (v - S^T k))^T``, ``o = S^T q`` (a ``lax.scan`` of the
  recurrence, not the chunked form); then ``W_o (RMSNorm_head(o) *
  sigmoid(x W_ga W_gb))``.
* MLA (``full_attn_layers``): q = x W_q (``qk_nope_head_dim`` +
  ``qk_rope_head_dim`` a head), [c, k_pe] = x W_kv_a, c through
  ``kv_a_layernorm``, [k_nope, v] = c W_kv_b; ``mla_use_nope``: q_pe and
  the one shared k_pe are scored as projected, no rotation; softmax scale
  (nope + rope)^-1/2, causal.

The first ``first_k_dense_replace`` layers have a SwiGLU MLP of
``intermediate_size``; the others an expert layer: sigmoid router scores
over every routed expert, the ``num_experts_per_token`` chosen by score
plus the correction bias (one group), gated by their unbiased scores
renormalised over the chosen (``moe_renormalize``) and scaled by
``routed_scaling_factor``, plus ``num_shared_experts`` shared experts as
one SwiGLU MLP. Untied head.

The chip's share: the file's ``num_experts`` is the number of experts
this chip holds, ``published.num_experts`` the router's width and
``expert_parallel.first_expert`` the first held. The router scores all of
them; only the held experts' contributions are added, the shared experts
whole, and that partial result goes on to the next layer, as the program
computes it on one chip without the exchange.

Departures from the published description: the routed experts held on
the other chips add nothing (the deployment's cut); the correction bias
is a drawn weight, not one learned; the epsilons the config does not
state (``kv_a_layernorm_eps``, the L2 norm's) are the modeling code's
defaults, listed under the file's ``assumed``.

``precision="fp8"`` is the control: every operand of every matrix product
is rounded to float8 e4m3 with a per-tensor scale, accumulation and all
other arithmetic (the recurrence among it) stay in float32.
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
#: the L2 norm's epsilon on q and k (fla's ``l2norm``)
L2_EPS = 1e-6
#: ModelConfig field -> configuration-file key, for the program check
_FIELDS = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
           "num_heads": "num_attention_heads",
           "num_kv_heads": "num_key_value_heads",
           "d_ff": "intermediate_size", "vocab_size": "vocab_size",
           "tie_embeddings": "tie_word_embeddings",
           "norm_eps": "rms_norm_eps", "kv_lora_rank": "kv_lora_rank",
           "qk_nope_head_dim": "qk_nope_head_dim",
           "qk_rope_head_dim": "qk_rope_head_dim",
           "v_head_dim": "v_head_dim",
           "latent_norm_eps": "kv_a_layernorm_eps",
           "first_dense_layers": "first_k_dense_replace",
           "num_experts_per_tok": "num_experts_per_token",
           "moe_d_ff": "moe_intermediate_size",
           "moe_routed_scale": "routed_scaling_factor",
           "experts_held": "num_experts", "hd": "head_dim"}
#: ModelConfig field -> key of ``linear_attn_config``
_KDA_FIELDS = {"kda_heads": "num_heads", "kda_head_dim": "head_dim",
               "kda_conv": "short_conv_kernel_size"}
#: what this module computes, as the file states it
_PUBLISHED = {"model_type": "kimi_linear", "hidden_act": "silu",
              "moe_router_activation_func": "sigmoid",
              "moe_renormalize": True, "q_lora_rank": None,
              "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
              "use_grouped_topk": True, "mla_use_nope": True,
              "num_nextn_predict_layers": 0}
#: (family, mlp_act, moe_impl, moe_score, qk_norm, qkv_bias, attn_softcap,
#: use_rope, embedding, residual and attention multipliers, logits
#: scaling)
_ARCH = ("moe", "silu", "ragged", "sigmoid", False, False, 0.0, False, 1.0,
         1.0, 0.0, 1.0)


def _sizes(m: dict):
    """(router width, experts held, first held, shared width)."""
    return (m["published"]["num_experts"], m["num_experts"],
            m["expert_parallel"]["first_expert"],
            m["num_shared_experts"] * m["moe_intermediate_size"])


def _kda_sizes(m: dict):
    """(heads, head width, conv taps, channels of each of q, k, v)."""
    c = m["linear_attn_config"]
    return (c["num_heads"], c["head_dim"], c["short_conv_kernel_size"],
            c["num_heads"] * c["head_dim"])


def layer_types(m: dict) -> list[str]:
    """Each layer's mixer, 0-indexed, from the 1-indexed lists."""
    full = set(m["linear_attn_config"]["full_attn_layers"])
    return ["attention" if i + 1 in full else "kda"
            for i in range(m["num_hidden_layers"])]


def runs(m: dict) -> list[tuple[str, str, bool, int]]:
    """(name, mixer, dense, layers) of each run of consecutive layers of
    one mixer and MLP kind, named by the mixer and the index of its first
    layer, as the program's tree names them."""
    n0 = m["first_k_dense_replace"]
    out, first = [], 0
    for (mixer, dense), group in itertools.groupby(
            (t, i < n0) for i, t in enumerate(layer_types(m))):
        n = len(tuple(group))
        out.append((f"{mixer}{first}", mixer, dense, n))
        first += n
    return out


def leaf_specs(m: dict) -> dict[str, Leaf]:
    """Every weight leaf of the program's tree, each run's leaves stacked
    over its layers. Matrices take the standard deviation of their true
    fan-in, so projections have unit variance and the attention logits too
    under the (nope + rope)^-1/2 scale; the embedded input has unit norm
    per row; norm scales sit near 1. The correction bias has a spread of
    0.1, near that of the sigmoid scores of the chosen experts. The KDA
    leaves that set its decays: ``A_log`` at the mean and spread of log
    U(1, 16), fla's initialisation (A in [1, 16]); ``dt_bias`` at those of
    the inverse softplus of a dt log-uniform over [0.001, 0.1], the
    initialisation of the gated delta rule's dt bias in fla, so that the
    channels' memories range from a token to a few hundred; the convs at
    the std of their taps."""
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    width, held, _first, shared = _sizes(m)
    f, fe = m["intermediate_size"], m["moe_intermediate_size"]
    kh, kd, taps, w = _kda_sizes(m)
    sd = 1 / math.sqrt(d)

    def mlp(prefix, width):
        return {f"{prefix}.w_gate": Leaf((d, width), sd, 0.0),
                f"{prefix}.w_up": Leaf((d, width), sd, 0.0),
                f"{prefix}.w_down": Leaf((width, d), 1 / math.sqrt(width),
                                         0.0)}

    attention = {
        "ln_attn": Leaf((d,), 0.05, 1.0),
        "attn.wq": Leaf((d, h, nope + rope), sd, 0.0),
        "attn.wkv_a": Leaf((d, r + rope), sd, 0.0),
        "attn.kv_norm": Leaf((r,), 0.05, 1.0),
        "attn.wkv_b": Leaf((r, h, nope + vd), 1 / math.sqrt(r), 0.0),
        "attn.wo": Leaf((h, vd, d), 1 / math.sqrt(h * vd), 0.0),
    }
    kda = {
        "ln_kda": Leaf((d,), 0.05, 1.0),
        **{f"kda.w_{n}": Leaf((d, w), sd, 0.0) for n in "qkv"},
        **{f"kda.conv_{n}": Leaf((taps, w), 1 / math.sqrt(taps), 0.0)
           for n in "qkv"},
        "kda.w_fa": Leaf((d, kd), sd, 0.0),
        "kda.w_fb": Leaf((kd, w), 1 / math.sqrt(kd), 0.0),
        "kda.dt_bias": Leaf((w,), 1.33, -4.6),
        "kda.A_log": Leaf((kh,), 0.675, 1.957),
        "kda.w_b": Leaf((d, kh), sd, 0.0),
        "kda.w_ga": Leaf((d, kd), sd, 0.0),
        "kda.w_gb": Leaf((kd, w), 1 / math.sqrt(kd), 0.0),
        "kda.o_norm": Leaf((kd,), 0.05, 1.0),
        "kda.w_o": Leaf((w, d), 1 / math.sqrt(w), 0.0),
    }
    experts = {
        "moe.router": Leaf((d, width), sd, 0.0),
        "moe.router_bias": Leaf((width,), 0.1, 0.0),
        "moe.w_gate": Leaf((held, d, fe), sd, 0.0),
        "moe.w_up": Leaf((held, d, fe), sd, 0.0),
        "moe.w_down": Leaf((held, fe, d), 1 / math.sqrt(fe), 0.0),
        **mlp("moe.shared", shared),
    }
    specs = {
        "embedding": Leaf((m["vocab_size"], d), sd, 0.0, vocab_axis=0),
        "lm_head": Leaf((d, m["vocab_size"]), sd, 0.0, vocab_axis=1),
        "ln_final": Leaf((d,), 0.05, 1.0),
    }
    for name, mixer, dense, depth in runs(m):
        layer = {"ln_mlp": Leaf((d,), 0.05, 1.0),
                 **(attention if mixer == "attention" else kda),
                 **(mlp("mlp", f) if dense else experts)}
        specs.update({f"{name}.{key}": leaf._replace(depth=depth)
                      for key, leaf in layer.items()})
    return specs


def check_program(cfg, m: dict) -> dict:
    """The program's ``ModelConfig`` against the file: each field that
    differs, as (program's, file's); also each published setting this
    module does not compute."""
    wrong = {f: (getattr(cfg, f), m[k]) for f, k in _FIELDS.items()
             if getattr(cfg, f) != m[k]}
    lin = m["linear_attn_config"]
    wrong.update({f: (getattr(cfg, f), lin[k]) for f, k in
                  _KDA_FIELDS.items() if getattr(cfg, f) != lin[k]})
    width, _held, first, shared = _sizes(m)
    kinds = tuple(layer_types(m))
    for f, have, want in (
            ("num_experts", cfg.num_experts, width),
            ("expert_offset", cfg.expert_offset, first),
            ("shared_d_ff", cfg.shared_d_ff, shared),
            ("layer_types", tuple(cfg.layer_types[:cfg.num_layers]), kinds)):
        if have != want:
            wrong[f] = (have, want)
    every = sorted(lin["kda_layers"] + lin["full_attn_layers"])
    if every != list(range(1, m["num_hidden_layers"] + 1)):
        wrong["linear_attn_config"] = (every, "each layer in one list")
    wrong.update({k: (v, m.get(k)) for k, v in _PUBLISHED.items()
                  if m.get(k) != v})
    arch = (cfg.family, cfg.mlp_act, cfg.moe_impl, cfg.moe_score,
            cfg.qk_norm, cfg.qkv_bias, cfg.attn_softcap, cfg.use_rope,
            cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling)
    if arch != _ARCH:
        wrong["architecture"] = (arch, _ARCH)
    return wrong


# ---------------------------------------------------------------------------
# Useful work
# ---------------------------------------------------------------------------

def _layer_counts(m: dict) -> tuple[int, int]:
    """(MLA layers, KDA layers)."""
    kinds = layer_types(m)
    return kinds.count("attention"), kinds.count("kda")


def _token_params(m: dict) -> float:
    """Weights each token multiplies once, over the whole stack (not the
    embedding or the head). Routed experts count at their expected share:
    ``num_experts_per_token`` of the router's width are chosen and this
    chip holds ``num_experts`` of them, so a token reaches 8 x 16/256 =
    0.5 of a held expert on average."""
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    width, held, _first, shared = _sizes(m)
    kh, kd, _taps, w = _kda_sizes(m)
    n_attn, n_kda = _layer_counts(m)
    attn = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) + \
        h * vd * d
    kda = 3 * d * w + 2 * (d * kd + kd * w) + d * kh + w * d
    n0 = m["first_k_dense_replace"]
    routed = m["num_experts_per_token"] * held / width * 3 * d * \
        m["moe_intermediate_size"]
    moe = d * width + 3 * d * shared + routed
    return (n_attn * attn + n_kda * kda + n0 * 3 * d *
            m["intermediate_size"] + (m["num_hidden_layers"] - n0) * moe)


def _kda_token_ops(m: dict) -> int:
    """Operations of one token's recurrence in every KDA layer: the three
    convs, then per state element its decay and its part of the delta
    rule's error, update and readout (7), and the error's per-channel
    multiply and subtract."""
    kh, kd, taps, w = _kda_sizes(m)
    per_layer = 2 * taps * 3 * w + 7 * kh * kd * kd + 2 * kh * kd
    return per_layer * _layer_counts(m)[1]


def _head(m: dict) -> int:
    return 2 * m["hidden_size"] * m["vocab_size"]


def generate_flops(m: dict, prompt: int, new_tokens: int) -> int:
    """Model operations of one request, useful work only (a multiply-add
    is 2): prefill yields the first token, then ``new_tokens - 1`` decode
    steps. Prefill attends with expanded keys and values (nope + rope
    scored, v summed) in the MLA layers; decode with the latent (r + rope
    scored, r summed), as the program computes each. The KDA layers'
    recurrence once per token (``_kda_token_ops``); routed experts at their
    expected share; logits at the last prompt position and at each decode
    step, over the real vocabulary."""
    h = m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    per_key = 2 * _layer_counts(m)[0] * h * (qk + m["v_head_dim"])
    dense = 2 * _token_params(m) + _kda_token_ops(m)
    pre = dense * prompt + per_key * prompt * (prompt + 1) // 2 + _head(m)
    dec = sum(dense + decode_attention_work(m, prompt + 1 + i)[0] + _head(m)
              for i in range(new_tokens - 1))
    return round(pre + dec)


def decode_attention_work(m: dict, context: int) -> tuple[int, int]:
    """(operations, bytes) of the latent decode kernel for one sequence
    over ``context`` positions in every MLA layer: each position's cached
    row (r + rope bfloat16 values) read once, scored by every head over
    all its columns and summed as values over its first r; plus each
    head's query read and output written."""
    h, r, rope = (m["num_attention_heads"], m["kv_lora_rank"],
                  m["qk_rope_head_dim"])
    c = r + rope
    layers = _layer_counts(m)[0]
    ops = 2 * h * (c + r) * context
    nbytes = 2 * c * context + 2 * h * (c + r)
    return ops * layers, nbytes * layers


def decode_state_work(m: dict) -> tuple[int, int]:
    """(operations, bytes) of the KDA decode kernel for one sequence's
    step, over every KDA layer: each head's float32 state read and written
    once, each element decayed, given its part of the error, updated and
    read out; the token's q, k, decay and v read, beta read and o written
    (float32)."""
    kh, kd, _taps, _w = _kda_sizes(m)
    n_kda = _layer_counts(m)[1]
    ops = 7 * kh * kd * kd + 2 * kh * kd
    nbytes = 4 * (2 * kh * kd * kd + 5 * kh * kd + kh)
    return ops * n_kda, nbytes * n_kda


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


def _swiglu(h, w, prefix, low):
    g = _mm("bsd,df->bsf", h, w[f"{prefix}.w_gate"], low)
    u = _mm("bsd,df->bsf", h, w[f"{prefix}.w_up"], low)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w[f"{prefix}.w_down"], low)


def _attention(h, w, static):
    _eps, kv_eps, nope, r, low = static
    b, s, _ = h.shape
    q = _mm("bsd,dhk->bshk", h, w["attn.wq"], low)
    kv = _mm("bsd,dk->bsk", h, w["attn.wkv_a"], low)
    c = _rms(kv[..., :r], w["attn.kv_norm"], kv_eps)
    kvb = _mm("bsr,rhk->bshk", c, w["attn.wkv_b"], low)
    heads = q.shape[2]
    k_pe = kv[..., None, r:]
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(k_pe, (b, s, heads, k_pe.shape[-1]))], -1)
    logits = _mm("bshk,bthk->bhst", q, k, low) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = _mm("bhst,bthv->bshv", p, kvb[..., nope:], low)
    return _mm("bshv,hvd->bsd", o, w["attn.wo"], low)


def _kda(h, w, static):
    eps, _kv_eps, _nope, _r, low = static
    b, s, _ = h.shape
    heads = w["kda.A_log"].shape[0]

    def conv(name):
        x = _mm("bsd,de->bse", h, w[f"kda.w_{name}"], low)
        taps = w[f"kda.conv_{name}"]
        k = taps.shape[0]
        pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        out = jax.nn.silu(sum(pad[:, i:i + s] * taps[i] for i in range(k)))
        return out.reshape(b, s, heads, -1)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True)
                                 + L2_EPS)

    q, k, v = conv("q"), conv("k"), conv("v")
    dk = q.shape[-1]
    q, k = l2(q) * dk ** -0.5, l2(k)
    f = _mm("bsr,re->bse", _mm("bsd,dr->bsr", h, w["kda.w_fa"], low),
            w["kda.w_fb"], low)
    g = -jnp.exp(w["kda.A_log"])[:, None] * jax.nn.softplus(
        f + w["kda.dt_bias"]).reshape(b, s, heads, dk)
    beta = jax.nn.sigmoid(_mm("bsd,dh->bsh", h, w["kda.w_b"], low))

    def step(state, inp):                  # state (b, H, d_k, d_v)
        qt, kt, vt, gt, bt = inp
        state = state * jnp.exp(gt)[..., None]
        err = vt - jnp.einsum("bhkv,bhk->bhv", state, kt, precision=HI)
        state = state + kt[..., None] * (bt[..., None] * err)[:, :, None]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt, precision=HI)

    state = jnp.zeros((b, heads, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    o = _rms(jnp.moveaxis(o, 0, 1), w["kda.o_norm"], eps)
    gate = _mm("bsr,re->bse", _mm("bsd,dr->bsr", h, w["kda.w_ga"], low),
               w["kda.w_gb"], low)
    o = o.reshape(b, s, -1) * jax.nn.sigmoid(gate)
    return _mm("bse,ed->bsd", o, w["kda.w_o"], low)


def _experts(h, w, routing, low):
    k, first, scale = routing
    scores = jax.nn.sigmoid(_mm("bsd,de->bse", h, w["moe.router"], low))
    _, idx = jax.lax.top_k(scores + w["moe.router_bias"], k)
    gate = jnp.take_along_axis(scores, idx, -1)
    gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20) * scale
    held = w["moe.w_gate"].shape[0]
    # (B, S, held): each held expert's gate for each token, 0 if unchosen
    mine = idx[..., None] == first + jnp.arange(held)
    per_expert = jnp.sum(jnp.where(mine, gate[..., None], 0.0), axis=-2)
    g = _mm("bsd,edf->bsef", h, w["moe.w_gate"], low)
    u = _mm("bsd,edf->bsef", h, w["moe.w_up"], low)
    y = _mm("bsef,efd->bsed", jax.nn.silu(g) * u, w["moe.w_down"], low)
    routed = jnp.einsum("bse,bsed->bsd", per_expert, y, precision=HI)
    return routed + _swiglu(h, w, "moe.shared", low)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, w, static, routing, mixer, dense):
    eps, low = static[0], static[-1]
    if mixer == "attention":
        x = x + _attention(_rms(x, w["ln_attn"], eps), w, static)
    else:
        x = x + _kda(_rms(x, w["ln_kda"], eps), w, static)
    h = _rms(x, w["ln_mlp"], eps)
    return x + (_swiglu(h, w, "mlp", low) if dense
                else _experts(h, w, routing, low))


@functools.partial(jax.jit, static_argnums=(4,))
def _logits(x, rows, ln, head, static):
    eps, low = static
    h = _rms(x[rows[:, 0], rows[:, 1]], ln, eps)
    return _mm("nd,dv->nv", h, head, low)


def logits_at(m: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              precision: str = "f32") -> np.ndarray:
    """Logits at ``rows`` ((n, 2) pairs of sequence and position) of the
    causal forward pass over ``tokens`` (B, S); (n, vocab) float32."""
    low = precision == "fp8"
    eps = float(m["rms_norm_eps"])
    static = (eps, float(m["kv_a_layernorm_eps"]), m["qk_nope_head_dim"],
              m["kv_lora_rank"], low)
    _width, _held, first, _shared = _sizes(m)
    routing = (m["num_experts_per_token"], first,
               float(m["routed_scaling_factor"]))
    specs = leaf_specs(m)
    x = jnp.take(weights.draw(specs, seed, "embedding"),
                 jnp.asarray(tokens, jnp.int32), axis=0)
    for name, mixer, dense, depth in runs(m):
        names = [k for k in specs if k.startswith(name + ".")]
        for layer in range(depth):
            w = {k.removeprefix(name + "."): weights.draw(specs, seed, k,
                                                          layer)
                 for k in names}
            x = _layer(x, w, static, routing, mixer, dense)
    out = _logits(x, jnp.asarray(rows, jnp.int32),
                  weights.draw(specs, seed, "ln_final"),
                  weights.draw(specs, seed, "lm_head"), (eps, low))
    return np.asarray(out)
