"""Plain float32 reference of a DeepSeek-V3 decoder with ``q_lora_rank``
null (Moonlight-16B-A3B), as one chip of an expert-parallel deployment
computes it. It follows the published modeling code (``model_type``
deepseek_v3), reads its sizes from the configuration file, draws its
weights from the seed (``bench/benchlib/weights.py``) one layer at a time,
and imports nothing of the program under test. Every matrix product runs
at ``HIGHEST`` precision.

A layer: pre-norm RMSNorm blocks; latent attention (MLA) with
q = x W_q (``qk_nope_head_dim`` + ``qk_rope_head_dim`` a head), [c, k_pe]
= x W_kv_a, c through ``kv_a_layernorm``, [k_nope, v] = c W_kv_b; rotary
positions on q_pe and the one shared k_pe over interleaved pairs (the
published code de-interleaves them, then rotates halves); softmax scale
(nope + rope)^-1/2, causal. The first ``first_k_dense_replace`` layers
have a SwiGLU MLP of ``intermediate_size``; the others an expert layer:
sigmoid router scores over every routed expert, the ``num_experts_per_tok``
chosen by score plus the correction bias (``noaux_tc``; with ``n_group``
and ``topk_group`` 1 the group limit selects every expert), gated by
their unbiased scores normalised over the chosen (``norm_topk_prob``) and
scaled by ``routed_scaling_factor``, plus ``n_shared_experts`` shared
experts as one SwiGLU MLP. Untied head.

The chip's share: the file's ``n_routed_experts`` is the number of experts
this chip holds, ``published.n_routed_experts`` the router's width and
``expert_parallel.first_expert`` the first held. The router scores all of
them; only the held experts' contributions are added, the shared experts
whole, and that partial result goes on to the next layer, as the program
computes it on one chip without the exchange.

Departures from the published description: the routed experts held on
the other chips add nothing (the deployment's cut); no multi-token
prediction layer (``num_nextn_predict_layers`` 0); ``seq_aux`` (a training
loss) is not computed; the correction bias is a drawn weight, not one
learned; ``kv_a_layernorm``'s epsilon is the file's ``kv_a_layernorm_eps``
(the modeling code's RMSNorm default).

``precision="fp8"`` is the control: every operand of every matrix product
is rounded to float8 e4m3 with a per-tensor scale, accumulation and all
other arithmetic stay in float32.
"""

from __future__ import annotations

import functools
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
           "num_kv_heads": "num_key_value_heads",
           "d_ff": "intermediate_size", "vocab_size": "vocab_size",
           "tie_embeddings": "tie_word_embeddings",
           "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
           "kv_lora_rank": "kv_lora_rank",
           "qk_nope_head_dim": "qk_nope_head_dim",
           "qk_rope_head_dim": "qk_rope_head_dim",
           "v_head_dim": "v_head_dim",
           "latent_norm_eps": "kv_a_layernorm_eps",
           "first_dense_layers": "first_k_dense_replace",
           "num_experts_per_tok": "num_experts_per_tok",
           "moe_d_ff": "moe_intermediate_size",
           "moe_routed_scale": "routed_scaling_factor",
           "experts_held": "n_routed_experts"}
#: what this module computes, as the file states it
_PUBLISHED = {"hidden_act": "silu", "scoring_func": "sigmoid",
              "topk_method": "noaux_tc", "q_lora_rank": None, "n_group": 1,
              "topk_group": 1, "moe_layer_freq": 1, "attention_bias": False,
              "norm_topk_prob": True}
#: (family, mlp_act, moe_impl, qk_norm, qkv_bias, attn_softcap, use_rope,
#: embedding, residual and attention multipliers, logits scaling)
_ARCH = ("moe", "silu", "ragged", False, False, 0.0, True, 1.0, 1.0, 0.0,
         1.0)


def _sizes(m: dict):
    """(router width, experts held, first held, shared width)."""
    return (m["published"]["n_routed_experts"], m["n_routed_experts"],
            m["expert_parallel"]["first_expert"],
            m["n_shared_experts"] * m["moe_intermediate_size"])


def leaf_specs(m: dict) -> dict[str, Leaf]:
    """Every weight leaf of the program's tree: ``dense_layers.*`` stacked
    over the leading dense layers, ``layers.*`` over the expert layers,
    the rest drawn once. Matrices take the standard deviation of their
    true fan-in, so q, k and v have unit variance and the attention
    logits too under the (nope + rope)^-1/2 scale; norm scales sit near 1.
    The correction bias has a spread of 0.1, near that of the sigmoid
    scores of the chosen experts, so that dropping it changes the choice
    and gating by the biased scores changes the weights."""
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    n0 = m["first_k_dense_replace"]
    n1 = m["num_hidden_layers"] - n0
    width, held, _first, shared = _sizes(m)
    f, fe = m["intermediate_size"], m["moe_intermediate_size"]
    sd = 1 / math.sqrt(d)

    def attention(stack, n):
        return {
            f"{stack}.ln_attn": Leaf((d,), 0.05, 1.0, n),
            f"{stack}.ln_mlp": Leaf((d,), 0.05, 1.0, n),
            f"{stack}.attn.wq": Leaf((d, h, nope + rope), sd, 0.0, n),
            f"{stack}.attn.wkv_a": Leaf((d, r + rope), sd, 0.0, n),
            f"{stack}.attn.kv_norm": Leaf((r,), 0.05, 1.0, n),
            f"{stack}.attn.wkv_b": Leaf((r, h, nope + vd), 1 / math.sqrt(r),
                                        0.0, n),
            f"{stack}.attn.wo": Leaf((h, vd, d), 1 / math.sqrt(h * vd), 0.0,
                                     n),
        }

    def mlp(prefix, width, n):
        return {
            f"{prefix}.w_gate": Leaf((d, width), sd, 0.0, n),
            f"{prefix}.w_up": Leaf((d, width), sd, 0.0, n),
            f"{prefix}.w_down": Leaf((width, d), 1 / math.sqrt(width), 0.0, n),
        }

    specs = {
        # the embedded input has unit norm per row
        "embedding": Leaf((m["vocab_size"], d), sd, 0.0, vocab_axis=0),
        "lm_head": Leaf((d, m["vocab_size"]), sd, 0.0, vocab_axis=1),
        "ln_final": Leaf((d,), 0.05, 1.0),
        **attention("dense_layers", n0),
        **mlp("dense_layers.mlp", f, n0),
        **attention("layers", n1),
        "layers.moe.router": Leaf((d, width), sd, 0.0, n1),
        "layers.moe.router_bias": Leaf((width,), 0.1, 0.0, n1),
        "layers.moe.w_gate": Leaf((held, d, fe), sd, 0.0, n1),
        "layers.moe.w_up": Leaf((held, d, fe), sd, 0.0, n1),
        "layers.moe.w_down": Leaf((held, fe, d), 1 / math.sqrt(fe), 0.0, n1),
        **mlp("layers.moe.shared", shared, n1),
    }
    return specs


def check_program(cfg, m: dict) -> dict:
    """The program's ``ModelConfig`` against the file: each field that
    differs, as (program's, file's); also each published setting this
    module does not compute."""
    wrong = {f: (getattr(cfg, f), m[k]) for f, k in _FIELDS.items()
             if getattr(cfg, f) != m[k]}
    width, _held, first, shared = _sizes(m)
    for f, want in (("num_experts", width), ("expert_offset", first),
                    ("shared_d_ff", shared)):
        if getattr(cfg, f) != want:
            wrong[f] = (getattr(cfg, f), want)
    wrong.update({k: (v, m.get(k)) for k, v in _PUBLISHED.items()
                  if m.get(k) != v})
    arch = (cfg.family, cfg.mlp_act, cfg.moe_impl, cfg.qk_norm,
            cfg.qkv_bias, cfg.attn_softcap, cfg.use_rope,
            cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling)
    if arch != _ARCH:
        wrong["architecture"] = (arch, _ARCH)
    return wrong


# ---------------------------------------------------------------------------
# Useful work
# ---------------------------------------------------------------------------

def _token_params(m: dict) -> int:
    """Weights each token multiplies once, over the whole stack (not the
    embedding or the head). Routed experts count at their expected share
    here: ``num_experts_per_tok`` of the router's width are chosen, and
    this chip holds ``n_routed_experts`` of them, so a token reaches
    6 x 8/64 = 0.75 of a held expert on average."""
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    width, held, _first, shared = _sizes(m)
    fe = m["moe_intermediate_size"]
    attn = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) + \
        h * vd * d
    n0 = m["first_k_dense_replace"]
    n1 = m["num_hidden_layers"] - n0
    routed = m["num_experts_per_tok"] * held / width * 3 * d * fe
    moe = d * width + 3 * d * shared + routed
    return round(m["num_hidden_layers"] * attn + n0 * 3 * d *
                 m["intermediate_size"] + n1 * moe)


def _head(m: dict) -> int:
    return 2 * m["hidden_size"] * m["vocab_size"]


def generate_flops(m: dict, prompt: int, new_tokens: int) -> int:
    """Model operations of one request, useful work only (a multiply-add
    is 2): prefill yields the first token, then ``new_tokens - 1`` decode
    steps. Prefill attends with expanded keys and values (nope + rope
    scored, v summed); decode with the latent (r + rope scored, r summed),
    as the program computes each. Routed experts count at their expected
    share (``_token_params``); logits at the last prompt position and at
    each decode step, over the real vocabulary."""
    layers, h = m["num_hidden_layers"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    per_key = 2 * layers * h * (qk + m["v_head_dim"])
    dense = 2 * _token_params(m)
    pre = dense * prompt + per_key * prompt * (prompt + 1) // 2 + _head(m)
    dec = sum(dense + decode_attention_work(m, prompt + 1 + i)[0] + _head(m)
              for i in range(new_tokens - 1))
    return pre + dec


def decode_attention_work(m: dict, context: int) -> tuple[int, int]:
    """(operations, bytes) of the latent decode kernel for one sequence
    over ``context`` positions in every layer: each position's cached row
    (r + rope bfloat16 values) read once, scored by every head over all its
    columns and summed as values over its first r; plus each head's query
    read and output written."""
    h, r, rope = (m["num_attention_heads"], m["kv_lora_rank"],
                  m["qk_rope_head_dim"])
    c = r + rope
    layers = m["num_hidden_layers"]
    ops = 2 * h * (c + r) * context
    nbytes = 2 * c * context + 2 * h * (c + r)
    return ops * layers, nbytes * layers


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


def _rope(x, theta):
    """The published rotary embedding: (B, S, H, rope) with interleaved
    pairs, viewed as (rope/2, 2), transposed and flattened, then
    ``x * cos + rotate_half(x) * sin`` with frequencies repeated."""
    b, s, h, n = x.shape
    x = x.reshape(b, s, h, n // 2, 2).swapaxes(-1, -2).reshape(b, s, h, n)
    inv = 1.0 / theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    emb = jnp.concatenate([freqs, freqs], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., n // 2:], x[..., :n // 2]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _swiglu(h, w, prefix, low):
    g = _mm("bsd,df->bsf", h, w[f"{prefix}.w_gate"], low)
    u = _mm("bsd,df->bsf", h, w[f"{prefix}.w_up"], low)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w[f"{prefix}.w_down"], low)


def _attention(x, w, static):
    eps, kv_eps, theta, nope, r, low = static
    b, s, _ = x.shape
    h = _rms(x, w["ln_attn"], eps)
    q = _mm("bsd,dhk->bshk", h, w["attn.wq"], low)
    kv = _mm("bsd,dk->bsk", h, w["attn.wkv_a"], low)
    c = _rms(kv[..., :r], w["attn.kv_norm"], kv_eps)
    k_pe = _rope(kv[..., None, r:], theta)
    q_pe = _rope(q[..., nope:], theta)
    kvb = _mm("bsr,rhk->bshk", c, w["attn.wkv_b"], low)
    heads = q.shape[2]
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(k_pe, (b, s, heads, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    logits = _mm("bshk,bthk->bhst", q, k, low) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = _mm("bhst,bthv->bshv", p, kvb[..., nope:], low)
    return x + _mm("bshv,hvd->bsd", o, w["attn.wo"], low)


@functools.partial(jax.jit, static_argnums=(2,))
def _dense_layer(x, w, static):
    x = _attention(x, w, static)
    return x + _swiglu(_rms(x, w["ln_mlp"], static[0]), w, "mlp", static[-1])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _expert_layer(x, w, static, routing):
    k, first, scale = routing
    low = static[-1]
    x = _attention(x, w, static)
    h = _rms(x, w["ln_mlp"], static[0])
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
    return x + routed + _swiglu(h, w, "moe.shared", low)


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
    static = (eps, float(m["kv_a_layernorm_eps"]), float(m["rope_theta"]),
              m["qk_nope_head_dim"], m["kv_lora_rank"], low)
    width, held, first, _shared = _sizes(m)
    routing = (m["num_experts_per_tok"], first,
               float(m["routed_scaling_factor"]))
    specs = leaf_specs(m)
    x = jnp.take(weights.draw(specs, seed, "embedding"),
                 jnp.asarray(tokens, jnp.int32), axis=0)
    for stack, n in (("dense_layers", m["first_k_dense_replace"]),
                     ("layers", m["num_hidden_layers"]
                      - m["first_k_dense_replace"])):
        names = [k for k in specs if k.startswith(stack + ".")]
        for layer in range(n):
            w = {k.removeprefix(stack + "."): weights.draw(specs, seed, k,
                                                           layer)
                 for k in names}
            x = (_dense_layer(x, w, static) if stack == "dense_layers"
                 else _expert_layer(x, w, static, routing))
    out = _logits(x, jnp.asarray(rows, jnp.int32),
                  weights.draw(specs, seed, "ln_final"),
                  weights.draw(specs, seed, "lm_head"), (eps, low))
    return np.asarray(out)
