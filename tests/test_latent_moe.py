"""Latent attention (MLA), the no-drop expert layer over held experts and
the two-run layer stack, against the plain float32 reference that the
benchmark keeps (``bench/refs/deepseek_v3_lm.py``), at the reduced size of
``moonlight-16b-a3b`` on seeded random weights."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.decode_attention.ref import latent_decode_attention_ref
from repro.models import mlp as M
from repro.models import transformer
from repro.models.common import init_params
from repro.models.registry import build

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from benchlib import weights  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "deepseek_v3_lm_ref", BENCH / "refs" / "deepseek_v3_lm.py")
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

ARCH = "moonlight-16b-a3b"
SEED = 2**31 + 7
#: the reduced config as the reference's configuration file states it
MODEL = {
    "num_hidden_layers": 3, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 256, "vocab_size": 512,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_a_layernorm_eps": 1e-6, "first_k_dense_replace": 1,
    "moe_intermediate_size": 64, "n_shared_experts": 2, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "q_lora_rank": None, "moe_layer_freq": 1, "attention_bias": False,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "rope_theta": 50000.0, "rms_norm_eps": 1e-05,
    "published": {"n_routed_experts": 8},
    "expert_parallel": {"chips": 2, "chip": 1, "first_expert": 4},
}
#: program at float32 against the float32 reference: the same arithmetic
#: in another order (expanded against absorbed attention, grouped against
#: dense experts), so logits agree to float32 rounding over three layers
#: (measured 4e-6 at logits of magnitude 4); every mutation below moves
#: them by more than 1
TOL = 1e-4
B, S = 2, 40


def _cfg(**over):
    return reduced_config(ARCH).replace(dtype="float32", **over)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    assert REF.check_program(cfg, MODEL) == {}
    bundle = build(cfg)
    params = weights.overwrite(bundle.init_params(jax.random.PRNGKey(0)),
                               REF.leaf_specs(MODEL), SEED)
    tokens = np.random.default_rng(1).integers(0, 512, (B, S)) \
        .astype(np.int32)
    rows = np.array([(i, j) for i in range(B) for j in range(S)], np.int32)
    want = REF.logits_at(MODEL, SEED, tokens, rows).reshape(B, S, -1)
    return cfg, params, tokens, want


def _forward(cfg, params, tokens):
    logits, _ = transformer.lm_forward(cfg, params,
                                       {"tokens": jnp.asarray(tokens)})
    return np.asarray(logits, np.float32)[..., :cfg.vocab_size]


def test_forward_matches_reference(model):
    cfg, params, tokens, want = model
    np.testing.assert_allclose(_forward(cfg, params, tokens), want, atol=TOL)


@pytest.mark.parametrize("impl", ["direct", "pallas"])
def test_prefill_then_latent_decode_matches_reference(model, impl):
    """Prefill the first 24 tokens into the latent cache, then decode the
    rest one token a step, the two rows at different depths (per-row
    positions), each step's logits against the reference's forward. With
    ``pallas`` the held-expert layer is the ``moe_decode`` kernel, and
    each step's tokens equal the direct decode's."""
    cfg, params, tokens, want = model
    cfg = cfg.replace(decode_impl=impl, attn_kv_block=16)
    bundle = build(cfg)
    direct = jax.jit(build(cfg.replace(decode_impl="direct")).decode_fn)
    cache = bundle.init_cache(B, 64)
    assert set(cache) == {"dense_layers", "layers"}
    assert cache["layers"]["latent"].shape == (2, B, 40, 64)   # positions last
    start = (24, 20)
    for row, n in enumerate(start):
        one = bundle.init_cache(1, 64)
        logits, one = bundle.prefill_fn(
            params, {"tokens": jnp.asarray(tokens[row:row + 1, :n])}, one)
        np.testing.assert_allclose(
            np.asarray(logits, np.float32)[0, -1, :512], want[row, n - 1],
            atol=TOL)
        cache = jax.tree.map(
            lambda c, o, r=row: c.at[:, r:r + 1].set(o), cache, one)
    decode = jax.jit(bundle.decode_fn)
    other = cache
    for i in range(S - max(start)):
        pos = np.asarray([n + i for n in start], np.int32)
        step = (jnp.asarray(tokens[np.arange(B), pos][:, None]),
                jnp.asarray(pos))
        logits, cache = decode(params, cache, *step)
        got = np.asarray(logits, np.float32)[:, 0, :512]
        np.testing.assert_allclose(got, want[np.arange(B), pos], atol=TOL)
        if impl == "pallas":
            logits, other = direct(params, other, *step)
            np.testing.assert_array_equal(
                got.argmax(-1), np.asarray(logits)[:, 0, :512].argmax(-1))


def _biased_gates(cfg, p, xt):
    """Gates by the biased scores, as a wrong implementation would."""
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    biased = jax.nn.sigmoid(logits) + p["router_bias"]
    w, idx = jax.lax.top_k(biased, cfg.num_experts_per_tok)
    return idx, w / jnp.sum(w, -1, keepdims=True) * cfg.moe_routed_scale


@pytest.mark.parametrize("mutation", ["bias_dropped", "biased_gates",
                                      "scale_dropped"])
def test_mutations_fail_the_comparison(model, mutation, monkeypatch):
    cfg, params, tokens, want = model
    if mutation == "bias_dropped":
        params = jax.tree.map(lambda x: x, params)
        params["layers"]["moe"]["router_bias"] = jnp.zeros_like(
            params["layers"]["moe"]["router_bias"])
    elif mutation == "biased_gates":
        monkeypatch.setattr(M, "route", _biased_gates)
    else:
        cfg = cfg.replace(moe_routed_scale=1.0)
    assert np.abs(_forward(cfg, params, tokens) - want).max() > 10 * TOL


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _moe(cfg, seed=0):
    params = init_params(jax.random.PRNGKey(seed), M.make_moe_specs(cfg),
                         jnp.float32)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                   (cfg.num_experts,))
    return {**params, "router_bias": bias}


def _slice(params, first, n):
    return {**params, **{k: params[k][first:first + n]
                         for k in ("w_gate", "w_up", "w_down")}}


def test_chip_shares_add_up_to_the_uncut_layer():
    """Eight experts over four chips of two: the shares' outputs, with the
    shared experts counted once, are the layer that holds all eight."""
    whole = _cfg(experts_held=8, expert_offset=0)
    params = _moe(whole)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, whole.d_model))
    want, _ = M.moe_ragged_forward(whole, params, x)
    shared = M.mlp_forward(whole, params["shared"], x)
    total = -3 * shared
    for chip in range(4):
        cfg = _cfg(experts_held=2, expert_offset=2 * chip)
        total = total + M.moe_ragged_forward(cfg, _slice(params, 2 * chip, 2),
                                             x)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def _per_token(cfg, params, xt):
    """Each token through each of its chosen held experts, one by one."""
    idx, w = M.route(cfg, params, xt)
    out = np.zeros(xt.shape, np.float32)
    for t in range(xt.shape[0]):
        for e, g in zip(np.asarray(idx[t]), np.asarray(w[t])):
            j = e - cfg.expert_offset
            if 0 <= j < cfg.held_experts:
                h = jax.nn.silu(xt[t] @ params["w_gate"][j]) * \
                    (xt[t] @ params["w_up"][j])
                out[t] += g * np.asarray(h @ params["w_down"][j])
    return out


def test_nothing_dropped_when_every_token_routes_to_one_expert():
    """A correction bias that sends all 512 tokens to one held expert: a
    capacity-bounded layer would drop most of them; this one computes
    every (token, choice) pair."""
    cfg = _cfg()
    params = _moe(cfg)
    params["router_bias"] = params["router_bias"].at[5].add(100.0)
    xt = jax.random.normal(jax.random.PRNGKey(6), (512, cfg.d_model))
    idx, _ = M.route(cfg, params, xt)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))
    got = M.moe_routed(cfg, params, xt)
    np.testing.assert_allclose(np.asarray(got), _per_token(cfg, params, xt),
                               atol=1e-4)


def test_pairs_for_experts_held_elsewhere_add_nothing():
    cfg = _cfg()
    params = _moe(cfg)
    xt = jax.random.normal(jax.random.PRNGKey(7), (64, cfg.d_model))
    far = {**params, "router_bias": params["router_bias"].at[:4].add(100.0)}
    idx, _ = M.route(cfg, far, xt)
    assert bool(jnp.all(idx < cfg.expert_offset))
    assert not np.asarray(M.moe_routed(cfg, far, xt)).any()


# ---------------------------------------------------------------------------
# the latent decode kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [16, 64])
def test_latent_kernel_matches_plain_absorbed_decode(block):
    """Interpreted kernel against the plain absorbed decode: ragged
    per-row lengths (one inside the first block, one at a block's end,
    one full), several blocks, bfloat16 rows."""
    rng = np.random.default_rng(0)
    b, h, c, dv, smax = 3, 4, 40, 32, 64
    q = jnp.asarray(rng.standard_normal((b, h, c)), jnp.bfloat16)
    cache = jnp.asarray(rng.standard_normal((b, c, smax)), jnp.bfloat16)
    lens = jnp.asarray([5, 32, 64], jnp.int32)
    got = da_ops.latent_decode_attention(q, cache, lens, scale=c ** -0.5,
                                         value_dim=dv, block_kv=block,
                                         interpret=True)
    want = latent_decode_attention_ref(q, cache, lens, scale=c ** -0.5,
                                       value_dim=dv)
    assert got.shape == (b, h, dv)
    # bfloat16 output, probabilities rounded to bfloat16 for the values
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    # rows past a length, stale in a serving cache, change nothing
    junk = cache.at[0, :, 5:].set(1e4).at[1, :, 32:].set(-3e4)
    again = da_ops.latent_decode_attention(q, junk, lens, scale=c ** -0.5,
                                           value_dim=dv, block_kv=block,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


@pytest.mark.parametrize("layer", [0, 1])
def test_latent_kernel_reads_its_layer_of_the_stack(layer):
    """The stacked latent cache (L, B, C, Smax) and a layer index, ragged
    lengths: the interpreted kernel reads that layer and no other, the
    last included, as the oracle does on the stack."""
    rng = np.random.default_rng(1)
    n, b, h, c, dv, smax = 2, 3, 4, 40, 32, 64
    q = jnp.asarray(rng.standard_normal((b, h, c)), jnp.float32)
    cache = jnp.asarray(rng.standard_normal((n, b, c, smax)), jnp.float32)
    lens = jnp.asarray([5, 32, 64], jnp.int32)
    kw = dict(scale=c ** -0.5, value_dim=dv)
    got = da_ops.latent_decode_attention(q, cache, lens, layer=layer,
                                         block_kv=16, interpret=True, **kw)
    alone = da_ops.latent_decode_attention(q, cache[layer], lens,
                                           block_kv=16, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
    want = latent_decode_attention_ref(q, cache, lens, layer=layer, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)
    other = latent_decode_attention_ref(q, cache, lens, layer=1 - layer,
                                        **kw)
    assert not np.allclose(np.asarray(got), np.asarray(other), atol=1e-2)


def test_latent_cache_bytes_per_token():
    """The published config caches (512 + 64) bfloat16 values a layer a
    position: 31104 bytes a token over 27 layers, 254803968 for the
    engine's 4 slots of 2048."""
    from repro.configs import get_config

    cfg = get_config(ARCH).replace(experts_held=8)
    cache = jax.eval_shape(lambda: build(cfg).init_cache(4, 2048))
    nbytes = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(cache))
    assert nbytes == 254803968 == 4 * 2048 * 31104
