"""Gated delta-rule (KDA) layers beside NoPE latent attention
(kimi-linear), the sigmoid-routed expert layer after them, and the matrix
state that the serving scheduler carries beside the latent cache, against
the plain float32 reference that the benchmark keeps
(``bench/refs/kimi_linear_lm.py``) at the reduced size of
``kimi-linear-48b-a3b`` on seeded random weights."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.kernels.kda_decode import ops as kd_ops
from repro.kernels.kda_decode.ref import kda_decode_ref
from repro.models import attention, kda
from repro.models import mlp as M
from repro.models import transformer
from repro.models.common import init_params
from repro.models.registry import build
from repro.serving.serve import BatchScheduler, Request

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from benchlib import weights  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "kimi_linear_lm_ref", BENCH / "refs" / "kimi_linear_lm.py")
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

ARCH = "kimi-linear-48b-a3b"
SEED = 2**31 + 13
#: the reduced config as the reference's configuration file states it:
#: chip 1 of 2, holding experts 4-7 of 8
MODEL = {
    "model_type": "kimi_linear", "num_hidden_layers": 4,
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 32, "intermediate_size": 256, "vocab_size": 512,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_a_layernorm_eps": 1e-6, "q_lora_rank": None,
    "mla_use_nope": True, "first_k_dense_replace": 1,
    "moe_intermediate_size": 64, "num_shared_experts": 2,
    "num_experts": 4, "num_experts_per_token": 3,
    "routed_scaling_factor": 2.446, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "use_grouped_topk": True, "moe_layer_freq": 1,
    "num_nextn_predict_layers": 0, "hidden_act": "silu",
    "tie_word_embeddings": False, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"full_attn_layers": [3], "kda_layers": [1, 2, 4],
                           "head_dim": 16, "num_heads": 4,
                           "short_conv_kernel_size": 4},
    "published": {"num_experts": 8},
    "expert_parallel": {"chips": 2, "chip": 1, "first_expert": 4},
}
#: program at float32 against the float32 reference: the same arithmetic
#: in another order (the chunked delta rule against the token-by-token
#: scan, grouped against dense experts, absorbed against expanded
#: attention), so logits agree to float32 rounding (measured about 5e-6
#: of the logits' largest magnitude); every mutation below moves them by
#: a relative 0.05 or more
REL = 1e-4
B, S = 2, 22


def _cfg(**over):
    return reduced_config(ARCH).replace(dtype="float32", **over)


@pytest.fixture(autouse=True)
def chunks_of_8(monkeypatch):
    """Prefill in chunks of 8, so that the tiny model's prompts span
    several chunks of the delta rule (the published 64 would be one)."""
    monkeypatch.setattr(kda, "CHUNK", 8)


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


def _close(got, want):
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=REL * np.abs(want).max())


def test_runs_follow_the_published_order():
    """Layer 0 is a KDA layer with the dense MLP; the 7 MLA layers sit at
    the published 1-indexed 4, 8, ..., 24, 27."""
    runs = [(r.name, r.mixer, r.moe, r.layers)
            for r in transformer.layer_runs(get_config(ARCH))]
    assert runs == [("kda0", "kda", False, 1), ("kda1", "kda", True, 2),
                    ("attention3", "attention", True, 1),
                    ("kda4", "kda", True, 3),
                    ("attention7", "attention", True, 1),
                    ("kda8", "kda", True, 3),
                    ("attention11", "attention", True, 1),
                    ("kda12", "kda", True, 3),
                    ("attention15", "attention", True, 1),
                    ("kda16", "kda", True, 3),
                    ("attention19", "attention", True, 1),
                    ("kda20", "kda", True, 3),
                    ("attention23", "attention", True, 1),
                    ("kda24", "kda", True, 2),
                    ("attention26", "attention", True, 1)]
    assert [r.name for r in transformer.layer_runs(_cfg())] == \
        ["kda0", "kda1", "attention2", "kda3"]


def test_the_other_configurations_keep_their_runs():
    """Runs keyed by mixer and MLP kind leave the trees of the
    configurations that have only one of the two keys as they were."""
    def names(arch, **over):
        return [(r.name, r.moe) for r in
                transformer.layer_runs(get_config(arch).replace(**over))]

    assert names("moonlight-16b-a3b") == [("dense_layers", False),
                                          ("layers", True)]
    assert names("qwen2-0.5b") == [("layers", False)]
    assert names("granite-4.0-h-small", num_layers=20) == [
        ("mamba0", True), ("attention5", True), ("mamba6", True),
        ("attention15", True), ("mamba16", True)]


def test_forward_matches_reference(model):
    cfg, params, tokens, want = model
    _close(_forward(cfg, params, tokens), want)


@pytest.mark.parametrize("impl", ["direct", "pallas"])
def test_prefill_then_decode_matches_reference(model, impl):
    """Prefill 9 and 14 tokens (neither a multiple of the chunk of 8) into
    the two rows of the cache, then decode the rest one token a step at
    per-row positions, each step's logits against the reference's
    forward. With ``pallas`` the KDA layers run the ``kda_decode`` kernel
    and the expert layers ``moe_decode``, and each step's tokens equal the
    direct decode's."""
    cfg, params, tokens, want = model
    bundle = build(cfg.replace(decode_impl=impl))
    direct = jax.jit(build(cfg.replace(decode_impl="direct")).decode_fn)
    cache = bundle.init_cache(B, 32)
    assert set(cache) == {"kda0", "kda1", "attention2", "kda3"}
    assert cache["kda1"]["state"].shape == (1, B, 4, 16, 16)
    assert cache["kda0"]["conv"].shape == (1, B, 3, 3 * 64)
    assert cache["attention2"]["latent"].shape == (1, B, 40, 32)
    start = (9, 14)
    for row, n in enumerate(start):
        logits, one = bundle.prefill_fn(
            params, {"tokens": jnp.asarray(tokens[row:row + 1, :n])},
            bundle.init_cache(1, 32))
        _close(np.asarray(logits, np.float32)[0, -1, :512], want[row, n - 1])
        cache = BatchScheduler._insert_row_impl(cache, one, row)
    decode = jax.jit(bundle.decode_fn)
    other = cache
    for i in range(S - max(start)):
        pos = np.asarray([n + i for n in start], np.int32)
        step = (jnp.asarray(tokens[np.arange(B), pos][:, None]),
                jnp.asarray(pos))
        logits, cache = decode(params, cache, *step)
        got = np.asarray(logits, np.float32)[:, 0, :512]
        _close(got, want[np.arange(B), pos])
        if impl == "pallas":
            logits, other = direct(params, other, *step)
            np.testing.assert_array_equal(
                got.argmax(-1), np.asarray(logits)[:, 0, :512].argmax(-1))


def test_readmitted_slot_starts_from_zero_state(model):
    """One slot serves a longer request, then a short one: the short one's
    state, conv inputs, latent rows and next logits equal those of a
    scheduler that served it alone, bit for bit; no state of the earlier
    request leaks in."""
    cfg, params, tokens, _want = model
    bundle = build(cfg.replace(decode_impl="pallas"))
    short = tokens[1, :5].tolist()

    def serve(prompts):
        sched = BatchScheduler(bundle, params, batch_size=1, max_len=64)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            sched.submit(r)
        sched.run()
        return sched, reqs[-1]

    reused, r1 = serve([tokens[0].tolist(), short])
    fresh, r2 = serve([short])
    assert r1.generated == r2.generated
    depth = len(short) + 3                    # positions written for it
    for name in ("kda0", "kda1", "kda3"):
        for leaf in ("conv", "state"):
            np.testing.assert_array_equal(np.asarray(reused.cache[name][leaf]),
                                          np.asarray(fresh.cache[name][leaf]))
    np.testing.assert_array_equal(
        np.asarray(reused.cache["attention2"]["latent"])[..., :depth],
        np.asarray(fresh.cache["attention2"]["latent"])[..., :depth])
    tok = jnp.asarray([[r1.generated[-1]]], jnp.int32)
    pos = jnp.asarray([depth], jnp.int32)
    a, _ = bundle.decode_fn(params, reused.cache, tok, pos)
    b, _ = bundle.decode_fn(params, fresh.cache, tok, pos)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_state_bytes_are_gauged_as_part_of_the_cache(model):
    from repro.observability.metrics import get_registry

    cfg, params, _tokens, _want = model
    BatchScheduler(build(cfg), params, batch_size=2, max_len=32)
    reg = get_registry()
    # 3 KDA layers: (3 conv inputs x 3 x 64 channels + 4 x 16 x 16) x 4 B
    state = 3 * 2 * (3 * 192 + 4 * 16 * 16) * 4
    latent = 2 * 40 * 32 * 4
    assert reg.gauge("serving.state_bytes").value == state
    assert reg.gauge("serving.cache_bytes").value == state + latent


def test_published_cut_state_and_cache_bytes():
    """The benchmark's cut, 4 slots of 2048: a fixed 2 MiB float32 state a
    slot a KDA layer (20 layers, 168 MB) and its convs' last 3 inputs,
    beside 66 MB of latent rows in the 7 MLA layers."""
    cfg = get_config(ARCH).replace(experts_held=16)
    cache = jax.eval_shape(lambda: build(cfg).init_cache(4, 2048))
    state = sum(c["state"].size * 4 for c in cache.values() if "state" in c)
    conv = sum(c["conv"].size * 2 for c in cache.values() if "conv" in c)
    latent = sum(c["latent"].size * 2 for c in cache.values()
                 if "latent" in c)
    assert state == 20 * 4 * 2 * 2**20 == 167772160
    assert conv == 20 * 4 * 3 * 3 * 4096 * 2
    assert latent == 7 * 4 * 576 * 2048 * 2 == 66060288


@pytest.mark.parametrize("mutation", ["rope_applied", "dt_bias_dropped",
                                      "beta_ignored", "k_not_normalised",
                                      "gate_dropped"])
def test_mutations_fail_the_comparison(model, mutation, monkeypatch):
    cfg, params, tokens, want = model
    params = jax.tree.map(lambda x: x, params)
    kdas = ("kda0", "kda1", "kda3")
    if mutation == "rope_applied":
        cfg = cfg.replace(use_rope=True)
    elif mutation == "dt_bias_dropped":
        for run in kdas:
            params[run]["kda"]["dt_bias"] = jnp.zeros_like(
                params[run]["kda"]["dt_bias"])
    elif mutation == "beta_ignored":
        real = kda._gates
        monkeypatch.setattr(kda, "_gates", lambda cfg, p, x: (
            real(cfg, p, x)[0], jnp.ones_like(real(cfg, p, x)[1])))
    elif mutation == "k_not_normalised":
        real = kda._conv

        def conv(cfg, p, qkv, prev):
            q, k, v, last = real(cfg, p, qkv, prev)
            return q, k * 2.0, v, last
        monkeypatch.setattr(kda, "_conv", conv)
    else:
        for run in kdas:
            params[run]["kda"]["w_ga"] = jnp.zeros_like(
                params[run]["kda"]["w_ga"])
    err = np.abs(_forward(cfg, params, tokens) - want).max()
    assert err > 500 * REL * np.abs(want).max()


# ---------------------------------------------------------------------------
# the chunked delta rule and the decode kernel
# ---------------------------------------------------------------------------

def _kda_inputs(seed, b, s, h, d, a_log):
    """q, k as the recurrence takes them (normalised, q scaled), v, the
    decays at A = exp(a_log) over softplus of N(-1, 1), beta."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((b, s, h, d))) * d ** -0.5
    k = unit(rng.standard_normal((b, s, h, d)))
    v = rng.standard_normal((b, s, h, d))
    g = -np.exp(a_log) * np.log1p(np.exp(rng.standard_normal((b, s, h, d))
                                         - 1))
    beta = 1 / (1 + np.exp(-rng.standard_normal((b, s, h))))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))


def _sequential(q, k, v, g, beta):
    """The recurrence one token at a time from a zero state, in numpy
    float64."""
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g,
                                                            beta))
    b, s, h, d = k.shape
    st = np.zeros((b, h, d, v.shape[-1]))
    os_ = []
    for t in range(s):
        st = st * np.exp(g[:, t])[..., None]
        err = v[:, t] - np.einsum("bhkv,bhk->bhv", st, k[:, t])
        st = st + k[:, t, ..., None] * (beta[:, t, :, None] * err)[:, :, None]
        os_.append(np.einsum("bhkv,bhk->bhv", st, q[:, t]))
    return np.stack(os_, 1), st


@pytest.mark.parametrize("s,a_log", [(13, 0.0), (24, 0.0), (19, np.log(16.0)),
                                     (40, np.log(16.0)), (5, 1.0)])
def test_chunked_delta_rule_matches_the_sequential_recurrence(s, a_log):
    """Chunks of 8 over lengths that are and are not multiples of it (the
    state carried across two to five chunks, or one chunk padded), at
    mild decays and at strong ones (A = 16: a chunk's decay sum reaches
    hundreds, where exp(-G) would overflow float32): outputs and final
    state equal the token-by-token recurrence to float32 rounding
    (relative 1e-5)."""
    q, k, v, g, beta = _kda_inputs(s, 2, s, 3, 16, a_log)
    o, final = kda.kda_chunked(q, k, v, g, beta, 8)
    want_o, want_state = _sequential(q, k, v, g, beta)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=0,
                               atol=1e-5 * np.abs(want_o).max())
    np.testing.assert_allclose(np.asarray(final), want_state, rtol=0,
                               atol=1e-5 * np.abs(want_state).max())


@pytest.mark.parametrize("heads_block", [1, 2, 8])
def test_decode_kernel_matches_the_oracle_in_its_layer_alone(heads_block):
    """The interpreted kernel against ``ref.py`` on a stack of three
    layers: o and the updated layer agree to float32 rounding (the same
    operations in another order), and every other layer of the stack is
    left as it was, bit for bit."""
    nl, b, h, d = 3, 2, 4, 16
    q, k, v, g, beta = _kda_inputs(3, b, 1, h, d, 1.0)
    stack = jnp.asarray(np.random.default_rng(5).standard_normal(
        (nl, b, h, d, d)), jnp.float32)
    for layer in range(nl):
        o, got = kd_ops.kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                   beta[:, 0], stack, layer,
                                   heads_block=heads_block, interpret=True)
        want_o, want = kda_decode_ref(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                      beta[:, 0], stack, layer)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                                   rtol=0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got[layer]),
                                   np.asarray(want[layer]), rtol=0, atol=1e-5)
        others = [i for i in range(nl) if i != layer]
        np.testing.assert_array_equal(np.asarray(got)[others],
                                      np.asarray(stack)[others])


def test_decode_steps_continue_the_chunked_prefill():
    """Prefill's final state, stored transposed, advanced by the kernel
    token by token, reads out what the recurrence reads out over the
    whole sequence."""
    b, h, d, s = 1, 2, 16, 12
    q, k, v, g, beta = _kda_inputs(7, b, s, h, d, 1.0)
    want_o, want_state = _sequential(q, k, v, g, beta)
    _, final = kda.kda_chunked(q[:, :9], k[:, :9], v[:, :9], g[:, :9],
                               beta[:, :9], 8)
    stack = final.swapaxes(-1, -2)[None]
    for t in range(9, s):
        o, stack = kd_ops.kda_decode(q[:, t], k[:, t], v[:, t], g[:, t],
                                     beta[:, t], stack, 0, interpret=True)
        np.testing.assert_allclose(np.asarray(o), want_o[:, t], rtol=0,
                                   atol=1e-5 * np.abs(want_o).max())
    np.testing.assert_allclose(np.asarray(stack[0]).swapaxes(-1, -2),
                               want_state, rtol=0,
                               atol=1e-5 * np.abs(want_state).max())


# ---------------------------------------------------------------------------
# NoPE latent attention and the chip's share of the expert layer
# ---------------------------------------------------------------------------

def test_nope_latent_rows_are_not_rotated():
    """With ``use_rope`` off the latent row's key part and q_pe are the
    projections as they are, whatever the positions; with it on (as
    moonlight), they are rotated."""
    cfg = _cfg()
    p = init_params(jax.random.PRNGKey(2), attention.make_mla_specs(cfg),
                    jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 3, cfg.d_model))
    pos = jnp.arange(3) + 5
    _, q_pe, rows = attention._mla_project(cfg, p, x, pos)
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = np.asarray(jnp.einsum("bsd,dhk->bshk", x, p["wq"]))
    kv = np.asarray(jnp.einsum("bsd,dk->bsk", x, p["wkv_a"]))
    np.testing.assert_allclose(np.asarray(q_pe), q[..., nope:], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rows)[..., r:], kv[..., r:],
                               rtol=1e-6)
    _, q_rot, rot = attention._mla_project(cfg.replace(use_rope=True), p, x,
                                           pos)
    assert np.abs(np.asarray(rot)[..., r:] - kv[..., r:]).max() > 1e-2
    assert np.abs(np.asarray(q_rot) - q[..., nope:]).max() > 1e-2


def test_chip_shares_add_up_to_the_uncut_layer():
    """Eight sigmoid-routed experts with the correction bias over four
    chips of two: the shares' outputs, with the shared experts counted
    once, are the layer that holds all eight."""
    whole = _cfg(experts_held=8, expert_offset=0)
    params = init_params(jax.random.PRNGKey(4), M.make_moe_specs(whole),
                         jnp.float32)
    params["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(6),
                                                    (8,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, whole.d_model))
    want, _ = M.moe_ragged_forward(whole, params, x)
    shared = M.mlp_forward(whole, params["shared"], x)
    total = -3 * shared
    for chip in range(4):
        cfg = _cfg(experts_held=2, expert_offset=2 * chip)
        part = {**params, **{k: params[k][2 * chip:2 * chip + 2]
                             for k in ("w_gate", "w_up", "w_down")}}
        total = total + M.moe_ragged_forward(cfg, part, x)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def test_the_refusal_names_the_families_still_refused():
    """With KDA runs served, the refused families are RG-LRU's 'hybrid',
    xLSTM's 'ssm' and 'audio'; the scheduler and the serving tree name
    the family and why."""
    from repro.models.registry import serving_params

    for arch, family, why in (("recurrentgemma-2b", "hybrid", "RG-LRU"),
                              ("xlstm-350m", "ssm", "xLSTM"),
                              ("whisper-large-v3", "audio", "encoder")):
        cfg = reduced_config(arch)
        bundle = build(cfg)
        params = bundle.init_params(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match=f"'{family}'.*{why}"):
            BatchScheduler(bundle, params, batch_size=1, max_len=16)
        with pytest.raises(ValueError, match=f"'{family}'.*{why}"):
            serving_params(cfg, params)
