"""Mamba-2 layers beside attention (granite-4.0-h), softmax top-k routing
into the no-drop expert layer, and the recurrent state that the serving
scheduler carries beside the KV cache, against the plain float32
reference that the benchmark keeps (``bench/refs/granite_moe_hybrid_lm.py``)
at the reduced size of ``granite-4.0-h-small`` on seeded random weights."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.kernels.ssm_decode import ops as sd_ops
from repro.kernels.ssm_decode.ref import ssm_decode_ref
from repro.models import mamba2
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
    "granite_moe_hybrid_lm_ref", BENCH / "refs" / "granite_moe_hybrid_lm.py")
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

ARCH = "granite-4.0-h-small"
SEED = 2**31 + 11
#: the reduced config as the reference's configuration file states it:
#: chip 0 of 2, holding experts 0-3 of 8
MODEL = {
    "model_type": "granitemoehybrid", "num_hidden_layers": 4,
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 512, "intermediate_size": 64,
    "shared_intermediate_size": 128, "num_local_experts": 4,
    "num_experts_per_tok": 3, "tie_word_embeddings": True,
    "rms_norm_eps": 1e-5, "embedding_multiplier": 12.0,
    "residual_multiplier": 0.22, "attention_multiplier": 0.0078125,
    "logits_scaling": 16.0,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_expand": 0.5, "hidden_act": "silu",
    "position_embedding_type": "nope", "attention_bias": False,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "normalization_function": "rmsnorm",
    "published": {"num_local_experts": 8},
    "expert_parallel": {"chips": 2, "chip": 0, "first_expert": 0},
}
#: program at float32 against the float32 reference: the same arithmetic
#: in another order (chunked SSD against the token-by-token scan, grouped
#: against dense experts), so logits agree to float32 rounding (measured
#: 1.8e-8 at logits of magnitude 0.025: a relative 1e-6); every mutation
#: below moves them by a relative 0.05 or more
REL = 1e-4
B, S = 2, 24


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


def _close(got, want):
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=REL * np.abs(want).max())


def test_runs_follow_the_published_order():
    cfg = get_config(ARCH).replace(num_layers=20)
    runs = [(r.name, r.mixer, r.layers) for r in transformer.layer_runs(cfg)]
    assert runs == [("mamba0", "mamba", 5), ("attention5", "attention", 1),
                    ("mamba6", "mamba", 9), ("attention15", "attention", 1),
                    ("mamba16", "mamba", 4)]
    assert [r.name for r in transformer.layer_runs(_cfg())] == \
        ["mamba0", "attention2", "mamba3"]


def test_forward_matches_reference(model):
    cfg, params, tokens, want = model
    _close(_forward(cfg, params, tokens), want)


@pytest.mark.parametrize("impl", ["direct", "pallas"])
def test_prefill_then_decode_matches_reference(model, impl):
    """Prefill 13 and 20 tokens (neither a multiple of the chunk of 8) into
    the two rows of the cache, then decode the rest one token a step at
    per-row positions, each step's logits against the reference's
    forward. With ``pallas`` the held-expert layer is the ``moe_decode``
    kernel, and each step's tokens equal the direct decode's."""
    cfg, params, tokens, want = model
    bundle = build(cfg.replace(decode_impl=impl))
    direct = jax.jit(build(cfg.replace(decode_impl="direct")).decode_fn)
    cache = bundle.init_cache(B, 32)
    assert set(cache) == {"mamba0", "attention2", "mamba3"}
    assert cache["mamba0"]["ssm"].shape == (2, B, 16, 64)
    assert cache["attention2"]["k"].shape == (1, B, 2, 32, 32)
    start = (13, 20)
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


def test_scheduler_serves_the_reference_greedy_tokens(model):
    """Two requests of 13 and 24 tokens co-batched through the scheduler,
    with the interpreted kernel: every served token is the float32
    reference's best at its position, up to float32 rounding of the
    logits (a gap at most the agreement tolerance)."""
    cfg, params, tokens, _want = model
    bundle = build(cfg.replace(decode_impl="pallas"))
    sched = BatchScheduler(bundle, params, batch_size=2, max_len=64)
    prompts = [tokens[0, :13].tolist(), tokens[1].tolist()]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=10)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    sched.run()
    seqs = np.zeros((2, 24 + 9), np.int32)
    rows, served = [], []
    for i, r in enumerate(reqs):
        seq = r.prompt + r.generated[:-1]
        seqs[i, :len(seq)] = seq
        rows += [(i, len(r.prompt) - 1 + j) for j in range(10)]
        served += r.generated
    ref = REF.logits_at(MODEL, SEED, seqs, np.asarray(rows, np.int32))
    gap = ref.max(-1) - ref[np.arange(len(served)), served]
    assert gap.max() <= REL * np.abs(ref).max()


def test_readmitted_slot_reproduces_a_fresh_run(model):
    """One slot serves a long request, then a short one: the short one's
    state, KV rows and next logits equal those of a scheduler that served
    it alone, bit for bit; no state of the earlier request leaks in."""
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
    for name in ("mamba0", "mamba3"):
        for leaf in ("conv", "ssm"):
            np.testing.assert_array_equal(np.asarray(reused.cache[name][leaf]),
                                          np.asarray(fresh.cache[name][leaf]))
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(reused.cache["attention2"][leaf])[..., :depth],
            np.asarray(fresh.cache["attention2"][leaf])[..., :depth])
    tok = jnp.asarray([[r1.generated[-1]]], jnp.int32)
    pos = jnp.asarray([depth], jnp.int32)
    a, _ = bundle.decode_fn(params, reused.cache, tok, pos)
    b, _ = bundle.decode_fn(params, fresh.cache, tok, pos)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_state_bytes_are_gauged_as_part_of_the_cache(model):
    from repro.observability.metrics import get_registry

    cfg, params, _tokens, _want = model
    sched = BatchScheduler(build(cfg), params, batch_size=2, max_len=32)
    reg = get_registry()
    # 3 Mamba layers: (3 conv inputs x 96 channels + 16 x 64 state) x 4 B
    state = 3 * 2 * (3 * 96 + 16 * 64) * 4
    kv = 2 * 2 * 2 * 32 * 32 * 4
    assert reg.gauge("serving.state_bytes").value == state
    assert reg.gauge("serving.cache_bytes").value == state + kv


def test_published_cut_state_and_cache_bytes():
    """The benchmark's cut, 20 layers (18 Mamba, 2 attention), 4 slots of
    2048: a fixed 4 MiB float32 state a slot a Mamba layer and its conv's
    last 3 inputs, beside 67 MB of KV rows."""
    cfg = get_config(ARCH).replace(num_layers=20, experts_held=9)
    cache = jax.eval_shape(lambda: build(cfg).init_cache(4, 2048))
    ssm = sum(c["ssm"].size * 4 for n, c in cache.items() if "ssm" in c)
    conv = sum(c["conv"].size * 2 for n, c in cache.items() if "conv" in c)
    kv = sum(leaf.size * 2 for n, c in cache.items() if "k" in c
             for leaf in c.values())
    assert ssm == 18 * 4 * 4 * 2**20
    assert conv == 18 * 4 * 3 * 8448 * 2
    assert kv == 2 * 2 * 4 * 8 * 128 * 2048 * 2 == 67108864


@pytest.mark.parametrize("mutation", ["D_dropped", "conv_bias_dropped",
                                      "softmax_over_all", "state_not_decayed"])
def test_mutations_fail_the_comparison(model, mutation, monkeypatch):
    cfg, params, tokens, want = model
    params = jax.tree.map(lambda x: x, params)
    if mutation == "D_dropped":
        for run in ("mamba0", "mamba3"):
            params[run]["ssm"]["D"] = jnp.zeros_like(params[run]["ssm"]["D"])
    elif mutation == "conv_bias_dropped":
        for run in ("mamba0", "mamba3"):
            params[run]["ssm"]["conv_b"] = jnp.zeros_like(
                params[run]["ssm"]["conv_b"])
    elif mutation == "softmax_over_all":
        def route(cfg, p, xt):
            logits = xt.astype(jnp.float32) @ p["router"]
            w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1),
                                   cfg.num_experts_per_tok)
            return idx, w
        monkeypatch.setattr(M, "route", route)
    else:
        monkeypatch.setattr(mamba2, "_dt", lambda p, raw: (
            jax.nn.softplus(raw + p["dt_bias"]),
            jnp.zeros_like(p["A_log"])))
    err = np.abs(_forward(cfg, params, tokens) - want).max()
    assert err > 500 * REL * np.abs(want).max()


# ---------------------------------------------------------------------------
# chunked SSD and the decode kernel
# ---------------------------------------------------------------------------

def _ssm_inputs(seed, b, s, h, p, g, n):
    rng = np.random.default_rng(seed)
    f = jnp.float32
    return (jnp.asarray(rng.standard_normal((b, s, h, p)), f),
            jax.nn.softplus(jnp.asarray(rng.standard_normal((b, s, h)) - 2,
                                        f)),
            -jnp.exp(jnp.asarray(rng.standard_normal(h), f)),
            jnp.asarray(rng.standard_normal((b, s, g, n)), f),
            jnp.asarray(rng.standard_normal((b, s, g, n)), f))


def _sequential(x, dt, a, bm, cm):
    """The recurrence one token at a time from a zero state, in numpy
    float64."""
    x, dt, a, bm, cm = (np.asarray(t, np.float64) for t in (x, dt, a, bm, cm))
    (b, _, h, p), (g, n) = x.shape, bm.shape[2:]
    st = np.zeros((b, h, p, n))
    bh, ch = np.repeat(bm, h // g, axis=2), np.repeat(cm, h // g, axis=2)
    ys = []
    for t in range(x.shape[1]):
        st = st * np.exp(dt[:, t] * a)[..., None, None] + \
            (dt[:, t, :, None] * x[:, t])[..., None] * bh[:, t, :, None, :]
        ys.append(np.einsum("bhpn,bhn->bhp", st, ch[:, t]))
    return np.stack(ys, 1), st


@pytest.mark.parametrize("s,groups", [(13, 1), (24, 1), (5, 2), (19, 2)])
def test_chunked_ssd_matches_the_sequential_scan(s, groups):
    """Chunks of 8 over lengths that are and are not multiples of it (the
    state carried across two or three chunks, or one chunk padded), one
    or two groups of B and C: outputs and final state equal the
    token-by-token recurrence to float32 rounding (relative 1e-5)."""
    b, h, p, n = 2, 4, 16, 8
    x, dt, a, bm, cm = _ssm_inputs(s, b, s, h, p, groups, n)
    y, final = mamba2.ssd_chunked(x, dt, a, bm, cm, 8)
    want_y, want_state = _sequential(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=0,
                               atol=1e-5 * np.abs(want_y).max())
    np.testing.assert_allclose(np.asarray(final), want_state, rtol=0,
                               atol=1e-5 * np.abs(want_state).max())


@pytest.mark.parametrize("groups,block", [(1, 32), (1, 2048), (2, 16)])
def test_decode_kernel_matches_the_oracle_in_its_layer_alone(groups, block):
    """The interpreted kernel against ``ref.py`` on a stack of three
    layers: y and the updated layer agree to float32 rounding (the same
    operations in another order), and every other layer of the stack is
    left as it was, bit for bit."""
    nl, b, h, p, n = 3, 2, 4, 16, 16
    x, dt, a, bm, cm = _ssm_inputs(3, b, 1, h, p, groups, n)
    d = jnp.asarray(np.random.default_rng(4).standard_normal(h), jnp.float32)
    stack = jnp.asarray(np.random.default_rng(5).standard_normal(
        (nl, b, n, h * p)), jnp.float32)
    for layer in range(nl):
        y, got = sd_ops.ssm_decode(x[:, 0], dt[:, 0], a, d, bm[:, 0],
                                   cm[:, 0], stack, layer, block=block,
                                   interpret=True)
        want_y, want = ssm_decode_ref(x[:, 0], dt[:, 0], a, d, bm[:, 0],
                                      cm[:, 0], stack, layer)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                                   rtol=0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got[layer]),
                                   np.asarray(want[layer]), rtol=0, atol=1e-5)
        others = [i for i in range(nl) if i != layer]
        np.testing.assert_array_equal(np.asarray(got)[others],
                                      np.asarray(stack)[others])


def test_decode_kernel_state_is_the_published_state_transposed():
    """One step from zero state is dt x (x) B in the published (H, P, N),
    stored at ``[n, h*P + p]``."""
    b, h, p, n = 1, 2, 16, 8
    x, dt, a, bm, cm = _ssm_inputs(6, b, 1, h, p, 1, n)
    stack = jnp.zeros((1, b, n, h * p), jnp.float32)
    _, got = sd_ops.ssm_decode(x[:, 0], dt[:, 0], a, jnp.zeros(h), bm[:, 0],
                               cm[:, 0], stack, 0, interpret=True)
    s = np.einsum("bhp,bn->bhpn", np.asarray(dt[:, 0, :, None] * x[:, 0]),
                  np.asarray(bm[:, 0, 0]))
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(mamba2.to_kernel_layout(s)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# softmax routing and the chip's share of the expert layer
# ---------------------------------------------------------------------------

def test_softmax_gate_is_over_the_chosen_logits():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(2), M.make_moe_specs(cfg),
                         jnp.float32)
    assert "router_bias" not in params
    xt = jax.random.normal(jax.random.PRNGKey(3), (16, cfg.d_model))
    idx, w = M.route(cfg, params, xt)
    logits = np.asarray(xt) @ np.asarray(params["router"])
    top = np.sort(logits, -1)[:, ::-1][:, :3]
    want = np.exp(top - top[:, :1])
    np.testing.assert_allclose(np.asarray(w),
                               want / want.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_array_equal(
        np.sort(np.asarray(idx), -1), np.sort(np.argsort(-logits, -1)[:, :3],
                                              -1))


def test_chip_shares_add_up_to_the_uncut_layer():
    """Eight softmax-routed experts over four chips of two: the shares'
    outputs, with the shared expert counted once, are the layer that
    holds all eight."""
    whole = _cfg(experts_held=8, expert_offset=0)
    params = init_params(jax.random.PRNGKey(4), M.make_moe_specs(whole),
                         jnp.float32)
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
    for arch, family in (("recurrentgemma-2b", "hybrid"),
                         ("xlstm-350m", "ssm")):
        cfg = reduced_config(arch)
        bundle = build(cfg)
        with pytest.raises(ValueError, match=f"'{family}'.*recurrent|"
                                             f"recurrent.*'{family}'"):
            BatchScheduler(bundle, bundle.init_params(jax.random.PRNGKey(0)),
                           batch_size=1, max_len=16)
