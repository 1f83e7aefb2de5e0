"""Per-architecture smoke tests: reduced configs of the SAME family run a
forward/train step on CPU asserting output shapes + no NaNs; serving path
(prefill + decode) is exercised for every arch. The FULL configs are only
exercised as ShapeDtypeStruct trees (no allocation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.models.registry import SHAPES, ShapeCell, build
from repro.serving.serve import make_decode_step, make_prefill_step
from repro.training.train_step import (
    TrainConfig, init_train_state, make_train_step,
)

ARCHS = [a for a in ARCH_IDS if a != "aiida-demo-110m"]
RNG = np.random.default_rng(0)


def _batch_for(bundle, b, s):
    cfg = bundle.cfg
    cell = ShapeCell("smoke", "train", s, b)
    out = {}
    for k, v in bundle.batch_struct(cell).items():
        if v.dtype == jnp.int32:
            out[k] = jnp.asarray(RNG.integers(0, cfg.vocab_size, v.shape),
                                 jnp.int32)
        else:
            out[k] = jnp.asarray(RNG.normal(0, 1, v.shape), v.dtype)
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_forward_and_train_step(arch):
    cfg = reduced_config(arch)
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    batch = _batch_for(bundle, 2, 64)

    loss, metrics = bundle.loss_fn(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss {loss}"

    tcfg = TrainConfig()
    state = init_train_state(bundle, tcfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(bundle, tcfg))
    state, m = step(state, batch)
    assert bool(jnp.isfinite(m["loss"])), f"{arch}: train loss {m['loss']}"
    assert bool(jnp.isfinite(m["grad_norm"]))
    assert int(state["step"]) == 1
    # params actually moved (some leaves may legitimately have ~0 grads;
    # check the global update magnitude)
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(state["params"])))
    assert delta > 1e-3, f"{arch}: optimizer did not move params ({delta})"


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_serving_path(arch):
    cfg = reduced_config(arch)
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    b, s = 2, 32
    batch = _batch_for(bundle, b, s)
    cache = bundle.init_cache(b, s + 8)
    prefill = jax.jit(make_prefill_step(bundle))
    tok, cache = prefill(params, batch, cache)
    assert tok.shape == (b, 1)
    assert 0 <= int(tok.min()) and int(tok.max()) < cfg.vocab_size
    decode = jax.jit(make_decode_step(bundle))
    for i in range(3):
        tok, cache = decode(params, cache, tok, jnp.asarray(s + i))
        assert tok.shape == (b, 1)
        assert 0 <= int(tok.min()) and int(tok.max()) < cfg.vocab_size


def test_microbatched_grad_accumulation_matches_single():
    arch = "qwen2-0.5b"
    cfg = reduced_config(arch)
    bundle = build(cfg)
    batch = _batch_for(bundle, 4, 32)
    s1 = init_train_state(bundle, TrainConfig(microbatches=1),
                          jax.random.PRNGKey(0))
    s2 = jax.tree.map(lambda x: x, s1)
    step1 = jax.jit(make_train_step(bundle, TrainConfig(microbatches=1)))
    step4 = jax.jit(make_train_step(bundle, TrainConfig(microbatches=4)))
    s1, m1 = step1(s1, batch)
    s2, m2 = step4(s2, batch)
    # same data, same update (up to accumulation-order float noise)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-3)
    p1 = np.asarray(jax.tree.leaves(s1["params"])[0], np.float32)
    p2 = np.asarray(jax.tree.leaves(s2["params"])[0], np.float32)
    np.testing.assert_allclose(p1, p2, atol=5e-4)


def test_full_configs_match_assignment():
    """The full (non-reduced) configs carry the exact assigned numbers."""
    expect = {
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "moonshot-v1-16b-a3b": (48, 2048, 16, 16, 1408, 163840),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
    }
    for arch, (L, d, h, kv, ff, vocab) in expect.items():
        cfg = get_config(arch)
        assert cfg.num_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.num_heads == h, arch
        assert cfg.num_kv_heads == kv, arch
        assert cfg.d_ff == ff, arch
        assert cfg.vocab_size == vocab, arch


def test_moe_configs():
    grok = get_config("grok-1-314b")
    assert grok.num_experts == 8 and grok.num_experts_per_tok == 2
    moon = get_config("moonshot-v1-16b-a3b")
    assert moon.num_experts == 64 and moon.num_experts_per_tok == 6


def test_long_context_applicability():
    cell = SHAPES["long_500k"]
    runs = {a: build(get_config(a)).supports_cell(cell)[0] for a in ARCHS}
    assert runs["recurrentgemma-2b"] and runs["xlstm-350m"]
    assert sum(runs.values()) == 2   # everyone else skips


def test_chunked_attention_matches_direct():
    """The memory-efficient chunked path is numerically the direct path."""
    from repro.models import attention as A
    cfg = reduced_config("qwen3-4b")
    import jax.random as jr
    p = {
        k: v for k, v in zip(
            ["wq", "wk", "wv", "wo", "q_norm", "k_norm"],
            [0.02 * jr.normal(jr.PRNGKey(i), s) for i, s in enumerate([
                (cfg.d_model, cfg.num_heads, cfg.hd),
                (cfg.d_model, cfg.num_kv_heads, cfg.hd),
                (cfg.d_model, cfg.num_kv_heads, cfg.hd),
                (cfg.num_heads, cfg.hd, cfg.d_model),
                (cfg.hd,), (cfg.hd,)])])
    }
    x = jr.normal(jr.PRNGKey(9), (2, 64, cfg.d_model), jnp.float32)
    pos = jnp.arange(64, dtype=jnp.int32)
    cfg_direct = cfg.replace(attn_impl="direct", dtype="float32")
    cfg_chunk = cfg.replace(attn_impl="chunked", attn_kv_block=16,
                            dtype="float32")
    out_d = A.attn_forward(cfg_direct, p, x, pos, causal=True)
    out_c = A.attn_forward(cfg_chunk, p, x, pos, causal=True)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_c),
                               atol=2e-5)
