"""The held-expert decode kernel (``kernels/moe_decode``, interpret mode)
against the expert layer it stands in for at decode (``moe_routed`` over
the layer's slice of the stacks) and against its plain oracle, at the
two expert configurations' routing (6 of 64 experts with 8 held; 10 of
72 with 9 held) on tiny widths; its schedule; and the counter the
serving scheduler keeps of what it reads."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.kernels.moe_decode import kernel as K
from repro.kernels.moe_decode import ops
from repro.kernels.moe_decode.ref import moe_decode_ref
from repro.models import mlp as M
from repro.models.common import init_params
from repro.models.registry import build
from repro.observability.metrics import get_registry
from repro.serving.serve import BatchScheduler, Request

#: each configuration's routing at one chip's share: router width, experts
#: a token, held experts and the first of them
ROUTING = {
    "moonlight-16b-a3b": dict(num_experts=64, num_experts_per_tok=6,
                              experts_held=8, expert_offset=8),
    "granite-4.0-h-small": dict(num_experts=72, num_experts_per_tok=10,
                                experts_held=9, expert_offset=0),
}
LAYERS = 5
#: two tiles of the kernel's 128 over the expert's width
WIDTH = 256


def _cfg(arch, dtype="float32"):
    return reduced_config(arch).replace(dtype=dtype, moe_d_ff=WIDTH,
                                        **ROUTING[arch])


def _layer(cfg, seed):
    """One layer's router and shared experts, and the run's stacked expert
    matrices (LAYERS, n, D, F) / (LAYERS, n, F, D)."""
    dt = jnp.dtype(cfg.dtype)
    p = init_params(jax.random.PRNGKey(seed), M.make_moe_specs(cfg),
                    jnp.float32)
    if "router_bias" in p:
        p["router_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed + 1), (cfg.num_experts,))
    n, d, f = cfg.held_experts, cfg.d_model, cfg.moe_d_ff
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 3)
    stacks = {
        "w_gate": jax.random.normal(keys[0], (LAYERS, n, d, f)) / d ** 0.5,
        "w_up": jax.random.normal(keys[1], (LAYERS, n, d, f)) / d ** 0.5,
        "w_down": jax.random.normal(keys[2], (LAYERS, n, f, d)) / f ** 0.5,
    }
    return p, {k: v.astype(dt) for k, v in stacks.items()}


def _forced(cfg, rows, scenario, rng):
    """(experts (T, k), gate weights (T, k)) for a routing the router is
    not asked for: ``none_held`` every pair to experts held elsewhere;
    ``one_expert`` every row to one held expert, the rest elsewhere;
    ``elsewhere`` about half of each row's pairs held here."""
    k, n, off = cfg.num_experts_per_tok, cfg.held_experts, cfg.expert_offset
    held = np.arange(off, off + n)
    far = np.setdiff1d(np.arange(cfg.num_experts), held)
    idx = []
    for _ in range(rows):
        if scenario == "none_held":
            pick = rng.choice(far, k, replace=False)
        elif scenario == "one_expert":
            pick = np.r_[off + 2, rng.choice(far, k - 1, replace=False)]
        else:
            h = k // 2
            pick = np.r_[rng.choice(held, h, replace=False),
                         rng.choice(far, k - h, replace=False)]
        idx.append(rng.permutation(pick))
    w = rng.uniform(0.1, 1.0, (rows, k)).astype(np.float32)
    return (jnp.asarray(np.stack(idx), jnp.int32),
            jnp.asarray(w / w.sum(1, keepdims=True)))


CASES = [
    # (arch, routing, rows, layer of the stack, dtype)
    ("moonlight-16b-a3b", "router", 4, 0, "float32"),
    ("moonlight-16b-a3b", "router", 1, LAYERS - 1, "float32"),
    ("moonlight-16b-a3b", "none_held", 4, 2, "float32"),
    ("moonlight-16b-a3b", "one_expert", 3, LAYERS - 1, "float32"),
    ("moonlight-16b-a3b", "elsewhere", 2, 0, "float32"),
    ("moonlight-16b-a3b", "repeated", 4, 2, "float32"),
    ("moonlight-16b-a3b", "router", 4, 2, "bfloat16"),
    ("granite-4.0-h-small", "router", 4, 2, "float32"),
    ("granite-4.0-h-small", "router", 2, 0, "float32"),
    ("granite-4.0-h-small", "none_held", 1, LAYERS - 1, "float32"),
    ("granite-4.0-h-small", "one_expert", 4, 0, "float32"),
    ("granite-4.0-h-small", "elsewhere", 3, 2, "float32"),
    ("granite-4.0-h-small", "repeated", 4, LAYERS - 1, "float32"),
    ("granite-4.0-h-small", "router", 3, LAYERS - 1, "bfloat16"),
]


@pytest.mark.parametrize("arch,routing,rows,layer,dtype", CASES)
def test_kernel_matches_the_layer_it_replaces(monkeypatch, arch, routing,
                                              rows, layer, dtype):
    """The decode layer with the kernel reading layer ``layer`` of the
    whole stacks equals ``moe_routed`` over that layer's slice, shared
    experts included; the kernel's routed part equals the oracle's; and
    it reports the held experts that some row routes to. ``repeated``:
    the rows of empty slots repeat a live row's token."""
    cfg = _cfg(arch, dtype)
    p, stacks = _layer(cfg, seed=len(routing) + rows)
    rng = np.random.default_rng(rows + 10 * layer)
    x = jax.random.normal(jax.random.PRNGKey(layer), (rows, 1, cfg.d_model))
    if routing == "repeated":
        x = jnp.broadcast_to(x[:1], x.shape)
    x = x.astype(cfg.dtype)
    if routing in ("none_held", "one_expert", "elsewhere"):
        forced = _forced(cfg, rows, routing, rng)
        monkeypatch.setattr(M, "route", lambda cfg, p, xt: forced)
    idx, w = M.route(cfg, p, x[:, 0])

    whole = {**p, **stacks}
    one = {**p, **{k: v[layer] for k, v in stacks.items()}}
    got, read = M.moe_ragged_forward(cfg, whole, x, layer)
    want, _ = M.moe_ragged_forward(cfg, one, x)
    local = np.asarray(idx) - cfg.expert_offset
    routed = set(local[(local >= 0) & (local < cfg.held_experts)].tolist())
    assert int(read) == len(routed)
    if routing == "none_held":
        assert not routed
    if routing == "one_expert":
        assert routed == {2}

    part, _ = ops.moe_decode(x[:, 0], idx - cfg.expert_offset, w,
                             stacks["w_gate"], stacks["w_up"],
                             stacks["w_down"], layer)
    oracle = moe_decode_ref(x[:, 0], idx - cfg.expert_offset, w,
                            stacks["w_gate"], stacks["w_up"],
                            stacks["w_down"], layer)
    scale = float(jnp.abs(oracle).max()) or 1.0
    if dtype == "float32":
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5 * float(jnp.abs(want).max()))
        np.testing.assert_allclose(np.asarray(part), np.asarray(oracle),
                                   atol=1e-5 * scale)
    else:
        # bfloat16 operands: the kernel keeps act(g) * u in float32 up to
        # the down matrix's operand, where the grouped matmul rounds g and
        # u too; both within a few bfloat16 roundings of the oracle
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=0.03 * float(jnp.abs(want.astype(jnp.float32)).max()))
        np.testing.assert_allclose(np.asarray(part), np.asarray(oracle),
                                   atol=0.02 * scale)
    if not routed:
        assert not np.asarray(part).any()


@pytest.mark.parametrize("active", [(), (3,), (0, 5), (1, 4, 6), tuple(range(8))])
def test_schedule_pads_with_the_last_routed_expert(active):
    """The routed held experts come first in ascending order, padded by
    repeating the last; over the whole list the weight index maps name
    each routed expert's tiles once and no other block, and one block
    when none is routed."""
    n, tiles = 8, 3
    routed = np.zeros((4, n), bool)
    for i, e in enumerate(active):
        routed[i % 4, e] = True
    ids, count = ops.schedule(jnp.asarray(routed))
    ids = np.asarray(ids)
    assert int(count[0]) == len(active)
    assert ids[:len(active)].tolist() == list(active)
    assert (ids[len(active):] == (active[-1] if active else 0)).all()
    named = [tuple(int(v) for v in K.weight_block(
        j, t, jnp.asarray(ids), count, tiles=tiles))
        for j in range(n) for t in range(tiles)]
    distinct = list(dict.fromkeys(named))                 # in grid order
    assert distinct == ([(e, t) for e in active for t in range(tiles)]
                        or [(0, tiles - 1)])
    # a padded entry stays on the block fetched last
    last = len(active) * tiles
    assert set(named[last:]) <= {named[max(last - 1, 0)]}


def _served_counts(bundle, params, prompts, new):
    """Serve ``prompts`` through the scheduler; returns the growth of
    the decode-step and expert counters."""
    names = ("serving.decode_steps", "moe.experts_read", "moe.experts_held")

    def counts():
        return [get_registry().snapshot()["counters"].get(k, 0)
                for k in names]

    before = counts()
    sched = BatchScheduler(bundle, params, batch_size=2, max_len=64)
    for i, prompt in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=prompt, max_new_tokens=new))
    sched.run()
    return [a - b for a, b in zip(counts(), before)], sched


def test_scheduler_counts_the_held_experts_read():
    """Every row routed to one held expert (a correction bias on it and on
    experts held elsewhere): each decode step reads one held expert a
    layer; a dense model's scheduler keeps no such counter."""
    cfg = reduced_config("moonlight-16b-a3b").replace(
        dtype="float32", decode_impl="pallas")
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    held, k = cfg.expert_offset + 1, cfg.num_experts_per_tok
    far = [e for e in range(cfg.num_experts)
           if not cfg.expert_offset <= e < cfg.expert_offset
           + cfg.held_experts][:k - 1]
    bias = jnp.zeros(cfg.num_experts).at[held].set(100.0).at[
        jnp.asarray(far)].set(50.0)
    moe = params["layers"]["moe"]
    params["layers"]["moe"] = {
        **moe, "router_bias": jnp.broadcast_to(bias, moe["router_bias"].shape)}
    prompts = [list(range(5, 17)), list(range(30, 39))]
    (steps, read, seen), _ = _served_counts(bundle, params, prompts, new=6)
    layers = cfg.num_layers - cfg.first_dense_layers
    assert steps > 0
    assert read == steps * layers
    assert seen == steps * layers * cfg.held_experts

    dense = build(reduced_config("qwen2-0.5b").replace(decode_impl="pallas"))
    (steps, read, seen), sched = _served_counts(
        dense, dense.init_params(jax.random.PRNGKey(0)), prompts, new=4)
    assert steps > 0 and read == seen == 0
    assert len(sched.decode_step(sched.params, sched.cache, sched.tokens,
                                 sched.pos, sched.active)) == 3
