"""Smoke run of the engine's device path on a TPU, through the normal
entry points, at the published width of qwen2-0.5b.

    python chip_smoke.py              # one chip: serve + train phases
    python chip_smoke.py --chips 4    # four chips: sharded paths only

One process holds the chip(s) and runs the phases in order; the first
failed check raises and the script exits non-zero. There is no CPU
fallback: when JAX finds no TPU the script exits 1 before any phase.
Weights come from ``--seed``; nothing is downloaded.

One chip:

* **serve** — ``generate`` (published qwen2-0.5b, file-backed store,
  caching on) for prompts of two lengths; every node finishes with exit
  status 0 and in-vocabulary tokens, and a replayed prompt is a cache hit
  that runs zero decode steps. The engine's compiled decode step contains
  the Pallas kernel, and the kernel matches its ``jax.numpy`` reference at
  the served shapes within ``KERNEL_ATOL``.
* **train** — one ``TPUTrainJob`` (``reduced: false``) finishes with exit
  status 0, finite losses and a first loss within ``FIRST_LOSS_ATOL`` of
  ln(vocab).

Four chips (``--chips 4``):

* **sharded decode** — aiida-demo-110m at its published width, cut to
  ``SHARDED_DEPTH`` layers, heads sharded over ``model=4``, against the
  same weights on one device: at every step the greedy token is the same
  and the logits agree within ``LOGIT_ATOL``. The sharded run is fed the
  one-device tokens, and both use float32 activations and full-precision
  matmuls. The cut is forced by the random weights: each random layer
  multiplies the sharded program's summation-order differences by about
  2-3, so at 12 layers the logits drift by ~0.1 and greedy choices flip
  with no fault. Every layer is the same scanned body, so two layers
  exercise the whole sharded decode program.
* **fsdp train** — three qwen2-0.5b train steps at batch 4 on ``data=4``
  (FSDP) and on one device, from the same weights, cut to ``FSDP_DEPTH``
  layers, in float32 under full-precision matmuls. Every step's loss
  agrees within ``LOSS_RTOL``; the first step's gradient norm within
  ``GRAD_NORM_RTOL`` and its parameter update within ``UPDATE_RTOL``.
  Adam's first update is about lr * sign(grad) whatever the gradient's
  scale, so the update sees which examples the gradient came from and the
  gradient norm sees its scale. Later gradient norms are printed, not
  compared: the random init makes the gradient norm chaotic in the
  weights (a 1e-6 relative nudge of the weights moves the first norm of
  an 8-layer stack by ~1.5x on one device), and the first update is such
  a nudge. The same cause limits the depth.

Each phase prints its compile seconds (backend compiles, persistent-cache
reads included) and peak device memory. The last line of standard output
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen2-0.5b"
SHARDED_ARCH = "aiida-demo-110m"
SHARDED_DEPTH = 2
PROMPT_LENGTHS = (12, 12, 24, 24, 24)
NEW_TOKENS = 16
#: max |kernel - reference| for the bf16 decode kernel at the served shapes
KERNEL_ATOL = 2e-2
#: |first loss - ln(vocab)| for random weights
FIRST_LOSS_ATOL = 0.25
#: max |logit gap| between the heads-sharded decode and one device
LOGIT_ATOL = 1e-3
#: depth of the FSDP comparison (float32, full-precision matmuls)
FSDP_DEPTH = 2
#: relative loss gap between data=4 FSDP and one device, every step
LOSS_RTOL = 1e-4
#: relative gap of the first step's gradient norm
GRAD_NORM_RTOL = 1e-4
#: |update(data=4) - update(one device)| / |update(one device)|, first step
UPDATE_RTOL = 1e-3

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    print(f"  ok: {what}", flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, per phase."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.seconds += duration_secs

    def _event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        import jax

        print(f"[{name}]", flush=True)
        s0, h0, t0 = self.seconds, self.cache_hits, time.monotonic()
        yield
        stats = jax.devices()[0].memory_stats() or {}
        print(f"[{name}] done: wall {time.monotonic() - t0:.2f} s, compile "
              f"{self.seconds - s0:.2f} s, persistent-cache hits "
              f"{self.cache_hits - h0}, device-0 peak bytes "
              f"{stats.get('peak_bytes_in_use', 'n/a')}", flush=True)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def check_decode_kernel(cfg, batch: int, max_len: int, seed: int) -> None:
    """The flash-decode kernel against its reference at the served shapes,
    reading the last layer of a two-layer stacked cache."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import interpret_default
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref

    check(not interpret_default(), "decode kernel compiled, not interpreted")
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    dt = cfg.activation_dtype
    q = jax.random.normal(kq, (batch, cfg.num_heads, cfg.hd), dt)
    stack = (2, batch, cfg.kv_heads_eff, cfg.hd, max_len)
    k = jax.random.normal(kk, stack, dt)
    v = jax.random.normal(kv, stack, dt)
    lens = jnp.asarray([1, 37, max_len - 28, max_len][:batch], jnp.int32)
    got = decode_attention(q, k, v, lens, layer=1,
                           block_kv=cfg.attn_kv_block)
    want = decode_attention_ref(q, k, v, lens, layer=1)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) -
                                want.astype(jnp.float32))))
    check(err <= KERNEL_ATOL, f"decode kernel vs ref at (B={batch}, "
          f"H={cfg.num_heads}, Hkv={cfg.kv_heads_eff}, hd={cfg.hd}, "
          f"Smax={max_len}) {dt}: max abs err {err:.3e} <= {KERNEL_ATOL}")


def serve_phase(workdir: Path, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.caching import enable_caching
    from repro.configs import get_config
    from repro.core.datatypes import ArrayData, Int, Str
    from repro.engine.launch import run_get_node
    from repro.engine.runner import Runner, set_default_runner
    from repro.observability.metrics import get_registry
    from repro.provenance.store import configure_store
    from repro.serving.inference import generate, get_engine, reset_engines

    published = get_config(ARCH)
    store = configure_store(str(workdir / "profile.db"))
    set_default_runner(Runner(store=store))
    steps = get_registry().counter("serving.decode_steps")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, published.vocab_size, n).tolist()
               for n in PROMPT_LENGTHS]

    def call(prompt):
        res, node = run_get_node(
            generate.process_class, arch=Str(ARCH),
            prompt=ArrayData(np.asarray(prompt, np.int32)),
            max_new_tokens=Int(NEW_TOKENS), seed=Int(seed), eos_id=Int(-1))
        status = store.get_node(node.pk)["exit_status"]
        check(status == 0, f"generate pk={node.pk} exit_status {status}")
        return node, np.asarray(res["tokens"].value)

    with enable_caching():
        first = []
        for prompt in prompts:
            t0 = time.monotonic()
            _, toks = call(prompt)
            in_vocab = (toks >= 0) & (toks < published.vocab_size)
            check(toks.shape == (NEW_TOKENS,) and bool(in_vocab.all()),
                  f"prompt of {len(prompt)}: {NEW_TOKENS} tokens in vocab "
                  f"({time.monotonic() - t0:.2f} s) {toks.tolist()}")
            first.append(toks)
        before = steps.value
        node, toks = call(prompts[0])
    attrs = json.loads(store.get_node(node.pk).get("attributes") or "{}")
    check("cached_from" in attrs and steps.value == before
          and np.array_equal(toks, first[0]),
          "replayed prompt is a cache hit with 0 new decode steps")

    eng = get_engine(ARCH, seed, need_len=max(PROMPT_LENGTHS) + NEW_TOKENS)
    check(eng.cfg == published.replace(decode_impl="pallas"),
          f"served config is the published {ARCH} (d_model "
          f"{eng.cfg.d_model}, {eng.cfg.num_layers} layers, vocab "
          f"{eng.cfg.vocab_size})")
    sched = eng.scheduler
    text = sched.decode_step.lower(eng.params, sched.cache, sched.tokens,
                                   sched.pos, sched.active).as_text()
    check("tpu_custom_call" in text,
          "engine decode step contains the Pallas kernel (tpu_custom_call)")
    check_decode_kernel(eng.cfg, sched.batch_size, sched.max_len, seed)
    reset_engines()


def train_phase(seed: int) -> None:
    from repro.calcjobs import TPUTrainJob
    from repro.configs import get_config
    from repro.core.datatypes import Dict
    from repro.engine.launch import run_get_node
    from repro.engine.runner import default_runner

    config = {"arch": ARCH, "reduced": False, "steps": 5, "batch": 2,
              "seq": 64, "seed": seed}
    res, node = run_get_node(TPUTrainJob, config=Dict(config))
    status = default_runner().store.get_node(node.pk)["exit_status"]
    check(status == 0, f"TPUTrainJob pk={node.pk} exit_status {status}")
    losses = res["metrics"].value["losses"]
    print(f"  losses {losses}", flush=True)
    check(len(losses) == 5 and all(math.isfinite(x) for x in losses),
          "5 finite losses")
    ln_v = math.log(get_config(ARCH).vocab_size)
    check(abs(losses[0] - ln_v) <= FIRST_LOSS_ATOL,
          f"first loss {losses[0]:.4f} within {FIRST_LOSS_ATOL} of "
          f"ln(vocab) {ln_v:.4f}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _decode(bundle, params, prompt, steps: int, *, feed=None, mesh=None):
    """Prefill + ``steps`` decode steps; returns each step's greedy token
    and its logits, ``(B, steps + 1)`` and ``(B, steps + 1, vocab)``.

    The tokens fed back are the model's own greedy choices, or ``feed``
    (teacher forcing). Under ``mesh`` the weights and the cache are placed
    by the serving rules first.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.sharding import make_rules, tree_named_shardings
    from repro.models.common import axis_rules

    vocab = bundle.cfg.vocab_size
    b, n = prompt.shape
    rules_ctx = contextlib.nullcontext()
    cache = bundle.init_cache(b, n + steps + 1)
    if mesh is not None:
        rules = make_rules(bundle.cfg, mesh, fsdp=False)
        check(rules["kv_heads_sharded"] == "model", "KV heads sharded")
        rules_ctx = axis_rules(mesh, rules)
        params = jax.device_put(params, tree_named_shardings(
            bundle.param_shapes(), bundle.param_axes(), rules, mesh))
        cache = jax.device_put(cache, tree_named_shardings(
            cache, bundle.cache_axes(), rules, mesh))
    with rules_ctx:
        prefill = jax.jit(bundle.prefill_fn)
        decode = jax.jit(bundle.decode_fn, donate_argnums=(1,))
        logits, cache = prefill(params, {"tokens": prompt}, cache)
        out = [np.asarray(logits[:, -1, :vocab])]
        pos = jnp.full((b,), n, jnp.int32)
        if mesh is not None:
            text = decode.lower(params, cache, prompt[:, :1], pos).as_text()
            check("tpu_custom_call" in text,
                  "sharded decode step contains the Pallas kernel")
        for i in range(steps):
            tok = (out[-1].argmax(-1) if feed is None else feed[:, i])
            logits, cache = decode(params, cache,
                                   jnp.asarray(tok, jnp.int32)[:, None],
                                   pos + i)
            out.append(np.asarray(logits[:, -1, :vocab]))
    logits = np.stack(out, axis=1)
    return logits.argmax(-1), logits


def sharded_decode_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, make_serving_mesh
    from repro.models.registry import build

    cfg = get_config(SHARDED_ARCH).replace(
        num_layers=SHARDED_DEPTH, decode_impl="pallas", dtype="float32")
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(seed))
    prompt = jnp.asarray(np.random.default_rng(seed).integers(
        1, cfg.vocab_size, (4, 16)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        one, one_logits = _decode(bundle, params, prompt, NEW_TOKENS)
        four, four_logits = _decode(bundle, params, prompt, NEW_TOKENS,
                                    feed=one, mesh=make_serving_mesh(1, 4))
    err = float(np.max(np.abs(one_logits - four_logits)))
    print(f"  one device : {one.tolist()}\n  model=4    : {four.tolist()}",
          flush=True)
    check(err <= LOGIT_ATOL, f"{SHARDED_ARCH} heads-sharded logits within "
          f"{LOGIT_ATOL} of one device's (max abs err {err:.3e})")
    check(np.array_equal(one, four),
          f"{SHARDED_ARCH} heads-sharded greedy tokens equal one device's")


def _train_steps(bundle, tcfg, state0, batches, mesh=None):
    """Train steps from the host state ``state0``, on one device or FSDP
    over ``mesh``. Returns each step's (loss, grad norm) and the host
    parameters after the first step."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import make_rules, tree_named_shardings
    from repro.models.common import axis_rules
    from repro.training.train_step import (make_train_step, train_state_axes,
                                           train_state_shapes)

    rules_ctx, state_sh, batch_sh = contextlib.nullcontext(), None, None
    if mesh is not None:
        rules = make_rules(bundle.cfg, mesh, fsdp=True)
        check(rules["embed"] == ("data",), "weights sharded over data (FSDP)")
        rules_ctx = axis_rules(mesh, rules)
        state_sh = tree_named_shardings(train_state_shapes(bundle, tcfg),
                                        train_state_axes(bundle, tcfg),
                                        rules, mesh)
        batch_sh = NamedSharding(mesh, P("data"))
    with rules_ctx:
        state = jax.device_put(state0, state_sh)
        step = jax.jit(make_train_step(bundle, tcfg),
                       out_shardings=(state_sh, None), donate_argnums=(0,))
        out, first_params = [], None
        for batch in batches:
            state, metrics = step(state, jax.device_put(batch, batch_sh))
            out.append((float(metrics["loss"]), float(metrics["grad_norm"])))
            if first_params is None:
                first_params = jax.device_get(state["params"])
    del state
    return out, first_params


def fsdp_readings(cfg, seed: int, steps: int = 3) -> dict:
    """The same ``steps`` train steps at batch 4 on one device and FSDP on
    ``data=4``, in float32 under full-precision matmuls.

    Returns the per-step losses and gradient norms of both runs and
    ``update_gap``: |u4 - u1| / |u1|, where u is the first step's parameter
    update. Adam's first update is about lr * sign(grad) whatever the
    gradient's scale, so ``update_gap`` sees which examples and which
    direction the gradient came from, and the gradient norm sees its scale.
    """
    import jax
    import numpy as np

    from repro.configs import make_serving_mesh
    from repro.models.registry import build
    from repro.training.optim import OptimConfig
    from repro.training.train_step import TrainConfig, init_train_state

    bundle = build(cfg)
    tcfg = TrainConfig(optim=OptimConfig(lr=3e-4, total_steps=steps,
                                         warmup_steps=1))
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(steps):
        toks = rng.integers(0, cfg.vocab_size, (4, 65), dtype=np.int32)
        batches.append({"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    with jax.default_matmul_precision("highest"):
        state0 = jax.device_get(jax.jit(lambda: init_train_state(
            bundle, tcfg, jax.random.PRNGKey(seed)))())
        one, p_one = _train_steps(bundle, tcfg, state0, batches)
        four, p_four = _train_steps(bundle, tcfg, state0, batches,
                                    mesh=make_serving_mesh(data=4, model=1))
    sq_gap = sq_upd = 0.0
    for p0, p1, p4 in zip(*(jax.tree.leaves(t) for t in
                             (state0["params"], p_one, p_four))):
        p0, p1, p4 = (np.asarray(x, np.float64) for x in (p0, p1, p4))
        sq_gap += float(np.sum((p4 - p1) ** 2))
        sq_upd += float(np.sum((p1 - p0) ** 2))
    return {"one": one, "four": four,
            "update_norm": math.sqrt(sq_upd),
            "update_gap": math.sqrt(sq_gap / sq_upd)}


def fsdp_train_phase(seed: int) -> None:
    from repro.configs import get_config

    cfg = get_config(ARCH).replace(num_layers=FSDP_DEPTH, dtype="float32")
    r = fsdp_readings(cfg, seed)
    one, four = r["one"], r["four"]
    print(f"  (loss, grad norm) one device : {one}\n"
          f"  (loss, grad norm) data=4     : {four}\n"
          f"  first update norm {r['update_norm']:.6e}, relative gap "
          f"{r['update_gap']:.3e}", flush=True)
    check(all(math.isfinite(x) for pair in one + four for x in pair),
          "finite losses and gradient norms")
    loss_gap = max(abs(a[0] - b[0]) / abs(a[0]) for a, b in zip(one, four))
    check(loss_gap <= LOSS_RTOL,
          f"{ARCH} data=4 FSDP losses within {LOSS_RTOL} of one device "
          f"(max relative gap {loss_gap:.2e})")
    gn_gap = abs(one[0][1] - four[0][1]) / one[0][1]
    check(gn_gap <= GRAD_NORM_RTOL,
          f"first gradient norm within {GRAD_NORM_RTOL} of one device's "
          f"(relative gap {gn_gap:.2e})")
    check(r["update_norm"] > 0 and r["update_gap"] <= UPDATE_RTOL,
          f"first parameter update within {UPDATE_RTOL} of one device's "
          f"(relative gap {r['update_gap']:.2e})")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {len(devices)}x {dev.device_kind}; compile cache "
          f"{cache_dir}", flush=True)

    meter = CompileMeter()
    if args.chips == 4:
        with meter.phase("sharded-decode"):
            sharded_decode_phase(args.seed)
        with meter.phase("fsdp-train"):
            fsdp_train_phase(args.seed)
    else:
        workdir = ROOT / "examples_out" / "chip_smoke"
        shutil.rmtree(workdir, ignore_errors=True)
        workdir.mkdir(parents=True)
        with meter.phase("serve"):
            serve_phase(workdir, args.seed)
        with meter.phase("train"):
            train_phase(args.seed)
    print(f"compile seconds total {meter.seconds:.2f}, persistent-cache "
          f"hits {meter.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
