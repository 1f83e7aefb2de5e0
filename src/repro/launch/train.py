"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 50 --batch 2 --seq 64 --reduced --ckpt-dir /tmp/run1

Wires together: config -> mesh + sharding rules -> data pipeline -> jitted
train step -> sharded/elastic checkpoints, with resume-from-latest. On a
real installation this is the entry point each TPU worker runs (the engine
submits it via TPUTrainJob/SLURM); on CPU it trains reduced configs.
"""

from __future__ import annotations

import argparse
import math
import time

import jax
import jax.numpy as jnp

from repro.configs import (enable_compile_cache, get_config, make_mesh,
                           reduced_config)
from repro.distributed.sharding import make_rules, tree_named_shardings
from repro.models.common import axis_rules
from repro.models.registry import build
from repro.training import checkpoint as ckpt_mod
from repro.training.data import DataConfig, TokenStream
from repro.training.optim import OptimConfig
from repro.training.train_step import (
    TrainConfig, init_train_state, make_train_step, train_state_axes,
    train_state_shapes,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    bundle = build(cfg)
    mesh = make_mesh((args.data_mesh, args.model_mesh), ("data", "model"))
    rules = make_rules(cfg, mesh, fsdp=args.data_mesh > 1)
    tcfg = TrainConfig(
        optim=OptimConfig(name=args.optimizer, lr=args.lr,
                          warmup_steps=max(1, args.steps // 20),
                          total_steps=args.steps),
        microbatches=args.microbatches,
        seed=args.seed)

    data = TokenStream(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, batch_size=args.batch,
        seed=args.seed,
        host_id=jax.process_index(), num_hosts=jax.process_count()))

    with axis_rules(mesh, rules):
        state_sh = tree_named_shardings(
            train_state_shapes(bundle, tcfg), train_state_axes(bundle, tcfg),
            rules, mesh)
        step_fn = jax.jit(make_train_step(bundle, tcfg),
                          out_shardings=(state_sh, None),
                          donate_argnums=(0,))

        start_step = 0
        if args.ckpt_dir and ckpt_mod.latest_step(args.ckpt_dir) is not None:
            target = jax.eval_shape(
                lambda: init_train_state(bundle, tcfg, jax.random.PRNGKey(0)))
            state = ckpt_mod.restore_checkpoint(args.ckpt_dir, target=target,
                                                shardings=state_sh)
            start_step = int(state["step"])
            print(f"[train] resumed from step {start_step}")
        else:
            state = init_train_state(bundle, tcfg,
                                     jax.random.PRNGKey(args.seed))
        checkpointer = (ckpt_mod.AsyncCheckpointer(args.ckpt_dir)
                        if args.ckpt_dir else None)

        t0 = time.time()
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
            state, metrics = step_fn(state, batch)
            if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
                loss = float(metrics["loss"])
                if math.isnan(loss):
                    raise SystemExit(310)   # NaN -> exit code for the engine
                dt = time.time() - t0
                tput = args.log_every * args.batch * args.seq / max(dt, 1e-9)
                print(f"[train] step {step+1}/{args.steps} "
                      f"loss={loss:.4f} lr={float(metrics['lr']):.2e} "
                      f"grad_norm={float(metrics['grad_norm']):.2f} "
                      f"({tput:.0f} tok/s)", flush=True)
                t0 = time.time()
            if checkpointer and (step + 1) % args.ckpt_every == 0:
                checkpointer.save(step + 1, state)
        if checkpointer:
            checkpointer.save(args.steps, state)
            checkpointer.wait()
            print(f"[train] final checkpoint at {checkpointer.last_path}")


if __name__ == "__main__":
    main()
