"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
        --steps 200 --ckpt-dir /tmp/ckpt

Production path: builds the mesh, shards params/optimizer with the logical
rules, runs the jitted train_step with checkpointing, straggler monitoring,
preemption handling and resumable data.  ``--smoke`` runs the reduced config
on the local devices (the CPU e2e path used by the examples/tests);
otherwise the full config is used (requires a real TPU slice).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs.registry import get
    from repro.data import SyntheticLM
    from repro.distributed.fault_tolerance import (CheckpointManager,
                                                   StragglerMonitor)
    from repro.launch.steps import make_train_step
    from repro.models.model import init_model
    from repro.optim import make_optimizer

    cfg = get(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    import dataclasses
    cfg = dataclasses.replace(cfg, microbatch=1)

    key = jax.random.PRNGKey(0)
    params = init_model(cfg, key)
    opt_init, _ = make_optimizer(cfg.optimizer)
    opt_state = opt_init(params)
    step0 = 0

    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch)
    mgr = StragglerMonitor()
    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir,
                                 interval_steps=args.ckpt_every)
        ckpt.install_preemption_handler()
        if args.resume and ckpt.latest_step() is not None:
            (state, extras, step0) = ckpt.restore_latest(
                {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            data.skip_to(extras.get("data_step", step0))
            print(f"resumed from step {step0}")

    train_step = jax.jit(make_train_step(
        cfg, peak_lr=args.lr, warmup=20, total_steps=args.steps),
        donate_argnums=(0, 1))

    losses = []
    for step in range(step0, args.steps):
        raw = data.next_batch()
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        if cfg.family == "vlm":
            s = batch["tokens"].shape[1]
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(s)[None, None],
                (3, batch["tokens"].shape[0], s)).astype(jnp.int32)
        if cfg.family == "encdec":
            batch["frames"] = jax.random.normal(
                jax.random.fold_in(key, step),
                (batch["tokens"].shape[0], batch["tokens"].shape[1],
                 cfg.d_model), jnp.float32)
        mgr.step_start()
        params, opt_state, metrics = train_step(
            params, opt_state, batch, jnp.int32(step))
        jax.block_until_ready(metrics["loss"])
        straggler = mgr.step_end()
        losses.append(float(metrics["ce_loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} ce={losses[-1]:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e}"
                  + (" [straggler]" if straggler else ""), flush=True)
        if ckpt and ckpt.should_save(step):
            ckpt.save(step, {"params": params, "opt": opt_state},
                      extras={"data_step": data.state.step})

    print(f"final: first10={np.mean(losses[:10]):.3f} "
          f"last10={np.mean(losses[-10:]):.3f} "
          f"straggler_summary={mgr.summary()}")
    return losses


if __name__ == "__main__":
    main()
