"""End-to-end training driver (runnable on CPU with reduced configs; the
same code path the dry-run lowers for the production mesh).

  PYTHONPATH=src python -m repro.launch.train --arch mistral-nemo-12b \
      --smoke --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Features: deterministic data, checkpoint/resume (exact), periodic async
saves, elastic restore (the checkpoint is mesh-agnostic), optional int8
gradient compression, grad accumulation.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, batch_at
from repro.models import init_params
from repro.training.checkpoint import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.training.optimizer import OptConfig, adamw_init
from repro.training.train_loop import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mistral-nemo-12b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    dcfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed,
        img_tokens=cfg.cross_kv_len, d_model=cfg.d_model,
    )
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    opt = adamw_init(params)
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state, extra = restore_checkpoint(
            args.ckpt_dir, {"params": params, "opt": opt}
        )
        params, opt = state["params"], state["opt"]
        start = extra["data_step"]
        print(f"resumed from step {start}")

    opt_cfg = OptConfig(lr=args.lr, warmup_steps=5,
                        total_steps=max(args.steps, 10))
    step_fn = jax.jit(
        make_train_step(cfg, opt_cfg, grad_accum=args.grad_accum,
                        compress_grads=args.compress_grads)
    )

    t0 = time.time()
    pending = None
    for step in range(start, start + args.steps):
        batch = {k: jnp.asarray(v) for k, v in batch_at(dcfg, step).items()}
        params, opt, m = step_fn(params, opt, batch)
        if (step + 1) % args.log_every == 0:
            dt = (time.time() - t0) / args.log_every
            print(
                f"step {step+1}: loss={float(m['loss']):.4f} "
                f"gnorm={float(m['grad_norm']):.3f} "
                f"lr={float(m['lr']):.2e} {dt*1e3:.0f} ms/step",
                flush=True,
            )
            t0 = time.time()
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            if pending is not None:
                pending.join()
            pending = save_checkpoint(
                args.ckpt_dir, step + 1, {"params": params, "opt": opt},
                extra={"data_step": step + 1}, block=False,
            )
    if pending is not None:
        pending.join()
    print("done")


if __name__ == "__main__":
    main()
