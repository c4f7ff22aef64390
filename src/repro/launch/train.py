"""Fault-tolerant training driver.

Features (scaled-down single-process embodiment of the 1000-node design,
DESIGN.md §5):
  * checkpoint/restart — atomic manifest commits every --ckpt-every steps;
    on start, resumes from the latest valid checkpoint (params + optimizer
    + step + dataloader cursor), restoring onto whatever mesh is current
    (elastic re-shard).
  * preemption handling — SIGTERM/SIGINT trigger a final checkpoint before
    exit, so a preempted worker loses at most one step.
  * straggler mitigation — the data pipeline is positionally deterministic
    (loader.py), so a replacement host reproduces any batch without peer
    coordination; per-step wall-time is logged and steps slower than
    --straggler-factor x the trailing median are flagged (on real fleets
    this feeds the scheduler's hot-spare swap).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import signal
import statistics
import sys
import time

import jax
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.data.loader import ShardedLoader
from repro.launch.compile_cache import enable_compile_cache
from repro.training.train_loop import init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=None, help="default: steps // 10")
    ap.add_argument("--total-steps", type=int, default=None,
                    help="LR schedule horizon (default: --steps); lets a partial "
                         "run share the schedule of the full job it resumes")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.kernel_interpret is not None:
        from repro.kernels.backend import set_interpret_override

        set_interpret_override(cfg.kernel_interpret)
    # top-k kernel tuning defaults from the benchmarks/tune_topk.py sweep
    # (CPU-interpret winners are a smoke signal only — re-sweep on real
    # hardware); explicit REPRO_TOPK_* env vars win over the config
    from repro.kernels.similarity_topk.ops import apply_topk_tuning

    apply_topk_tuning(cfg.topk_block_n, cfg.topk_grid_order)
    state, specs = init_train_state(cfg, jax.random.PRNGKey(args.seed))
    loader = ShardedLoader(cfg.vocab_size, args.global_batch, args.seq_len, seed=args.seed)

    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state, extra = restore_checkpoint(args.ckpt_dir, state)
        loader.restore(extra["loader"])
        start = int(extra["loader"]["step"])
        print(f"[restore] resumed at step {start}")

    warmup = args.warmup if args.warmup is not None else max(args.steps // 10, 1)
    horizon = args.total_steps or args.steps
    train_step = jax.jit(
        make_train_step(cfg, peak_lr=args.lr, warmup_steps=warmup, total_steps=horizon),
        donate_argnums=(0,),
    )

    stop = {"now": False}

    def _sig(_signo, _frame):
        print("[preempt] signal received — checkpointing before exit", flush=True)
        stop["now"] = True

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)

    def checkpoint(step):
        if args.ckpt_dir:
            path = save_checkpoint(args.ckpt_dir, step, state, extra={"loader": loader.state()})
            print(f"[ckpt] step {step} -> {path}", flush=True)

    step_times = []
    losses = []
    for step in range(start, args.steps):
        batch = next(loader)
        t0 = time.perf_counter()
        state, metrics = train_step(state, {"tokens": batch["tokens"]})
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        step_times.append(dt)
        losses.append(loss)
        if len(step_times) >= 8:
            med = statistics.median(step_times[-20:])
            if dt > args.straggler_factor * med:
                print(f"[straggler] step {step} took {dt:.2f}s (median {med:.2f}s)", flush=True)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:.4f} grad_norm "
                  f"{float(metrics['grad_norm']):.3f} lr {float(metrics['lr']):.2e} "
                  f"({dt:.2f}s)", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            checkpoint(step + 1)
        if stop["now"]:
            checkpoint(step + 1)
            sys.exit(0)

    checkpoint(args.steps)
    print(f"final loss {losses[-1]:.4f} (uniform = {np.log(cfg.vocab_size):.4f})")
    return losses


if __name__ == "__main__":
    main()
