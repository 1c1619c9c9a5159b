"""Training driver — any assigned architecture, smoke or full scale.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 100 --batch 8 --seq 128 [--ckpt /tmp/run]

Full-scale (non ``--smoke``) runs expect real accelerators; on this CPU
container use ``--smoke`` (the reduced same-family config) or the dry-run
(`repro.launch.dryrun`) for the production shapes.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.training.checkpoint import load_checkpoint, save_checkpoint
from repro.training.data import DataConfig, SyntheticLM
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default=None, help="checkpoint path prefix")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    print(f"[train] {cfg.name} ({'smoke' if args.smoke else 'FULL'}): "
          f"{cfg.n_layers}L d={cfg.d_model} family={cfg.family} on "
          f"{jax.device_count()} device(s)")

    state, _ = init_train_state(jax.random.PRNGKey(args.seed), cfg)
    if args.resume:
        state = load_checkpoint(args.resume, jax.device_get(state))
        print(f"[train] resumed from {args.resume}")
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                      total_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, opt,
                                      n_microbatches=args.microbatches))
    data = SyntheticLM(cfg, DataConfig(batch=args.batch, seq=args.seq,
                                       seed=args.seed))

    t0 = time.time()
    tokens_done = 0
    for i, batch in zip(range(args.steps), data.batches()):
        state, metrics = step_fn(state, batch)
        tokens_done += args.batch * args.seq
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = time.time() - t0
            print(f"step {i:5d}  loss {float(metrics['loss']):9.4f}  "
                  f"aux {float(metrics['aux_loss']):7.4f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"gnorm {float(metrics['grad_norm']):7.2f}  "
                  f"{tokens_done / max(dt, 1e-9):9.0f} tok/s")
        if args.ckpt and (i + 1) % args.ckpt_every == 0:
            path = f"{args.ckpt}.step{i + 1}.npz"
            save_checkpoint(path, state, step=i + 1)
            print(f"[train] checkpoint -> {path}")
    if args.ckpt:
        save_checkpoint(f"{args.ckpt}.final.npz", state, step=args.steps)
        print(f"[train] final checkpoint -> {args.ckpt}.final.npz")


if __name__ == "__main__":
    main()
