"""Quickstart: train a small qwen3-family model for a few hundred steps on
CPU and watch the loss drop, then save/restore a checkpoint and serve a few
greedy completions from the trained weights.

    PYTHONPATH=src python examples/quickstart.py [--steps 200]
"""

from __future__ import annotations

import argparse
import tempfile

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.training.checkpoint import load_checkpoint, save_checkpoint
from repro.training.data import DataConfig, SyntheticLM
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = get_smoke_config("qwen3-0.6b")
    print(f"arch: {cfg.name} (reduced: {cfg.n_layers}L d={cfg.d_model})")

    state, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    opt = AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps)
    step = jax.jit(make_train_step(cfg, opt))
    data = SyntheticLM(cfg, DataConfig(batch=args.batch, seq=args.seq))

    for i, batch in zip(range(args.steps), data.batches()):
        state, metrics = step(state, batch)
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(metrics['loss']):8.3f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"gnorm {float(metrics['grad_norm']):.2f}")

    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/ckpt.npz"
        save_checkpoint(path, state, step=args.steps)
        restored = load_checkpoint(path, jax.device_get(state))
        leaf = jax.tree_util.tree_leaves(restored["params"])[0]
        print(f"checkpoint round-trip OK ({leaf.dtype}, "
              f"step {args.steps})")

    engine = ServeEngine(cfg, state["params"],
                         EngineConfig(max_batch=2, max_context=64))
    reqs = [Request(uid=i, prompt=np.arange(4, dtype=np.int32) + i,
                    max_new_tokens=12) for i in range(2)]
    for r in engine.run(reqs):
        print(f"request {r.uid}: generated {r.generated}")


if __name__ == "__main__":
    main()
