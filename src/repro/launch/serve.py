"""Serving driver — batched greedy decoding with the paper's memory watch.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --requests 4 --prompt-len 8 --max-new 32 [--partition-gb 10]

With ``--partition-gb`` the engine runs the time-series predictor against
that slice size and performs the early restart (grow to the next profile)
when the converged peak estimate exceeds it — the live §2.3 flow.  The
profiles it may grow to are the slices of the host's own pod
(:func:`repro.launch.mesh.host_pod_backend`).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.configs.base import ModelConfig
from repro.core.partition_state import PartitionBackend, PartitionProfile
from repro.core.restart import NeedsLargerPartition, host_restart_target
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import host_pod_backend
from repro.models import registry
from repro.obs.spans import span
from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.training.checkpoint import load_checkpoint


@dataclasses.dataclass
class ServeResult:
    requests: list[Request]
    engine: ServeEngine            # the engine of the attempt that finished
    profile_gb: float | None       # the slice size it finished on
    restarts: list[PartitionProfile]
    seconds: float                 # wall time of every attempt, restarts included


def serve_with_early_restart(cfg: ModelConfig, params: dict,
                             requests: list[Request], *,
                             backend: PartitionBackend, max_context: int,
                             partition_gb: float | None = None,
                             log=print) -> ServeResult:
    """Serve ``requests``, regrowing the slice each time the predictor
    raises the early restart, until the batch finishes.

    A restart is checkpointless: the batch is served again from its prompts
    on the larger slice.  A target the host cannot give raises instead of
    looping (:func:`repro.core.restart.host_restart_target`).
    """
    restarts: list[PartitionProfile] = []
    t0 = time.perf_counter()
    while True:
        for r in requests:
            r.generated.clear()
            r.token_times.clear()
        try:
            with span("serve.attempt", attempt=len(restarts),
                      slice_gb=partition_gb, rows=len(requests)):
                engine = ServeEngine(
                    cfg, params,
                    EngineConfig(max_batch=len(requests),
                                 max_context=max_context,
                                 partition_gb=partition_gb),
                    backend=backend)
                out = engine.run(requests)
        except NeedsLargerPartition as e:
            with span("serve.restart", from_gb=partition_gb) as mark:
                nxt = host_restart_target(backend, partition_gb, e)
                mark.set_metadata(to_gb=nxt.mem_gb)
                log(f"[serve] EARLY RESTART: predictor flagged the "
                    f"{partition_gb:.1f}GB slice -> regrowing to "
                    f"{nxt.name} ({nxt.mem_gb:.1f}GB)")
            restarts.append(nxt)
            partition_gb = nxt.mem_gb
            continue
        return ServeResult(requests=out, engine=engine,
                           profile_gb=partition_gb, restarts=restarts,
                           seconds=time.perf_counter() - t0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-context", type=int, default=256)
    ap.add_argument("--partition-gb", type=float, default=None)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    print(f"[serve] {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"family={cfg.family}")
    params, _ = registry.init_params_compiled(jax.random.PRNGKey(args.seed),
                                              cfg)
    if args.ckpt:
        state = load_checkpoint(args.ckpt, {"params": jax.device_get(params)})
        params = state["params"]
        print(f"[serve] weights from {args.ckpt}")

    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len
                                        ).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    res = serve_with_early_restart(cfg, params, reqs,
                                   backend=host_pod_backend(),
                                   max_context=args.max_context,
                                   partition_gb=args.partition_gb)
    n_tok = sum(len(r.generated) for r in res.requests)
    print(f"[serve] {n_tok} tokens in {res.seconds:.1f}s "
          f"({n_tok / max(res.seconds, 1e-9):.1f} tok/s)")
    for r in res.requests[:4]:
        print(f"  req {r.uid}: {r.generated[:16]}"
              f"{'...' if len(r.generated) > 16 else ''}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    if peak is not None:
        print(f"[serve] device peak memory in use {peak / 1024 ** 3:.3f} GB")
    acc = res.engine.accountant
    print(f"[serve] predictor's estimate of peak live memory "
          f"{acc.peak_in_use / 1024 ** 3:.3f} GB over {len(acc.history)} "
          f"iterations")


if __name__ == "__main__":
    main()
