#!/usr/bin/env python3
"""Chip smoke test: the live serving path on a TPU at full model width.

    python chip_smoke.py [--seed 0] [--partition-gb 2.0]
    python chip_smoke.py --chips 4 [--seed 0]

One chip (the default).  qwen3-1.7b at its published width (28L, d=2048,
16H/8KV, vocab 151936; bf16 weights made from ``--seed``, no checkpoint)
serves 8 requests of 128 prompt tokens and 32 new tokens through
``ServeEngine`` and the early-restart loop of ``repro.launch.serve``.  It
starts on a slice smaller than its weights, so the predictor restarts it
once onto the chip's 1x1 slice.  Then the logits at the last prompt
position that the engine's cache-writing prefill returned, and those of
the prompt replayed one position per decode step (``registry.decode_step``),
are checked against the XLA forward (``registry.prefill``), and the Pallas
flash-attention forward, which must lower to a TPU kernel, against the XLA
forward.

Four chips (``--chips 4``).  Only the multi-tenant pod: three qwen3-1.7b
tenants lease 1x1 slices of the host's 2x2 buddy pod with their params and
caches placed there; the growing one early-restarts onto a 1x2 slice with
its params sharded over it, and its logits are checked against the same
tenant on one chip, teacher-forced on the same tokens.

Logits agree when every row has the same argmax and max|delta| is at most
four bf16 ulps of the largest reference logit.

Without a TPU it exits non-zero and prints no result.  Otherwise the last
line of stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

MODEL = "qwen3-1.7b"
N_REQUESTS, PROMPT_LEN, MAX_NEW, MAX_CONTEXT = 8, 128, 32, 2048
TENANT_PROMPT, TENANT_TOKENS, TENANT_CONTEXT = 16, 24, 256
#: the growing tenant's live bytes reach this by its last token: more than
#: a 16 GB chip, less than a 1x2 slice
GROW_TO_GB = 24.0
#: logit tolerance, in bf16 ulps of the largest reference logit
LOGIT_ULPS = 4
GB = 1024 ** 3


class CheckFailed(RuntimeError):
    pass


def check_logits(what: str, got, ref, device: str) -> None:
    """Same argmax in every row, and max|delta| within LOGIT_ULPS bf16 ulps
    of the largest reference logit."""
    import numpy as np
    got = np.asarray(got, np.float32).reshape(-1, np.shape(got)[-1])
    ref = np.asarray(ref, np.float32).reshape(-1, np.shape(ref)[-1])
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise CheckFailed(f"{what}: non-finite logits")
    peak = float(np.abs(ref).max())
    limit = LOGIT_ULPS * 2.0 ** (math.floor(math.log2(peak)) - 7)
    err = float(np.abs(got - ref).max())
    same = got.argmax(-1) == ref.argmax(-1)
    print(f"[chip_smoke] {device} {what}: max|delta| {err} "
          f"(limit {limit}, max|ref| {peak}), argmax agrees on "
          f"{int(same.sum())}/{same.size} rows")
    if err > limit or not same.all():
        raise CheckFailed(f"{what}: logits disagree")


def print_memory(when: str, device: str) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[chip_smoke] {device} memory_stats {when}: " + ", ".join(
        f"{k} {stats.get(k, 'not reported')}"
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")))


def serve_phase(args, device: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.memory.accountant import pytree_nbytes
    from repro.launch.mesh import host_pod_backend
    from repro.launch.serve import serve_with_early_restart
    from repro.models import registry
    from repro.serving.engine import Request

    cfg = get_config(MODEL)
    t0 = time.perf_counter()
    params, _ = registry.init_params_compiled(jax.random.PRNGKey(args.seed),
                                              cfg)
    param_gb = pytree_nbytes(params) / GB
    print(f"[chip_smoke] {device} {cfg.name}: {cfg.n_layers}L "
          f"d={cfg.d_model} {cfg.n_heads}H/{cfg.n_kv_heads}KV "
          f"vocab={cfg.vocab}, {param_gb:.3f} GiB of bf16 params "
          f"(init {time.perf_counter() - t0:.1f}s)")
    print_memory("after init", device)
    if not args.partition_gb < param_gb:
        raise CheckFailed(f"--partition-gb {args.partition_gb} must be "
                          f"below the {param_gb:.3f} GiB of params")

    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, PROMPT_LEN)
                    .astype(np.int32), max_new_tokens=MAX_NEW)
            for i in range(N_REQUESTS)]
    backend = host_pod_backend()
    t0 = time.perf_counter()
    res = serve_with_early_restart(
        cfg, params, reqs, backend=backend, max_context=MAX_CONTEXT,
        partition_gb=args.partition_gb,
        log=lambda m: print(f"[chip_smoke] {device} {m}"))
    jax.block_until_ready(res.engine.prompt_logits)
    seconds = time.perf_counter() - t0
    n_tok = sum(len(r.generated) for r in res.requests)
    print(f"[chip_smoke] {device} served {n_tok} tokens in {seconds}s wall "
          f"({len(res.restarts) + 1} attempts, compiles included), "
          f"finished on the {res.profile_gb:.1f}GB slice")
    for r in res.requests:
        print(f"[chip_smoke] {device} req {r.uid}: {r.generated}")
    print_memory("after serving", device)
    if len(res.restarts) != 1:
        raise CheckFailed(f"expected one early restart, got "
                          f"{[p.name for p in res.restarts]}")
    if any(len(r.generated) != MAX_NEW
           or not all(0 <= t < cfg.vocab for t in r.generated)
           for r in res.requests):
        raise CheckFailed("a request did not get its tokens")

    batch = {"tokens": jnp.asarray(np.stack([r.prompt for r in reqs]))}
    prefill = jax.jit(registry.prefill, static_argnums=1)
    ref = prefill(params, cfg, batch)[:, -1, :cfg.vocab]
    check_logits("engine prefill vs XLA forward, last prompt position",
                 res.engine.prompt_logits[:, -1, :cfg.vocab], ref, device)
    step = jax.jit(lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c),
                   donate_argnums=(3,))
    caches = registry.init_caches(cfg, N_REQUESTS, MAX_CONTEXT)
    for pos in range(PROMPT_LEN):
        replayed, caches = step(params, batch["tokens"][:, pos:pos + 1],
                                jnp.int32(pos), caches)
    check_logits("decode step replay vs XLA forward, last prompt position",
                 replayed[:, -1, :cfg.vocab], ref, device)
    lowered = prefill.lower(params, dataclasses.replace(cfg,
                                                        attn_impl="pallas"),
                            batch)
    if "tpu_custom_call" not in lowered.as_text():
        raise CheckFailed("the Pallas prefill lowered without a TPU kernel")
    pallas = lowered.compile()(params, batch)[:, -1, :cfg.vocab]
    check_logits("Pallas flash prefill vs XLA prefill", pallas, ref, device)


def multi_tenant_phase(args, device: str) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.mesh import host_pod_backend, make_slice_mesh
    from repro.launch.tenants import (TenantJob, decode_on_slice,
                                      place_params, run_pod)
    from repro.models import registry

    devices = jax.devices()
    if len(devices) != 4:
        raise CheckFailed(f"--chips 4 needs 4 devices, found {len(devices)}")
    backend = host_pod_backend(devices)
    cfg = get_config(MODEL)
    rng = np.random.default_rng(args.seed)
    jobs = [TenantJob(f"tenant-{c}", rng.integers(0, cfg.vocab, TENANT_PROMPT)
                      .astype(np.int32), TENANT_TOKENS, seed=args.seed + i,
                      grow_to_gb=GROW_TO_GB if c == "c" else 0.0)
            for i, c in enumerate("abc")]
    t0 = time.perf_counter()
    runs = run_pod(cfg, backend, jobs, devices=devices,
                   context=TENANT_CONTEXT,
                   log=lambda m: print(f"[chip_smoke] {device} {m}"))
    print(f"[chip_smoke] {device} pod served in "
          f"{time.perf_counter() - t0:.3f}s wall, compiles included")
    for run in runs:
        print(f"[chip_smoke] {device} {run.job.name} on {run.profile.name} "
              f"devices {[d.id for d in run.devices.flat]}"
              f"{' (restarted from ' + run.restarted_from.name + ')' if run.restarted_from else ''}"
              f": {run.tokens}")
    a, b, c = runs
    if (a.restarted_from or b.restarted_from or a.profile.name != "1x1"
            or b.profile.name != "1x1"):
        raise CheckFailed("tenants a and b should finish on their 1x1 slices")
    if c.restarted_from is None or c.profile.name != "1x2":
        raise CheckFailed("the growing tenant should restart onto a 1x2")

    one = make_slice_mesh([devices[0]], (1, 1))
    with jax.default_device(devices[0]):
        params, specs = registry.init_params_compiled(
            jax.random.PRNGKey(c.job.seed), cfg)
    _, ref = decode_on_slice(cfg, place_params(params, specs, one), one,
                             c.job.prompt, c.job.n_tokens,
                             context=TENANT_CONTEXT, forced=c.tokens)
    check_logits("1x2 restart vs one chip, teacher-forced", c.logits, ref,
                 device)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--partition-gb", type=float, default=2.0,
                    help="slice the served model starts on; below its "
                         "params, so the early restart runs once")
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    print(f"[chip_smoke] device: platform={info['platform']} "
          f"kind={info['kind']} count={info['count']} "
          f"(compile cache {cache_dir})")
    if info["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {info['platform']}",
              file=sys.stderr)
        return 3
    device = f"[{info['platform']} {info['kind']} x{info['count']}]"
    if args.chips == 4:
        multi_tenant_phase(args, device)
    else:
        serve_phase(args, device)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
