"""Multi-tenant TPU-pod serving — the paper's system LIVE on real sub-meshes.

This is MIGM's end-to-end flow on (forced-host) JAX devices, through
:func:`repro.launch.tenants.run_pod`:

  1. a 2x2 "pod" of 4 devices is managed by the buddy-slice
     PartitionStateMachine (the TPU adaptation of the A100 MIG FSM);
  2. three tenants (small transformer serving tasks) each lease a tight
     slice (Alg. 3 argmax-reachability), and their params and KV caches are
     placed on that slice's devices;
  3. one tenant has a growing context; the MemoryAccountant + time-series
     predictor watch its allocator stats and raise NeedsLargerPartition —
     the pod performs the checkpointless early restart onto a bigger slice
     (re-jit + device_put, sharded over the slice), exactly the paper's §2.3
     flow.

    PYTHONPATH=src python examples/multi_tenant.py

On a four-chip TPU host the same function runs at full width:
``python chip_smoke.py --chips 4``.
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.mesh import host_pod_backend
from repro.launch.tenants import TenantJob, run_pod

#: per-chip HBM scaled so the reduced model's weights fill most of a chip
CHIP_HBM_GB = 0.004


def main() -> None:
    devices = jax.devices()[:4]
    backend = host_pod_backend(devices, chip_hbm_gb=CHIP_HBM_GB)
    cfg = get_smoke_config("qwen3-0.6b")
    prompt = np.random.default_rng(0).integers(0, cfg.vocab, 8).astype(
        np.int32)
    jobs = [TenantJob("tenant-a", prompt, 24, seed=0),
            TenantJob("tenant-b", prompt, 24, seed=1),
            TenantJob("tenant-c-growing", prompt, 48, seed=2,
                      grow_to_gb=1.5 * CHIP_HBM_GB)]
    runs = run_pod(cfg, backend, jobs, devices=devices)
    for run in runs:
        print(f"  {run.job.name}: {len(run.tokens)} tokens on "
              f"{run.profile.name} ({[d.id for d in run.devices.flat]})"
              + (f", restarted from {run.restarted_from.name}"
                 if run.restarted_from else ""))


if __name__ == "__main__":
    main()
