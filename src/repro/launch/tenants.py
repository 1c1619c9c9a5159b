"""Multi-tenant serving on the buddy sub-slices of one pod — live.

Each tenant leases the tightest slice that holds its weights (Alg. 3's
argmax-reachability placement), and its params and KV caches are placed on
that slice's devices with ``device_put``, sharded by the logical-axis rules
of :mod:`repro.sharding.partitioning`.  A tenant whose context grows is
watched by the time-series predictor; when the converged peak outgrows its
slice it raises :class:`NeedsLargerPartition` and the pod performs the
checkpointless early restart: release, lease the predicted slice, re-place
the params there, re-jit, decode again (paper §2.3).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.configs.base import ModelConfig
from repro.core.memory.accountant import (MemoryAccountant, pytree_nbytes,
                                          spec_nbytes)
from repro.core.memory.timeseries import PeakMemoryPredictor
from repro.core.partition_manager import PartitionManager
from repro.core.partition_state import PartitionProfile
from repro.core.restart import (NeedsLargerPartition, early_restart_target,
                                host_restart_target)
from repro.core.tpu_slices import TpuPodBackend
from repro.launch.mesh import make_slice_mesh, slice_devices
from repro.models import registry
from repro.models.layers import padded_vocab
from repro.sharding.partitioning import act_spec, param_sharding

GB = 1024 ** 3


@dataclasses.dataclass
class TenantJob:
    name: str
    prompt: np.ndarray      # [S] int32
    n_tokens: int           # tokens to generate
    seed: int = 0           # the tenant's weights are made from this seed
    #: a tenant whose context grows: the live GB its allocator series
    #: reaches by its last token (0 = no growth beyond its KV cache)
    grow_to_gb: float = 0.0


@dataclasses.dataclass
class TenantRun:
    job: TenantJob
    profile: PartitionProfile        # the slice it finished on
    devices: np.ndarray              # that slice's devices
    tokens: list[int]                # generated tokens
    logits: np.ndarray               # [n_tokens, vocab] f32, one per token
    restarted_from: PartitionProfile | None = None


def _is_spec(x) -> bool:
    return isinstance(x, tuple)


def place_params(params: dict, specs: dict, mesh: Mesh) -> dict:
    """``device_put`` params onto the slice, sharded by the param rules."""
    shardings = jax.tree.map(
        lambda axes, p: param_sharding(axes, mesh, p.shape), specs, params,
        is_leaf=_is_spec)
    return jax.device_put(params, shardings)


def place_caches(cfg: ModelConfig, caches: dict, mesh: Mesh) -> dict:
    """``device_put`` KV caches onto the slice, sharded by the act rules."""
    shardings = jax.tree.map(
        lambda axes, c: NamedSharding(mesh, act_spec(axes, mesh, c.shape)),
        registry.cache_specs(cfg), caches, is_leaf=_is_spec)
    return jax.device_put(caches, shardings)


def decode_on_slice(cfg: ModelConfig, params: dict, mesh: Mesh,
                    prompt: np.ndarray, n_tokens: int, *, context: int,
                    forced: list[int] | None = None, watch=None
                    ) -> tuple[list[int], np.ndarray]:
    """Greedy decode of one sequence on the slice's mesh.

    The prompt is replayed through ``decode_step`` to fill the cache, then
    ``n_tokens`` are generated.  With ``forced`` the step feeds those tokens
    instead of its own argmax (teacher forcing).  ``watch(i)`` runs after
    token ``i`` and may raise :class:`NeedsLargerPartition`.  Every output
    is checked to sit on exactly the slice's devices.

    Returns the generated tokens and the logits that chose each of them.
    """
    want = set(mesh.devices.flat)
    with jax.default_device(mesh.devices.flat[0]):
        caches = registry.init_caches(cfg, 1, context)
    caches = place_caches(cfg, caches, mesh)
    # outputs pinned to the input layout: the step compiles once per slice
    logits_sharding = NamedSharding(mesh, act_spec(
        ("batch", "seq", "vocab"), mesh, (1, 1, padded_vocab(cfg))))
    step = jax.jit(
        lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c),
        out_shardings=(logits_sharding,
                       jax.tree.map(lambda c: c.sharding, caches)))
    seq = [int(t) for t in prompt]
    tokens: list[int] = []
    rows: list[np.ndarray] = []
    with mesh:
        for pos in range(len(prompt) + n_tokens - 1):
            tok = np.asarray([[seq[pos]]], np.int32)
            logits, caches = step(params, tok, np.int32(pos), caches)
            if pos < len(prompt) - 1:
                continue
            row = np.asarray(logits[0, 0, :cfg.vocab], np.float32)
            rows.append(row)
            tokens.append(int(row.argmax()))
            seq.append(forced[len(tokens) - 1] if forced else tokens[-1])
            if watch is not None:
                watch(len(tokens) - 1)
    for leaf in jax.tree.leaves((params, logits, caches)):
        if leaf.devices() != want:
            raise RuntimeError(f"output on {leaf.devices()}, slice is {want}")
    return tokens, np.stack(rows)


def _growth_watch(job: TenantJob, params_bytes: float, partition_gb: float,
                  backend: TpuPodBackend):
    """The allocator series of a growing tenant, fed to the predictor: live
    bytes rise linearly from its params to ``job.grow_to_gb``; the converged
    peak beyond ``partition_gb`` raises the early restart."""
    acc = MemoryAccountant()
    predictor = PeakMemoryPredictor(max_iter=job.n_tokens, converge_tol=0.3)

    def watch(i: int) -> None:
        live = params_bytes + (job.grow_to_gb * GB - params_bytes) \
            * (i + 1) / job.n_tokens
        acc.note_alloc(live * 0.1 + params_bytes * 0.01)
        acc.note_live(live)
        stats = acc.end_iteration()
        pred = predictor.observe(stats.requested_bytes, stats.reuse_ratio)
        if predictor.will_oom(partition_gb * GB, pred):
            raise NeedsLargerPartition(early_restart_target(
                backend, pred.peak_mem_bytes / GB))
    return watch


def run_pod(cfg: ModelConfig, backend: TpuPodBackend, jobs: list[TenantJob],
            *, devices=None, context: int = 256, log=print
            ) -> list[TenantRun]:
    """Lease a slice per tenant (all co-resident), place each tenant's
    params on its slice, then decode each in turn, early-restarting the
    tenants the predictor flags.  Every lease is released at the end."""
    pm = PartitionManager(backend)
    need_gb = spec_nbytes(registry.abstract_params(cfg)[0]) / GB * 1.3
    leased = []
    for job in jobs:
        profile = backend.tightest_profile(need_gb)
        part = pm.allocate(profile) or pm.allocate_with_reshape(profile)
        if part is None:
            raise RuntimeError(f"no {profile.name} slice for {job.name}")
        devs = slice_devices(backend, part.handle, devices)
        mesh = make_slice_mesh(devs, devs.shape)
        # made on the default device by one compiled program, then moved
        params, specs = registry.init_params_compiled(
            jax.random.PRNGKey(job.seed), cfg)
        leased.append((job, profile, part, mesh,
                       place_params(params, specs, mesh), specs))
        del params
        log(f"{job.name}: leased {profile.name} at {part.handle} "
            f"(pod reachability now {backend.reachability(pm.state)})")
    log(f"pod state with {len(jobs)} tenants: {pm.describe()}")

    runs = []
    for job, profile, part, mesh, params, specs in leased:
        watch = (_growth_watch(job, pytree_nbytes(params), profile.mem_gb,
                               backend) if job.grow_to_gb else None)
        try:
            toks, logits = decode_on_slice(cfg, params, mesh, job.prompt,
                                           job.n_tokens, context=context,
                                           watch=watch)
            runs.append(TenantRun(job, profile, mesh.devices, toks, logits))
            pm.release(part)
        except NeedsLargerPartition as e:
            # the checkpointless early restart: free the slice, lease the
            # predicted one, re-place the params there, decode again
            pm.release(part)
            bigger = host_restart_target(backend, profile.mem_gb, e)
            part = pm.allocate(bigger) or pm.allocate_with_reshape(bigger)
            if part is None:
                raise RuntimeError(f"no {bigger.name} slice free for "
                                   f"{job.name}") from e
            devs = slice_devices(backend, part.handle, devices)
            mesh = make_slice_mesh(devs, devs.shape)
            log(f"{job.name}: EARLY RESTART {profile.name} -> {bigger.name} "
                f"at {part.handle} ({devs.shape[0]}x{devs.shape[1]} devices)")
            params = place_params(params, specs, mesh)
            toks, logits = decode_on_slice(cfg, params, mesh, job.prompt,
                                           job.n_tokens, context=context)
            runs.append(TenantRun(job, bigger, mesh.devices, toks, logits,
                                  restarted_from=profile))
            pm.release(part)
        log(f"{job.name}: {len(toks)} tokens on {runs[-1].profile.name}, "
            f"first 8: {toks[:8]}")
    log(f"final state: {pm.describe()} (back to empty pod: "
        f"{pm.state == backend.initial_state()})")
    return runs
