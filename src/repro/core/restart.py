"""Restart policies (paper §2.3, §4.3, §6).

MIGM recovers from OOM with *checkpointless restarts* (unlike MISO, which
checkpoints/restores every active job on reconfiguration).  Two flavours:

* **OOM restart** — the job crashed; requeue it with the next-larger profile
  as its estimate (``next_larger_profile``).
* **Early restart** — the time-series predictor's converged peak estimate
  exceeds the current partition; preempt *now* and requeue with the predicted
  peak as the estimate, saving the wasted iterations between now and the
  would-be crash (Qwen2: restart at iter 6 instead of crashing at 94).

For JAX jobs a "restart" is cheap by construction: model state lives in host
pytrees between steps, so restarting on a larger slice is re-`jit`-ing the
step function with new shardings and re-placing the state — no external
checkpoint needed.  :func:`migrate_state` implements exactly that and is used
by the live multi-tenant launcher (examples/multi_tenant.py).
"""

from __future__ import annotations

from typing import Any, Callable

import jax

from repro.core.partition_state import PartitionBackend, PartitionProfile
from repro.core.planner.ladders import predicted_rung, restart_rung


def oom_restart_target(backend: PartitionBackend,
                       current: PartitionProfile) -> PartitionProfile:
    """Next-larger slice after a crash (paper: 10GB -> 20GB example) — the
    first rung of the planner's growth ladder
    (:func:`repro.core.planner.ladders.restart_rung`)."""
    return restart_rung(backend, current)


def early_restart_target(backend: PartitionBackend,
                         predicted_peak_gb: float,
                         headroom: float = 1.0) -> PartitionProfile | None:
    """Tightest slice that holds the predicted peak (+ optional headroom) —
    the planner's :func:`~repro.core.planner.ladders.predicted_rung`."""
    return predicted_rung(backend, predicted_peak_gb, headroom)


def host_restart_target(backend: PartitionBackend, current_gb: float | None,
                        err: "NeedsLargerPartition") -> PartitionProfile:
    """The slice a live early restart grows to.  A target that is not one of
    ``backend``'s profiles (the predicted peak outgrows the host) or is no
    larger than the current slice raises, so a restart loop cannot spin."""
    nxt = err.profile
    if (nxt is None or nxt not in backend.profiles
            or nxt.mem_gb <= (current_gb or 0.0)):
        largest = backend.profiles[-1]
        raise RuntimeError(
            f"early restart from the {current_gb}GB slice wants "
            f"{nxt.name if nxt else 'a larger slice'}; the largest slice of "
            f"this host is {largest.name} ({largest.mem_gb:.1f}GB)") from err
    return nxt


def migrate_state(state: Any, target_shardings: Any) -> Any:
    """Re-place a job's pytree state onto a new (larger) sub-mesh.

    This is the TPU-native 'process restart': ``jax.device_put`` with the new
    shardings moves params/caches; the caller re-jits its step function with
    the matching in/out shardings.
    """
    return jax.device_put(state, target_shardings)


def with_oom_retry(run_step: Callable[..., Any], *,
                   backend: PartitionBackend,
                   profile: PartitionProfile,
                   max_retries: int = 4) -> Callable[..., Any]:
    """Wrap a step callable with grow-on-OOM semantics for live execution.

    On a JAX RESOURCE_EXHAUSTED error the wrapper re-raises a
    :class:`NeedsLargerPartition` carrying the next profile, which the
    scheduler handles as a requeue (mirroring the paper's restart loop).
    """

    def wrapped(*args, **kwargs):
        try:
            return run_step(*args, **kwargs)
        except Exception as e:  # XlaRuntimeError: RESOURCE_EXHAUSTED
            if "RESOURCE_EXHAUSTED" not in str(e) and "Out of memory" not in str(e):
                raise
            raise NeedsLargerPartition(oom_restart_target(backend, profile)) from e

    return wrapped


class NeedsLargerPartition(RuntimeError):
    def __init__(self, profile: PartitionProfile | None = None) -> None:
        super().__init__(f"restart on "
                         f"{profile.name if profile else 'a larger slice'}")
        self.profile = profile
