"""Production mesh construction, and the map from buddy slices to devices.

A v5e pod is a 16x16 chip grid (256 chips); the multi-pod deployment is
2 pods = 512 chips connected over DCN.  Functions, not module constants —
importing this module never touches jax device state.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.core.tpu_slices import TpuPodBackend

#: buddy pod laid over the chips of one host: device count -> (pod_shape,
#: max_depth).  A v5e host holds one chip or a 2x2.
HOST_PODS = {1: ((1, 1), 0), 4: ((2, 2), 2)}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_slice_mesh(devices, shape: tuple[int, int],
                    axes: tuple[str, str] = ("data", "model")):
    """Mesh over a sub-slice's devices (multi-tenant launcher)."""
    arr = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(arr, axes)


def host_pod_backend(devices=None, **backend_kw) -> TpuPodBackend:
    """The buddy pod over the devices present: a 1x1 pod on one chip, a 2x2
    with ``max_depth=2`` on four.  Any other count is refused, so a restart
    can never target a slice the host does not have."""
    n = len(jax.devices() if devices is None else devices)
    if n not in HOST_PODS:
        raise ValueError(f"no buddy pod for {n} devices; "
                         f"supported counts: {sorted(HOST_PODS)}")
    pod_shape, max_depth = HOST_PODS[n]
    return TpuPodBackend(max_depth=max_depth, pod_shape=pod_shape,
                         **backend_kw)


def slice_devices(backend: TpuPodBackend, handle, devices=None
                  ) -> np.ndarray:
    """The devices of the slice at ``handle``, as a [sx, sy] array.

    The pod's devices are laid out row-major over ``backend.pod_shape``; the
    slice is the rectangle at the handle's origin and shape.
    """
    devices = jax.devices() if devices is None else devices
    px, py = backend.pod_shape
    if len(devices) < px * py:
        raise ValueError(f"pod {px}x{py} needs {px * py} devices, "
                         f"{len(devices)} present")
    grid = np.asarray(devices[:px * py], dtype=object).reshape(px, py)
    x0, y0 = backend.slice_origin(handle)
    sx, sy = backend.slice_shape(handle)
    return grid[x0:x0 + sx, y0:y0 + sy]
