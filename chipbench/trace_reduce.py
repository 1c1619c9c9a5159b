"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so that the second can be checked on a recorded
trace without the chip:

1. ``extract(path)`` reads the ``.xplane.pb`` the JAX profiler wrote and
   keeps the device timelines (each chip's XLA ops and XLA modules) and the
   benchmark's own host spans (names starting with ``chipbench.``), as
   plain lists of ``[name, start_ns, duration_ns]``.
2. The functions below reduce those lists: busy time as the union of op
   intervals, idle gaps, the step program and its executions.
"""

from __future__ import annotations

import glob
import os

HOST_PREFIX = "chipbench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def extract(path: str) -> dict:
    from jax.profiler import ProfileData
    devices, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events]
            if dev["ops"]:
                devices.append(dev)
        else:
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    devices.sort(key=lambda d: d["name"])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def spans(events: dict, name: str) -> list[tuple[float, float]]:
    """(start, end) of the host spans called ``chipbench.<name>``."""
    return [(s, s + d) for n, s, d in events["host"]
            if n == HOST_PREFIX + name]


def window(events: dict) -> tuple[float, float] | None:
    """From the start of the first traced batch to the end of the last."""
    batches = spans(events, "batch")
    if not batches:
        return None
    return batches[0][0], batches[-1][1]


def union(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals clipped to [t0, t1]."""
    out: list[list[float]] = []
    for s, d in sorted((s, d) for s, d in intervals):
        a, b = max(s, t0), min(s + d, t1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(dev: dict, t0: float, t1: float) -> float:
    return sum(b - a for a, b in union(
        ((s, d) for _, s, d in dev["ops"]), t0, t1))


def idle_gaps(dev: dict, t0: float, t1: float) -> list[tuple[float, float]]:
    """The intervals of [t0, t1] in which no op ran on the device."""
    gaps, last = [], t0
    for a, b in union(((s, d) for _, s, d in dev["ops"]), t0, t1):
        if a > last:
            gaps.append((last, a))
        last = b
    if t1 > last:
        gaps.append((last, t1))
    return gaps


def step_program(dev: dict, t0: float, t1: float) -> str | None:
    """The XLA module with the most device time in [t0, t1]: the model
    step on the serving path."""
    total: dict[str, float] = {}
    for n, s, d in dev["modules"]:
        if t0 <= s < t1:
            total[n] = total.get(n, 0.0) + d
    return max(total, key=total.get) if total else None


def executions(dev: dict, module: str, t0: float, t1: float
               ) -> list[tuple[float, float]]:
    """(start, duration) of each execution of ``module`` in [t0, t1]."""
    return sorted((s, d) for n, s, d in dev["modules"]
                  if n == module and t0 <= s < t1)


def op_name(event_name: str) -> str:
    """An op's HLO text cut to its name and result shape
    (``%fusion.152 = bf16[32,6144]``)."""
    return event_name.split("{", 1)[0].strip()


#: control-flow ops enclose the ops they run: counting them would count
#: that time twice
CONTAINERS = ("%while", "%conditional", "%call")


def top_ops(dev: dict, t0: float, t1: float, k: int = 10
            ) -> list[list]:
    """The ``k`` ops with the most device time, in seconds."""
    total: dict[str, float] = {}
    for n, s, d in dev["ops"]:
        if t0 <= s < t1 and not n.startswith(CONTAINERS):
            total[op_name(n)] = total.get(op_name(n), 0.0) + d
    best = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9] for n, v in best]
