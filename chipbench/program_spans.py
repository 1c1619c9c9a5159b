"""The program's own host spans in a traced run, and the device's idle time
put down to them.

The live serving path names its spans ``repro.*`` (the list is in
``src/repro/obs/spans.py``); they are profiler annotations, so they sit in
the same ``.xplane.pb`` as the device's ops and the harness's
``chipbench.*`` spans, on one clock.  ``trace_reduce.extract`` keeps only
the harness's spans; this module reads the program's from the same file:

- ``spans(run)``: the ``repro.*`` spans, and the harness's
  ``chipbench.batch`` spans, of the trace this run wrote, as
  ``[name, start_ns, end_ns, args]``; read once and kept on the run.  None
  where the newest trace under ``.chipbench/trace/`` is not the run's (its
  batch spans differ) or holds no program span, as with a program that
  records none;
- ``self_ns``: a span's time outside some of its children;
- ``idle_by_span``: each idle gap of a chip in the traced window, cut where
  spans open and close, each piece put down to the innermost span covering
  it, else to ``chipbench.batch``, else to "outside any span".

    python3 chipbench/program_spans.py <trace dir>

prints that idle table for the trace under a directory.
"""

from __future__ import annotations

import glob
import math
import os
import sys
from pathlib import Path

import trace_reduce as tr

TRACE_ROOT = Path(__file__).resolve().parent.parent / ".chipbench" / "trace"
PREFIX = "repro."
BATCH = tr.HOST_PREFIX + "batch"
OUTSIDE = "outside any span"


def extract(path: str) -> list[list]:
    """``[name, start_ns, end_ns, args]`` of the host spans named
    ``repro.*`` and ``chipbench.batch`` in a trace, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            out += [[e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)] for e in line.events
                    if e.name.startswith(PREFIX) or e.name == BATCH]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _read(run) -> list[list] | None:
    if not (run.trace and run.trace.get("host")):
        return None
    paths = glob.glob(os.path.join(str(TRACE_ROOT), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    evs = extract(max(paths, key=os.path.getmtime))
    mine = [(s, e) for n, s, e, _ in evs if n == BATCH]
    theirs = [(s, s + d) for n, s, d in run.trace["host"] if n == BATCH]
    if mine != theirs:
        return None
    t0, t1 = tr.window(run.trace)
    evs = [s for s in evs if t0 <= s[1] and s[2] <= t1]
    return evs if any(s[0].startswith(PREFIX) for s in evs) else None


def spans(run) -> list[list] | None:
    """The program's spans in the trace this run wrote, inside its traced
    window (see the module's docstring)."""
    if "_program_spans" not in vars(run):
        run._program_spans = _read(run)
    return run._program_spans


def named(evs: list[list], name: str, within=None) -> list[list]:
    """The spans called ``name``, inside the span ``within`` if given."""
    lo, hi = (within[1], within[2]) if within else (-math.inf, math.inf)
    return [s for s in evs if s[0] == name and lo <= s[1] and s[2] <= hi]


def self_ns(parent: list, kids: list[list]) -> float:
    """``parent``'s duration less the part of it that ``kids`` cover."""
    covered = tr.union(((s, e - s) for _, s, e, *_ in kids),
                       parent[1], parent[2])
    return parent[2] - parent[1] - sum(b - a for a, b in covered)


def segments(evs: list[list]) -> list[tuple[float, float, str]]:
    """``(start, end, name)``: the innermost span at each instant that some
    span covers, for spans that nest, as those of one thread do."""
    out, stack, t = [], [], -math.inf
    for name, s, e, *_ in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            if end > t:
                out.append((t, end, top))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][0]))
        stack.append((name, e))
        t = s
    while stack:
        top, end = stack.pop()
        if end > t:
            out.append((t, end, top))
            t = end
    return out


def idle_by_span(evs: list[list], dev: dict, t0: float, t1: float
                 ) -> dict[str, float]:
    """Idle nanoseconds of ``dev`` in ``[t0, t1]`` by the innermost span
    covering them (see the module's docstring)."""
    segs = segments(evs)
    out: dict[str, float] = {}
    i = 0
    for g0, g1 in tr.idle_gaps(dev, t0, t1):
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        t, j = g0, i
        while t < g1:
            if j < len(segs) and segs[j][0] <= t:
                end, name = min(segs[j][1], g1), segs[j][2]
                j += 1
            else:
                end = min(segs[j][0], g1) if j < len(segs) else g1
                name = OUTSIDE
            if end > t:
                out[name] = out.get(name, 0.0) + end - t
                t = end
    return out


def table(trace_dir: str) -> list[str]:
    """The idle table of the trace under ``trace_dir``, as lines."""
    path = tr.find_xplane(trace_dir)
    events, evs = tr.extract(path), extract(path)
    t0, t1 = tr.window(events)
    lines = []
    for dev in events["devices"]:
        idle = idle_by_span(evs, dev, t0, t1)
        total = sum(idle.values())
        lines.append(f"{dev['name']}: idle {total / 1e9:.6f} s of a "
                     f"{(t1 - t0) / 1e9:.6f} s window")
        for name, ns in sorted(idle.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {name:28s} {ns / 1e9:10.6f} s "
                         f"{100 * ns / total:7.2f}%")
        under = sum(ns for n, ns in idle.items() if n.startswith(PREFIX))
        lines.append(f"  {'under repro.* spans':28s} {under / 1e9:10.6f} s "
                     f"{100 * under / total:7.2f}%")
    return lines


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(f"usage: {sys.argv[0]} <trace dir>")
    print("\n".join(table(sys.argv[1])))
