"""The device time of the ops that read or write an SSM's recurrent state,
per execution of the step program, and the least bytes that state moves.

An op counts when the trace records the program's ``repro.ssm.state``
scope for it (``models/ssm.ssm_decode_step``: decay, outer product, add,
read-out, ``D`` skip), or when its result is float32 shaped
``[..., heads, headdim, d_state]``: the layer scan's slice of the state
stack, its write of the new state into the output stack, and any copy of
the stack.  Where no op of the trace carries the scope, the shape alone
picks them.  Control-flow ops enclose the ops they run and never count.
"""

from __future__ import annotations

import bisect
import re

import trace_reduce as tr
import xspace

SCOPE = "repro.ssm.state"
F32 = 4
_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def dims(hp: dict) -> tuple[int, int, int]:
    """(heads, headdim, d_state) of the configuration's state."""
    return (hp["expand"] * hp["d_model"] // hp["headdim"], hp["headdim"],
            hp["d_state"])


def least_bytes(hp: dict, rows: int) -> int:
    """Bytes of float32 state that one decode step must read and write:
    every layer's ``[rows, heads, headdim, d_state]``, once each way."""
    h, p, n = dims(hp)
    return 2 * F32 * hp["n_layer"] * rows * h * p * n


def result_shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """(dtype, dims) of each result of an op, from its HLO text
    (``%fusion.3 = f32[64,16,80,64,128]{...} fusion(...)``, or a tuple)."""
    head = text.split(" = ", 1)[1] if " = " in text else ""
    if head.startswith("("):
        head = head[:head.find(")") + 1]
    else:
        head = head.split("{", 1)[0].split(" ", 1)[0]
    return [(t, tuple(int(d) for d in ds.split(",") if d))
            for t, ds in _SHAPE.findall(head)]


def per_step(run) -> list[tuple[dict, float]] | None:
    """Each traced execution of the step program with the nanoseconds its
    state ops took; None without a trace or with no state op in it."""
    a = run.analysis()
    space = xspace.of_run(run)
    if not (a and a["steps"] and space and space["devices"]):
        return None
    tail = dims(run.ctx.config)
    ops = [op for op in space["devices"][0]["ops"]
           if not op[0].startswith(tr.CONTAINERS)]
    scoped = any(xspace.mentions(op, SCOPE) for op in ops)

    def is_state(op):
        return (scoped and xspace.mentions(op, SCOPE)) or any(
            t == "f32" and ds[-3:] == tail for t, ds in result_shapes(op[0]))
    picked = sorted((op[1], op[2]) for op in ops if is_state(op))
    if not picked:
        return None
    starts = [s for s, _ in picked]
    out = []
    for step in a["steps"]:
        lo = bisect.bisect_left(starts, step["start"])
        hi = bisect.bisect_left(starts, step["start"] + step["dur"])
        out.append((step, sum(d for _, d in picked[lo:hi])))
    return out
