"""The traced run's ``breakdown``: the device ops that took most time, and
the idle gaps summed by what the host was doing before the next step, both
in seconds; and the chips' mean busy time over the traced window."""

import trace_reduce as tr


def _label(step: dict | None) -> str:
    if step is None:
        return "after the last step of a batch"
    if step["position"] == 0:
        return ("restart: engine rebuilt on the larger slice"
                if step["attempt"] else
                "batch start: engine built, caches made, prompts sent")
    return {"replay": "replay: between prompt steps",
            "decode": "decode: host sync, argmax and predictor"}[step["phase"]]


def gap_labels(a: dict, dev: dict) -> dict[str, float]:
    """Idle seconds of ``dev`` in the traced window, summed by the step
    that ends each gap."""
    starts = sorted((s["start"], i) for i, s in enumerate(a["steps"]))
    gaps: dict[str, float] = {}
    for g0, g1 in tr.idle_gaps(dev, a["t0"], a["t1"]):
        # a step program starts a little before its first op: the gap
        # waits for the first step that starts after the gap opens
        nxt = next((a["steps"][i] for s, i in starts if s > g0), None)
        label = _label(nxt)
        gaps[label] = gaps.get(label, 0.0) + (g1 - g0) / 1e9
    return gaps


def read(run) -> dict:
    a = run.analysis()
    t0, t1 = a["t0"], a["t1"]
    devs = a["devices"]
    busy = sum(tr.busy_ns(d, t0, t1) for d in devs) / len(devs)
    ops: dict[str, float] = {}
    for d in devs:
        for name, secs in tr.top_ops(d, t0, t1, k=len(d["ops"])):
            ops[name] = ops.get(name, 0.0) + secs / len(devs)
    gaps = gap_labels(a, devs[0])
    return {
        "device_ops": sorted(([n, v] for n, v in ops.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": sorted(([n, v] for n, v in gaps.items()),
                            key=lambda x: -x[1])[:10],
        "step_program": a["step"],
        "busy_window": (busy / 1e9, (t1 - t0) / 1e9),
    }
