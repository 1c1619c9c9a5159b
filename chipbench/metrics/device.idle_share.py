"""Share of the traced window in which no operation ran on the chip: one
minus the union of the op intervals over the window, averaged over the
chips, in percent."""

import trace_reduce as tr


def read(run):
    a = run.analysis()
    if not a:
        return None
    span = a["t1"] - a["t0"]
    busy = [tr.busy_ns(d, a["t0"], a["t1"]) for d in a["devices"]]
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
