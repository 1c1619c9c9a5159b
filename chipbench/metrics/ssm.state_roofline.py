"""Share of the state ops' device time (``ssm.state_ms``) that the chip's
bandwidth needs: the float32 state's least bytes of each traced execution
(every layer's state read and written once, ``ssm_state.least_bytes``),
over the peak HBM bandwidth, summed, over the state ops' summed time, in
percent."""

import ssm_state


def read(run):
    steps = ssm_state.per_step(run)
    if not steps or run.peaks is None:
        return None
    least = sum(ssm_state.least_bytes(run.ctx.config, s["rows"])
                for s, _ in steps) / run.peaks["hbm_bytes_per_s"]
    spent = sum(ns for _, ns in steps) / 1e9
    return 100.0 * least / spent if spent else None
