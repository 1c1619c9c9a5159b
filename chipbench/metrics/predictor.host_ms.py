"""Mean duration of the ``repro.engine.predictor`` spans of the traced
window, in milliseconds: one decode step's memory bookkeeping, the
predictor's fit and the restart decision.  Nothing to read in a program
that records no such spans."""

import program_spans as ps


def read(run):
    evs = ps.spans(run)
    got = ps.named(evs, "repro.engine.predictor") if evs else []
    if not got:
        return None
    return sum(e - s for _, s, e, _ in got) / len(got) / 1e6
