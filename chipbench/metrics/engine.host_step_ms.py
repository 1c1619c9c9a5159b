"""Host time of a decode step while the chip waits for the host: the self
time of each ``repro.engine.decode`` span of the traced window outside its
``repro.engine.sync`` children (each a step's wait for its token), summed,
over the number of those steps, in milliseconds.  It holds the step's
dispatch, the tokens' append and the predictor.  Nothing to read in a
program that records no such spans."""

import program_spans as ps


def read(run):
    evs = ps.spans(run)
    if not evs:
        return None
    total, steps = 0.0, 0
    for decode in ps.named(evs, "repro.engine.decode"):
        syncs = ps.named(evs, "repro.engine.sync", within=decode)
        total += ps.self_ns(decode, syncs)
        steps += len(syncs)
    return total / steps / 1e6 if steps else None
