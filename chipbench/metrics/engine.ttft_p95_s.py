"""95th percentile (nearest rank, exact), over the requests of the batches
the profiler did not record, of the time from the batch's issue to the
first of the request's tokens to reach the host
(``Request.token_times[0]``), restarts included.  The engine feeds the
argmax at the last prompt position on without bringing it to the host, so
this is the token of the first decode step, one step after that one.
Nothing to read in a program that does not stamp its tokens."""

import math


def read(run):
    waits = sorted(r.token_times[0] - rec["t_issue"]
                   for rec in run.records if not rec.get("traced")
                   for r in rec["requests"] if getattr(r, "token_times", None))
    if not waits:
        return None
    return waits[math.ceil(0.95 * len(waits)) - 1]
