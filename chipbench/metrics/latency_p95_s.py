"""95th percentile (nearest rank, exact) over every request of the window
of the time from its batch's issue to its batch's return, restarts
included."""

import math


def read(run):
    lat = sorted(x for r in run.records for x in run.driver.latencies(r))
    return lat[math.ceil(0.95 * len(lat)) - 1]
