"""From the start of the process to the first timed batch: weights made on
the device, programs compiled or read from the cache, one warm batch."""


def read(run):
    return run.setup_s
