"""Tokens the requests asked for, summed over every request the window
completed, over the window's wall time up to the end of its last batch."""


def read(run):
    return sum(run.driver.tokens(r) for r in run.records) / run.window_s
