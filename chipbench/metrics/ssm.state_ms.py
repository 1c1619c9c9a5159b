"""Device time of the ops that read or write the recurrent state
(``ssm_state.py``), per execution of the step program, mean over the traced
executions, in milliseconds.  Nothing to read in a program without such
state."""

import ssm_state


def read(run):
    steps = ssm_state.per_step(run)
    if not steps:
        return None
    return sum(ns for _, ns in steps) / len(steps) / 1e6
