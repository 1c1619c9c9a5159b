"""Mean device time of one execution of the step program (the XLA module
with the most device time in the traced window), in milliseconds."""


def read(run):
    a = run.analysis()
    if not a or not a["steps"]:
        return None
    return sum(s["dur"] for s in a["steps"]) / len(a["steps"]) / 1e6
