"""Host seconds from an attempt's start (its batch's issue, or the restart
before it) to the restart that throws it away, averaged over the window's
restarts.  Nothing to read where no batch restarted."""


def read(run):
    lost = []
    for rec in run.records:
        start = rec["t_issue"]
        for mark in rec["restarts"]:
            lost.append(mark - start)
            start = mark
    return sum(lost) / len(lost) if lost else None
