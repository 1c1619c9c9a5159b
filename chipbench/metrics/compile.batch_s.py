"""Seconds spent tracing, lowering and compiling (or loading from the
cache) programs during a batch, averaged over the batches the profiler did
not record: the union of the intervals of the program's compile log
(``repro.obs.spans.compile_log``) whose events ended between a batch's
issue and its return.  Each engine the restart loop builds traces its step
anew.  Nothing to read in a program without the compile log."""


def read(run):
    try:
        from repro.obs.spans import compile_log
    except ImportError:
        return None
    recs = [r for r in run.records if not r.get("traced")]
    if not recs:
        return None
    log = compile_log()
    return sum(log.seconds(r["t_issue"], r["t_return"])
               for r in recs) / len(recs)
