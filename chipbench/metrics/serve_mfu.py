"""Model operations of the useful tokens (every completed request's prompt
and asked-for tokens) per second, over the chips' peak bf16 rate, in
percent.  Taken over the batches the profiler did not record and the time
they took: the traced batch runs slower, and stopping the profiler takes
seconds between batches."""


def read(run):
    if run.peaks is None:
        return None
    recs = [r for r in run.records if not r.get("traced")]
    if not recs:
        return None
    flops = sum(run.driver.useful_flops(r) for r in recs)
    secs = sum(r["t_return"] - r["t_issue"] for r in recs)
    return 100.0 * flops / secs / (run.chips * run.peaks["bf16_flops_per_s"])
