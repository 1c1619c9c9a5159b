"""Share of the step program's device time that the chip's roofline
needs: for each traced execution, the larger of its operations over the
peak rate and its bytes over the peak bandwidth (``cost/<reference>.py``,
live positions only), summed, over the summed execution times."""


def read(run):
    a = run.analysis()
    if not a or not a["steps"]:
        return None
    flops_s = run.peaks["bf16_flops_per_s"]
    bytes_s = run.peaks["hbm_bytes_per_s"]
    least = 0.0
    for s in a["steps"]:
        flops, nbytes = run.ctx.cost.step_cost(run.ctx.config, s["rows"],
                                               s["position"])
        least += max(flops / flops_s, nbytes / bytes_s)
    return 100.0 * least / (sum(s["dur"] for s in a["steps"]) / 1e9)
