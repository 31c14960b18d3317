"""The same model FLOPs over the time the devices were busy (summed over
the cell's chips) at the bf16 peak: how near the roofline the devices run
while they run, whatever implements the work."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("steps") or tr["busy_s"] <= 0:
        return None
    work = ctx["flops_per_step"] * ctx["steps"]
    return 100.0 * work / (tr["busy_s"] * ctx["chips"] * ctx["peak_flops"])
