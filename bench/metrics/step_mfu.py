"""Model FLOP utilization of the whole step: ``bench/flops.py`` FLOPs per
step times the steps of the traced window, over the window, the chips and
the chip's bf16 peak (``bench/peaks.json``)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("steps"):
        return None
    work = ctx["flops_per_step"] * ctx["steps"]
    return 100.0 * work / (tr["window_s"] * ctx["chips"] * ctx["peak_flops"])
