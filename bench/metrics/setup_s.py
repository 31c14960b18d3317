"""Seconds from process start to the start of the measured window: imports,
weights, plan, the checked first steps and warm-up (compiles included)."""


def read(ctx):
    return ctx.get("setup_s")
