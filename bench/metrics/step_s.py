"""Seconds per training step: the measured window, ended by
``block_until_ready`` on parameters and optimizer state, over the steps
completed in it."""


def read(ctx):
    return ctx.get("step_s")
