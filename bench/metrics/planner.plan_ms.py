"""Milliseconds from ``SpindleSession.bind()`` to its ``on_plan`` hook: the
plan through the plan cache (a miss in a fresh process), host clock."""


def read(ctx):
    return ctx.get("plan_ms")
