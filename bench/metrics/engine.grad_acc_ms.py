"""Host milliseconds per traced step in gradient bookkeeping: the zero
gradient tree (``spindle.grad_init``) and the accumulation of each record's
parameter gradients and cotangents (``spindle.grad_acc:<instance>``), self
time (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.phase_ms(ctx, "spindle.grad_init", "spindle.grad_acc")
