"""Host milliseconds per traced step in the AdamW update as a whole: the
program's ``spindle.optim`` span, its ``spindle.optim.clip`` child (the
global norm for clipping) included (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.phase_ms(ctx, "spindle.optim", "spindle.optim.clip")
