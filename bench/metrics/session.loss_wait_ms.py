"""Host milliseconds per traced step that the session waits for the device
to hand over the loss: the program's ``spindle.loss_read`` span around
``float(loss)`` (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.phase_ms(ctx, "spindle.loss_read")
