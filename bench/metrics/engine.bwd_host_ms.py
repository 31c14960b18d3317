"""Host milliseconds per traced step from the last forward wave's
``on_wave`` to ``step()``'s return: reverse waves, gradient accumulation,
AdamW and the loss read, mean over the traced steps."""


def read(ctx):
    bwd = ctx.get("bwd_ms") or []
    return sum(bwd) / len(bwd) if bwd else None
