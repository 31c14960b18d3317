"""Host milliseconds per traced step from ``step()`` entry to the last
forward wave's ``on_wave``: dispatch of the forward waves and their
transfers, mean over the traced steps."""


def read(ctx):
    fwd = ctx.get("fwd_ms") or []
    return sum(fwd) / len(fwd) if fwd else None
