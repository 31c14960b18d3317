"""Share of the traced window in which no operation ran on a device, mean
over the cell's chips (``bench/trace.py``)."""


def read(ctx):
    tr = ctx.get("trace")
    return tr["idle_pct"] if tr else None
