"""Device operations per traced step, summed over the cell's chips: the
events of the trace's ``XLA Ops`` lines (``bench/trace.py``'s
``n_device_ops``) over the window's steps. An eager primitive runs as a
program of one or a few such operations; a compiled program runs as all of
its own, its fusions and the compiler's asynchronous copies and slices
included. It counts operations on the device, not program launches."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("steps"):
        return None
    return tr["n_device_ops"] / ctx["steps"]
