"""Host milliseconds per traced step in the reverse-wave ``jax.vjp`` pulls:
the self time of the program's ``spindle.bwd:<instance>`` spans, each one
record's pull and its cotangent's placement (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.phase_ms(ctx, "spindle.bwd")
