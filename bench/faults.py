"""Faults planted in the program, to show that the comparison catches them.

Used by ``bench/control.py`` on the chip and by the tests on the CPU; the
benchmark's own runs never plant one. Each fault patches the engine for the
duration of a ``with planted(kind):`` block:

- ``unchanged``: a step that returns its parameters and optimizer state
  unchanged (the loss is still computed);
- ``half_batch``: every task's batch cut to its first half, the means taken
  over the rest.
"""

from __future__ import annotations

import contextlib

KINDS = ("unchanged", "half_batch")


def _half(batches):
    return {t: {k: v[: v.shape[0] // 2] for k, v in b.items()}
            for t, b in batches.items()}


@contextlib.contextmanager
def planted(kind: str):
    from .program import WaveEngine

    if kind == "unchanged":
        name, orig = "train_step", WaveEngine.train_step

        def patched(self, params, opt_state, batches, optimizer, **kw):
            _, _, loss = orig(self, params, opt_state, batches, optimizer, **kw)
            return params, opt_state, loss
    elif kind == "half_batch":
        name, orig = "loss_and_grads", WaveEngine.loss_and_grads

        def patched(self, params, batches, **kw):
            return orig(self, params, _half(batches), **kw)
    else:
        raise ValueError(f"unknown fault {kind!r}; choose from {KINDS}")
    setattr(WaveEngine, name, patched)
    try:
        yield
    finally:
        setattr(WaveEngine, name, orig)
