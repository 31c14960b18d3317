"""Model FLOPs of one training step, from a cell's plain spec.

The architecture counts the matrix products of one forward pass
(``forward_flops`` of ``bench/arch/<arch>/reference.py``); norms, softmax
and the optimizer are left out. A training step is three times the forward
pass (forward, and the two products of the backward pass); recomputation is
not counted.
"""

from __future__ import annotations

from typing import Any, Dict

from . import spec as specmod


def step_flops(spec: Dict[str, Any]) -> float:
    """Model FLOPs of one training step: forward and backward."""
    return 3.0 * specmod.arch(spec).forward_flops(spec)
