"""Model FLOPs of one training step, from a cell's plain spec.

The architecture counts the matrix products of one forward pass
(``forward_flops`` of ``bench/arch/<arch>/reference.py``); norms, softmax
and the optimizer are left out. A training step is three times the forward
pass (forward, and the two products of the backward pass); recomputation is
not counted.

Routed products. Where a product's rows depend on the routing (a mixture of
experts: each token multiplied by the experts chosen for it), the
architecture also exports ``routed_flops(spec)``: the part of
``forward_flops`` that those products make, counted as the useful work on
the experts held here (the configuration's ``share``), that is, for each
routed product, tokens x experts per token x held / total x 2 x contracted x
out. It is an expectation over the routing: skewed routing puts more or
fewer rows on the experts held here than it counts. An architecture without
routed products does not define it and reads 0. ``tests/bench/
test_bench_flops.py`` ties both numbers to the program: ``forward_flops -
routed_flops`` to its ``dot_general`` products, and ``routed_flops`` x total
/ held to its routed products at their static rows (one row per token and
chosen expert). A routed kernel's ``<kernel>_roofline`` reader takes its
operations from ``routed_flops``.
"""

from __future__ import annotations

from typing import Any, Dict

from . import spec as specmod


def step_flops(spec: Dict[str, Any]) -> float:
    """Model FLOPs of one training step: forward and backward."""
    return 3.0 * specmod.arch(spec).forward_flops(spec)


def routed_flops(spec: Dict[str, Any]) -> float:
    """The routed part of ``forward_flops`` (see above); 0 for an
    architecture without routed products."""
    routed = getattr(specmod.arch(spec), "routed_flops", None)
    return 0.0 if routed is None else float(routed(spec))
