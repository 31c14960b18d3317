"""Architecture ``mt_dense`` as the program builds it: the cell's
``MTModel`` from the plain spec, holding the benchmark's seeded weights.

The only architecture-specific code that imports the program;
``bench/program.py`` calls ``build_model`` through ``spec.arch``.
"""

from __future__ import annotations

from typing import Any, Dict

from repro.runtime.mtmodel import ExecComponent, ExecFlow, MTModel


class SeededModel(MTModel):
    """``MTModel`` whose ``init`` hands over the benchmark's seeded weights
    (made in one jitted call) instead of initialising leaf by leaf."""

    def __init__(self, components, flows, weights):
        super().__init__(components, flows)
        self._weights = weights

    def init(self, rng):
        weights, self._weights = self._weights, None
        if weights is None:
            raise RuntimeError("the seeded weights were handed over already")
        return weights


def build_model(spec: Dict[str, Any], weights) -> SeededModel:
    comps = [
        ExecComponent(
            name, c["kind"], c["n_layers"], c["d_model"], c["n_heads"],
            d_ff=c["d_ff"], vocab=c["vocab"], shared=c["shared"],
            merge_shared=c["merge_shared"],
        )
        for name, c in spec["components"].items()
    ]
    flows = [
        ExecFlow(f["task"], tuple(tuple(b) for b in f["branches"]),
                 tuple(f["join"]), f["batch"], dict(f["seq"]))
        for f in spec["flows"]
    ]
    return SeededModel(comps, flows, weights)
