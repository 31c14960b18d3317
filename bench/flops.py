"""Model FLOPs of one training step, from a cell's plain spec.

Counts the matrix products the model needs: per layer the four attention
projections, the three SwiGLU matrices, and the attention scores and
weighted sum (QK^T and AV, over the full S x S as the naive attention
computes them); the decoder's prefix projections and LM head; the contrastive
projections and logits. Norms, RoPE, softmax and the optimizer are left out.
A training step is three times the forward pass (forward, and the two
products of the backward pass); recomputation is not counted.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_forward(d: int, ff: int, batch: int, seq: int) -> float:
    """One transformer layer's forward FLOPs over ``batch`` x ``seq`` tokens."""
    tokens = batch * seq
    return 2.0 * tokens * (4 * d * d + 3 * d * ff) + 4.0 * batch * seq * seq * d


def component_forward(spec: Dict[str, Any], comp: str, batch: int,
                      in_widths: Dict[str, int]) -> float:
    """Forward FLOPs of one execution of ``comp`` over ``batch`` samples;
    ``in_widths`` are the widths of the components feeding a join."""
    c = spec["components"][comp]
    d = c["d_model"]
    if c["kind"] == "contrastive":
        proj = sum(2.0 * batch * w * d for w in in_widths.values())
        return proj + 2.0 * batch * batch * d
    seq = c["seq"]
    f = c["n_layers"] * layer_forward(d, c["d_ff"], batch, seq)
    if c["kind"] == "decoder":
        f += sum(2.0 * batch * w * d for w in in_widths.values())
        f += 2.0 * batch * seq * d * c["vocab"]
    return f


def forward_flops(spec: Dict[str, Any]) -> float:
    """Forward FLOPs of one step over every flow of the spec."""
    comps = spec["components"]
    total = 0.0
    for f in spec["flows"]:
        b = f["batch"]
        for br in f["branches"]:
            for comp in br:
                total += component_forward(spec, comp, b, {})
        widths = {br[-1]: comps[br[-1]]["d_model"] for br in f["branches"] if br}
        # a merged decoder over the union batch costs the sum of its tasks'
        # shares: every term is linear in the batch
        total += component_forward(spec, f["join"][0], b, widths)
    return total


def step_flops(spec: Dict[str, Any]) -> float:
    """Model FLOPs of one training step: forward and backward."""
    return 3.0 * forward_flops(spec)
