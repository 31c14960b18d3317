"""Pallas TPU chunked scan for the RG-LRU linear recurrence.

Computes ``h_t = a_t ⊙ h_{t-1} + b_t`` over (B, S, D) gate/input tensors —
the inner loop of RecurrentGemma's recurrent block.  TPU adaptation
(DESIGN.md §3): instead of a warp-level scan (the GPU route), the sequence
is cut into VMEM-resident chunks; the grid walks ``(B, D-blocks, chunks)``
with the chunk dimension innermost and sequential, carrying the (bd,)
recurrent state in VMEM scratch.  Inside a chunk the recurrence runs as a
``fori_loop`` of fused VPU multiply-adds over (bd,)-wide rows — sequential
in time but fully vectorized across the feature block, which is the shape
the VPU wants (8×128 lanes).

Block defaults (chunk=256, bd=512) hold 2·256·512·4 B = 1 MB of a/b plus
0.5 MB of output per step in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(
    a_ref,  # (1, chunk, bd)
    b_ref,  # (1, chunk, bd)
    h_ref,  # (1, chunk, bd) out
    state_scr,  # (1, bd) carry
    *,
    chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    def step(t, carry_h):
        h = a_ref[0, t] * carry_h + b_ref[0, t]
        h_ref[0, t] = h.astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, state_scr[0])
    state_scr[0] = h


def rglru_scan(
    a: jnp.ndarray,  # (B, S, D) decay gates in (0,1)
    b: jnp.ndarray,  # (B, S, D) gated inputs
    *,
    chunk: int = 256,
    block_d: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """Linear recurrence h_t = a_t·h_{t-1} + b_t, h_0 = b_0 (zero init)."""
    B, S, D = a.shape
    ch = min(chunk, S)
    bd = min(block_d, D)
    ns, ndb = -(-S // ch), -(-D // bd)
    ps, pd = ns * ch - S, ndb * bd - D
    if ps or pd:
        a = jnp.pad(a, ((0, 0), (0, ps), (0, pd)))
        b = jnp.pad(b, ((0, 0), (0, ps), (0, pd)))

    kernel = functools.partial(_scan_kernel, chunk=ch)
    h = pl.pallas_call(
        kernel,
        grid=(B, ndb, ns),
        in_specs=[
            pl.BlockSpec((1, ch, bd), lambda ib, idb, ic: (ib, ic, idb)),
            pl.BlockSpec((1, ch, bd), lambda ib, idb, ic: (ib, ic, idb)),
        ],
        out_specs=pl.BlockSpec((1, ch, bd), lambda ib, idb, ic: (ib, ic, idb)),
        out_shape=jax.ShapeDtypeStruct((B, ns * ch, ndb * bd), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, b)
    return h[:, :S, :D]
