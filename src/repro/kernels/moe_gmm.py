"""Pallas TPU grouped matmul for MoE experts (megablox-style ragged skip).

Computes ``y[e] = x[e] @ w[e]`` for capacity-padded per-expert buffers
``x (E, C, d)``, ``w (E, d, f)`` → ``y (E, C, f)``, with an optional
``group_sizes (E,)`` carrying the *actual* token count per expert: row
blocks entirely beyond ``group_sizes[e]`` are skipped with ``pl.when``,
so padded capacity costs zero MXU work — the TPU adaptation of megablox's
ragged grouped matmul (a CUDA kernel would use CSR-style tiles; on TPU we
keep the dense capacity layout for layout-friendliness and skip whole
128-aligned tiles instead — DESIGN.md §3).

Grid ``(E, nc, nf, nd)`` with the contraction dim ``nd`` innermost and
sequential; the fp32 accumulator persists in VMEM scratch across it.
Block defaults (bc=128, bf=128, bd=512) keep VMEM ≈ 128·512·4 + 512·128·4 +
128·128·4 ≈ 0.6 MB and every matmul MXU-shaped.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(
    sizes_ref,  # (E,) int32 in SMEM-like memory (full array)
    x_ref,  # (1, bc, bd)
    w_ref,  # (1, bd, bf)
    y_ref,  # (1, bc, bf)
    acc_scr,  # (bc, bf) fp32
    *,
    bc: int,
    nd: int,
):
    e = pl.program_id(0)
    ic = pl.program_id(1)
    id_ = pl.program_id(3)

    row_start = ic * bc
    live = row_start < sizes_ref[e]

    @pl.when(id_ == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _compute():
        acc_scr[...] += jax.lax.dot_general(
            x_ref[0].astype(jnp.float32),
            w_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(id_ == nd - 1)
    def _finalize():
        # zero rows beyond the ragged group size (partially-live blocks)
        rows = row_start + jax.lax.broadcasted_iota(
            jnp.int32, acc_scr.shape, 0
        )
        acc = jnp.where(rows < sizes_ref[e], acc_scr[...], 0.0)
        y_ref[0] = acc.astype(y_ref.dtype)


def grouped_matmul(
    x: jnp.ndarray,  # (E, C, d)
    w: jnp.ndarray,  # (E, d, f)
    group_sizes: Optional[jnp.ndarray] = None,  # (E,) int32; None = all full
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    E, C, d = x.shape
    _, _, f = w.shape
    if group_sizes is None:
        group_sizes = jnp.full((E,), C, jnp.int32)

    bc = min(block_c, C)
    bf = min(block_f, f)
    bd = min(block_d, d)
    nc, nf, nd = -(-C // bc), -(-f // bf), -(-d // bd)
    pc, pf, pd = nc * bc - C, nf * bf - f, nd * bd - d
    if pc or pd:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, pd)))
    if pd or pf:
        w = jnp.pad(w, ((0, 0), (0, pd), (0, pf)))

    kernel = functools.partial(_gmm_kernel, bc=bc, nd=nd)
    y = pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # group sizes, whole array
            pl.BlockSpec((1, bc, bd), lambda e, ic, if_, id_: (e, ic, id_)),
            pl.BlockSpec((1, bd, bf), lambda e, ic, if_, id_: (e, id_, if_)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, ic, if_, id_: (e, ic, if_)),
        out_shape=jax.ShapeDtypeStruct((E, nc * bc, nf * bf), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(group_sizes.astype(jnp.int32), x, w)
    return y[:, :C, :f]
