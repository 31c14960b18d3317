"""Pallas TPU paged-attention decode kernel (one query token per row).

The serving fast path stores full-attention KV in a shared page pool
(``(n_pages, K, page_size, hd)``) indexed through per-row page tables
(:mod:`repro.serving.pages`).  This kernel computes one decode step of
attention directly against that layout — the pool is never gathered back
into a per-row slab in HBM:

  * ``PrefetchScalarGridSpec`` prefetches the page table and the per-row
    valid lengths; the K/V BlockSpec index_maps translate logical page
    ``i`` of row ``b`` to its *physical* page ``table[b, i]``, so the DMA
    engine streams exactly the pages the row owns.
  * Grid ``(B, K, n_pages_per_row)`` with the page dimension innermost and
    sequential ("arbitrary"); the online-softmax state (m, l, acc) lives in
    VMEM scratch persisted across a row's pages — the same structure as
    ``flash_attention.py``, with pages in place of KV blocks.
  * GQA-native: the ``H/K`` query heads of one KV group ride in a single
    q block ``(rep, hd)``, so each physical page is streamed once per
    group, not once per query head.
  * Logical pages that start beyond the row's valid length are skipped
    whole via ``pl.when`` (no MXU work, no DMA waste for short rows), and
    the page containing position ``len`` is masked per-position — identical
    validity semantics (``kpos <= len``) to the reference gather in
    ``repro.models.attention.attn_decode``.

Off-TPU (interpret mode) the public wrapper in ``repro.kernels.ops`` falls
back to the reference gather (:func:`repro.kernels.ref.
paged_attention_ref`); this kernel is exercised directly in interpret mode
by ``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    table_ref,  # scalar-prefetch: (B, n_pp) int32 physical page ids
    len_ref,  # scalar-prefetch: (B,) int32 per-row valid length (pos)
    q_ref,  # (1, 1, rep, hd)
    k_ref,  # (1, 1, ps, hd) — the row's i-th logical page
    v_ref,  # (1, 1, ps, hd)
    o_ref,  # (1, 1, rep, hd)
    m_scr,  # (rep,) scratch
    l_scr,  # (rep,)
    acc_scr,  # (rep, hd)
    *,
    sm_scale: float,
    ps: int,
    n_pp: int,
):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = len_ref[b]
    # whole page beyond the valid prefix (kpos <= pos)? skip the DMA'd
    # block's compute entirely — unmapped pages alias the trash page and
    # are only ever skipped here
    page_live = (i * ps) <= pos

    @pl.when(page_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (rep, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (rep, ps)
        kpos = i * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(i == n_pp - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_attention(
    q: jnp.ndarray,  # (B, H, hd) — one decode token per row
    k_pool: jnp.ndarray,  # (P, K, ps, hd) — shared physical page pool
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, n_pp) int32 physical page ids
    lengths: jnp.ndarray,  # (B,) int32: positions <= lengths[b] are valid
    *,
    sm_scale: float | None = None,
    interpret: bool,
) -> jnp.ndarray:
    """Paged decode attention over head-major layouts. Returns (B, H, hd)."""
    B, H, hd = q.shape
    P, K, ps, _ = k_pool.shape
    n_pp = page_table.shape[1]
    assert H % K == 0, "query heads must be a multiple of kv heads"
    rep = H // K
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)

    qg = q.reshape(B, K, rep, hd)
    table = page_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    kernel = functools.partial(
        _paged_kernel, sm_scale=sm_scale, ps=ps, n_pp=n_pp
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, n_pp),
        in_specs=[
            pl.BlockSpec(
                (1, 1, rep, hd), lambda b, k, i, tbl, ln: (b, k, 0, 0)
            ),
            # logical page i of row b lives at physical page tbl[b, i]
            pl.BlockSpec(
                (1, 1, ps, hd), lambda b, k, i, tbl, ln: (tbl[b, i], k, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, ps, hd), lambda b, k, i, tbl, ln: (tbl[b, i], k, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rep, hd), lambda b, k, i, tbl, ln: (b, k, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(table, lengths, qg, k_pool, v_pool)
    return out.reshape(B, H, hd)
