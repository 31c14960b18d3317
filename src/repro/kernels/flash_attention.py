"""Pallas TPU flash attention (causal, GQA-native).

TPU-native design (not a CUDA port — DESIGN.md §3):

  * Grid ``(B, H, nq, nk)`` with the KV dimension innermost and sequential
    ("arbitrary"); the running online-softmax state (m, l, acc) lives in
    VMEM scratch that persists across the nk iterations of one (b,h,iq)
    cell — the TPU analogue of a CUDA thread-block's shared-memory loop.
  * Blocks are MXU-aligned: q/kv block sizes default to 512/512 with
    head_dim padded to a multiple of 128 by the wrapper; the two matmuls
    per block (``q·kᵀ`` and ``p·v``) each feed the 128×128 systolic array.
  * GQA without materializing repeated KV: the k/v BlockSpec index_map
    divides the head index by the group size, so all ``H/K`` query heads of
    one group stream the *same* KV block from HBM — a bandwidth saving a
    repeat-then-attend implementation doesn't get.
  * Causality skips whole blocks above the diagonal via ``pl.when``
    (no wasted MXU work), and masks the diagonal blocks only.

VMEM budget at the default blocks (bq=bk=512, hd=128, fp32 acc):
q 256 KB + k/v 512 KB + acc 256 KB + m/l 4 KB ≈ 1 MB — comfortably inside
the ~16 MB/core v5e VMEM, leaving room for double buffering.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, 1, bq, hd)
    k_ref,  # (1, 1, bk, hd)
    v_ref,  # (1, 1, bk, hd)
    o_ref,  # (1, 1, bq, hd)
    m_scr,  # (bq,) scratch
    l_scr,  # (bq,)
    acc_scr,  # (bq, hd)
    *,
    causal: bool,
    sm_scale: float,
    bq: int,
    bk: int,
    nk: int,
    seq_k: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    # Entire block strictly above the causal diagonal? Skip the MXU work.
    block_live = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_k  # KV padding
        if causal:
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # (B, H, Sq, hd)
    k: jnp.ndarray,  # (B, K, Sk, hd)  — K divides H (GQA)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    sm_scale: float | None = None,
    interpret: bool,
) -> jnp.ndarray:
    """Flash attention over head-major layouts. Returns (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    _, K, Sk, _ = k.shape
    assert H % K == 0, "query heads must be a multiple of kv heads"
    rep = H // K
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    pad_q = nq * bq - Sq
    pad_k = nk * bk - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        sm_scale=sm_scale,
        bq=bq,
        bk=bk,
        nk=nk,
        seq_k=Sk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, bk, hd), lambda b, h, iq, ik, rep=rep: (b, h // rep, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, hd), lambda b, h, iq, ik, rep=rep: (b, h // rep, ik, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, hd), q.dtype),
        scratch_shapes=[
            # VMEM scratch: running max / denominator / accumulator,
            # persisted across the (sequential) nk grid dimension.
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
