"""Attention: GQA with RoPE / qk-norm, chunked-flash XLA path, KV-cache decode.

Three execution paths, all numerically interchangeable (tested):

  * ``naive``   — materializes (.., S, S) scores; reference for small shapes.
  * ``chunked`` — flash-style online-softmax over KV chunks inside
    ``lax.scan`` (and over Q chunks), O(S) memory in XLA; the path the
    dry-run lowers (DESIGN.md §3: on-TPU runs swap in the Pallas kernel).
  * ``local``   — sliding-window attention (recurrentgemma), O(S·W).

Decode consumes a KV cache laid out (B, K, S, hd); for long caches the
sequence dim is sharded over the model axis (flash-decode style split-KV —
XLA inserts the partial-softmax collectives).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import apply_rope, dense_init, rms_normalize

NEG_INF = -1e30


def attn_init(rng, d: int, n_heads: int, n_kv: int, head_dim: int, dtype):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    return {
        "wq": dense_init(k1, d, n_heads * head_dim, dtype),
        "wk": dense_init(k2, d, n_kv * head_dim, dtype),
        "wv": dense_init(k3, d, n_kv * head_dim, dtype),
        "wo": dense_init(k4, n_heads * head_dim, d, dtype),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _repeat_kv(k, n_heads):
    """(B, S, K, hd) -> (B, S, H, hd) by repeating each kv head H/K times."""
    B, S, K, hd = k.shape
    if K == n_heads:
        return k
    rep = n_heads // K
    return jnp.repeat(k, rep, axis=2)


# ---------------------------------------------------------------------------
# Full (naive) attention — reference
# ---------------------------------------------------------------------------


def naive_attention(q, k, v, *, causal: bool, window: int = 0, q_offset: int = 0):
    """q (B,Sq,H,hd), k/v (B,Sk,H,hd) already head-repeated. Returns (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


# ---------------------------------------------------------------------------
# Chunked flash-style attention (XLA path)
# ---------------------------------------------------------------------------


def chunked_attention(
    q,
    k,
    v,
    *,
    causal: bool,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    q_offset: int = 0,
    skip_masked_blocks: bool = True,
):
    """Online-softmax attention, O(Sk·chunk) memory.

    Scans over Q chunks (outer) and KV chunks (inner), keeping running
    (max, denominator, accumulator).  With ``skip_masked_blocks`` and
    ``causal``, KV blocks strictly above the diagonal are skipped with
    ``lax.cond`` so compiled FLOPs stay ≈ the causal half (a §Perf item).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // kv_chunk)
    pad_q = nq * q_chunk - Sq
    pad_k = nk * kv_chunk - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(hd)

    qs = q.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)  # (nq,B,c,H,hd)
    ks = k.reshape(B, nk, kv_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kv_chunk, H, hd).transpose(1, 0, 2, 3, 4)

    kpos_base = jnp.arange(kv_chunk)
    qpos_base = jnp.arange(q_chunk)

    def q_body(_, qc_i):
        qc, iq = qc_i  # (B,c,H,hd), scalar chunk index
        qpos = qpos_base + iq * q_chunk + q_offset

        def kv_body(carry, kc_i):
            m, l, acc = carry
            kc, vc, ik = kc_i
            kpos = kpos_base + ik * kv_chunk

            def compute(m, l, acc):
                s = (
                    jnp.einsum("bqhd,bkhd->bhqk", qc, kc).astype(jnp.float32)
                    * scale
                )
                mask = jnp.ones((q_chunk, kv_chunk), dtype=bool)
                if causal:
                    mask &= kpos[None, :] <= qpos[:, None]
                if pad_k:
                    mask &= (kpos[None, :] < Sk)
                s = jnp.where(mask[None, None], s, NEG_INF)
                # the running max only keeps exp() in range and the output
                # does not depend on it, so it takes no gradient.  A max's
                # gradient divides by its tie count, which is zero (NaN)
                # when the TPU recomputes s under remat with other rounding.
                m_new = jax.lax.stop_gradient(
                    jnp.maximum(m, jnp.max(s, axis=-1))
                )
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + jnp.sum(p, axis=-1)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "bhqk,bkhd->bhqd", p, vc.astype(jnp.float32)
                )
                return m_new, l_new, acc_new

            if causal and skip_masked_blocks:
                # Entire block above the diagonal? skip (saves ~half the FLOPs)
                block_live = (ik * kv_chunk) <= (iq * q_chunk + q_chunk - 1 + q_offset)
                m, l, acc = jax.lax.cond(
                    block_live, compute, lambda m, l, a: (m, l, a), m, l, acc
                )
            else:
                m, l, acc = compute(m, l, acc)
            return (m, l, acc), None

        m0 = jnp.full((B, H, q_chunk), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), dtype=jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, hd), dtype=jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0), (ks, vs, jnp.arange(nk))
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,c,H,hd)

    _, outs = jax.lax.scan(q_body, None, (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_chunk, H, hd)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# Sliding-window (local) attention — O(S·W)
# ---------------------------------------------------------------------------


def local_attention(q, k, v, *, window: int, q_chunk: int = 512, q_offset: int = 0):
    """Causal attention restricted to the last ``window`` positions.

    Scans Q chunks; each attends a (window + chunk)-wide KV slice obtained by
    dynamic slicing — total work O(S·(W+c)) instead of O(S²).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    nq = -(-Sq // q_chunk)
    pad_q = nq * q_chunk - Sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    span = window + q_chunk  # kv positions visible to one q chunk
    # pad K/V on the left by `window` so the slice start is never negative
    kpad = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vpad = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))

    qs = q.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)

    def q_body(_, qc_i):
        qc, iq = qc_i
        start = iq * q_chunk  # in padded coords the window base
        kc = jax.lax.dynamic_slice_in_dim(kpad, start, span, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vpad, start, span, axis=1)
        qpos = jnp.arange(q_chunk) + iq * q_chunk + q_offset
        kpos = jnp.arange(span) + iq * q_chunk - window  # absolute positions
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc).astype(jnp.float32) / math.sqrt(hd)
        mask = (kpos[None, :] <= qpos[:, None]) & (
            kpos[None, :] > qpos[:, None] - window
        ) & (kpos[None, :] >= 0) & (kpos[None, :] < Sk)
        s = jnp.where(mask[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vc.dtype), vc)
        return None, out

    _, outs = jax.lax.scan(q_body, None, (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_chunk, H, hd)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# Block-level apply (projections + rope + attention + out-proj)
# ---------------------------------------------------------------------------


def attn_apply(
    params,
    x,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float,
    causal: bool = True,
    qk_norm: bool = False,
    window: int = 0,
    positions: Optional[jnp.ndarray] = None,
    impl: str = "chunked",
    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    return_kv: bool = False,
):
    """Full attention block on (B, S, d). Optionally returns (k, v) for caches.

    ``kv_override`` supplies externally-computed K/V (cross-attention)."""
    B, S, d = x.shape
    q = _split_heads(x @ params["wq"], n_heads, head_dim)
    if kv_override is None:
        k = _split_heads(x @ params["wk"], n_kv, head_dim)
        v = _split_heads(x @ params["wv"], n_kv, head_dim)
        if qk_norm:
            q, k = rms_normalize(q), rms_normalize(k)
        pos = positions if positions is not None else jnp.arange(S)[None, :]
        if rope_theta > 0:
            q = apply_rope(q, pos, rope_theta)
            k = apply_rope(k, pos, rope_theta)
    else:
        k, v = kv_override
        if qk_norm:
            q = rms_normalize(q)
        pos = positions if positions is not None else jnp.arange(S)[None, :]
        if rope_theta > 0:
            q = apply_rope(q, pos, rope_theta)
    kv = (k, v)
    if impl == "pallas" and window == 0 and S > 256:
        # Pallas flash kernel: head-major layout, GQA-native (no KV repeat)
        from ..kernels import flash_attention as _flash

        out = _flash(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=causal,
        ).transpose(0, 2, 1, 3)
    else:
        kfull = _repeat_kv(k, n_heads)
        vfull = _repeat_kv(v, n_heads)
        if impl == "naive" or S <= 256:
            out = naive_attention(q, kfull, vfull, causal=causal, window=window)
        elif window > 0:
            out = local_attention(q, kfull, vfull, window=window)
        else:
            out = chunked_attention(q, kfull, vfull, causal=causal)
    y = out.reshape(B, S, n_heads * head_dim) @ params["wo"]
    if return_kv:
        return y, kv
    return y


def paged_gather(pool, page_table):
    """Per-row contiguous KV view of a paged pool.

    pool (P, K, page_size, hd) + page_table (B, n_pages) → (B, K, S, hd)
    with ``S = n_pages * page_size`` and logical position ``p`` at index
    ``p`` — the exact slab layout, so everything downstream of the gather
    (repeat, scoring, masking) is the UNCHANGED slab code and paged decode
    stays token-identical to slab decode.  The transpose is the gather's
    relayout cost; the Pallas kernel path avoids it entirely on TPU (the
    page indirection happens in the BlockSpec index_map)."""
    B, n_pp = page_table.shape
    _, K, ps, hd = pool.shape
    g = jnp.take(pool, page_table, axis=0)  # (B, n_pp, K, ps, hd)
    return g.transpose(0, 2, 1, 3, 4).reshape(B, K, n_pp * ps, hd)


def paged_scatter(pool, page_table, pos_b, vals):
    """Write one token's K or V per row into a paged pool.

    vals (B, K, hd) lands at each row's logical position ``pos_b`` through
    its page table.  Rows whose logical page is unmapped write through
    page-table entry 0 — the reserved trash page — which is how inactive
    batch rows ride along in the fixed-shape decode step without touching
    live pages."""
    ps = pool.shape[2]
    pg = jnp.take_along_axis(page_table, (pos_b // ps)[:, None], axis=1)[:, 0]
    off = pos_b % ps
    return pool.at[pg, :, off, :].set(vals.astype(pool.dtype))


def attn_decode(
    params,
    x,
    cache_k,
    cache_v,
    pos,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float,
    qk_norm: bool = False,
    window: int = 0,
    cross: bool = False,
    cross_len: Optional[jnp.ndarray] = None,
    page_table: Optional[jnp.ndarray] = None,
    impl: str = "ref",
):
    """One-token decode. x (B,1,d); cache_k/v (B, K, S, hd); pos is a scalar
    int or an (B,) int vector of **per-row** positions (continuous batching:
    each batch slot serves a different request at its own depth).

    Returns (y, new_cache_k, new_cache_v).  For ``window>0`` the cache is a
    circular buffer of size ``window``.  ``cross=True`` treats the cache as a
    fixed encoder memory (no update; valid length ``cross_len``).

    ``page_table`` switches to the **paged** layout: cache_k/v are shared
    pools (n_pages, K, page_size, hd) and ``page_table`` (B, pages_per_row)
    maps each row's logical pages to physical ones.  The new token's K/V is
    scattered through the table, the row's pages are gathered back into the
    slab layout, and scoring/masking below is byte-for-byte the slab code —
    the reference gather path the Pallas paged-decode kernel falls back to
    under interpret mode."""
    B = x.shape[0]
    paged = page_table is not None
    if paged and (cross or window > 0):
        raise ValueError("paged KV applies to full causal self-attention only")
    S = page_table.shape[1] * cache_k.shape[2] if paged else cache_k.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = pos if pos.ndim else jnp.full((B,), pos)  # (B,) per-row positions
    q = _split_heads(x @ params["wq"], n_heads, head_dim)  # (B,1,H,hd)
    if qk_norm:
        q = rms_normalize(q)
    if rope_theta > 0 and not cross:
        q = apply_rope(q, pos_b[:, None], rope_theta)

    if not cross:
        k = _split_heads(x @ params["wk"], n_kv, head_dim)
        v = _split_heads(x @ params["wv"], n_kv, head_dim)
        if qk_norm:
            k = rms_normalize(k)
        if rope_theta > 0:
            k = apply_rope(k, pos_b[:, None], rope_theta)
        if paged:
            cache_k = paged_scatter(cache_k, page_table, pos_b, k[:, 0])
            cache_v = paged_scatter(cache_v, page_table, pos_b, v[:, 0])
        else:
            slot = pos_b % window if window > 0 else pos_b
            # cache layout (B, K, S, hd); per-row scatter at each row's slot
            def _row_update(c, u, s_):
                return jax.lax.dynamic_update_slice_in_dim(c, u, s_, axis=1)

            cache_k = jax.vmap(_row_update)(
                cache_k, k.transpose(0, 2, 1, 3).astype(cache_k.dtype), slot
            )
            cache_v = jax.vmap(_row_update)(
                cache_v, v.transpose(0, 2, 1, 3).astype(cache_v.dtype), slot
            )

    if paged and impl == "pallas":
        # Mosaic paged-decode kernel on TPU; the ops wrapper falls back to
        # the reference gather below under interpret mode
        from ..kernels import paged_attention as _paged_attn

        ctx = _paged_attn(q[:, 0], cache_k, cache_v, page_table, pos_b)
        y = ctx.reshape(B, 1, n_heads * head_dim) @ params["wo"]
        return y, cache_k, cache_v

    # scores over the full cache with per-row validity masking; the paged
    # layout funnels through the gather into the IDENTICAL slab arithmetic
    view_k = paged_gather(cache_k, page_table) if paged else cache_k
    view_v = paged_gather(cache_v, page_table) if paged else cache_v
    rep = n_heads // view_k.shape[1]
    kk = jnp.repeat(view_k, rep, axis=1) if rep > 1 else view_k  # (B,H,S,hd)
    vv = jnp.repeat(view_v, rep, axis=1) if rep > 1 else view_v
    s = jnp.einsum("bqhd,bhkd->bhqk", q, kk).astype(jnp.float32)
    s = s / math.sqrt(head_dim)
    kpos = jnp.arange(S)
    if cross:
        valid = jnp.broadcast_to(
            kpos[None, :]
            < (cross_len if cross_len is not None else jnp.asarray(S)),
            (B, S),
        )
    elif window > 0:
        # circular buffer: slots hold the last min(pos+1, window) tokens
        valid = kpos[None, :] < jnp.minimum(pos_b + 1, window)[:, None]
    else:
        valid = kpos[None, :] <= pos_b[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bqhd", p.astype(vv.dtype), vv)
    y = out.reshape(B, 1, n_heads * head_dim) @ params["wo"]
    return y, cache_k, cache_v


def attn_prefill_chunk(
    params,
    x,
    pool_k,
    pool_v,
    page_table,
    pos0: int,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float,
    qk_norm: bool = False,
):
    """One prefill chunk against a paged cache (DIP-style chunked prefill).

    x (B, C, d) holds the chunk's embeddings for positions
    ``pos0 .. pos0+C-1`` (the same static ``pos0`` for every row — chunked
    admission groups requests by prompt length, so a group advances in
    lockstep).  The chunk's K/V is scattered into the paged pools, then the
    FULL prefix ``[0, pos0+C)`` is gathered back and attended with the same
    :func:`naive_attention` arithmetic the one-shot prefill path uses — so
    with a lossless cache dtype the last chunk's outputs are bit-identical
    to a one-shot prefill (pinned in tests/test_serving.py).

    Returns (y (B, C, d), pool_k, pool_v)."""
    B, C, _ = x.shape
    ps = pool_k.shape[2]
    seen = pos0 + C  # prefix length after this chunk
    q = _split_heads(x @ params["wq"], n_heads, head_dim)  # (B,C,H,hd)
    k = _split_heads(x @ params["wk"], n_kv, head_dim)
    v = _split_heads(x @ params["wv"], n_kv, head_dim)
    if qk_norm:
        q, k = rms_normalize(q), rms_normalize(k)
    pos = (pos0 + jnp.arange(C))[None, :]
    if rope_theta > 0:
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)

    # scatter the chunk through the page tables (all C positions at once)
    pg = jnp.take(page_table, pos[0] // ps, axis=1)  # (B, C)
    off = jnp.broadcast_to(pos[0] % ps, (B, C))
    pool_k = pool_k.at[pg, :, off, :].set(k.astype(pool_k.dtype))
    pool_v = pool_v.at[pg, :, off, :].set(v.astype(pool_v.dtype))

    # gather the prefix (past chunks + this one) back into the slab layout
    n_need = -(-seen // ps)
    kf = paged_gather(pool_k, page_table[:, :n_need])[:, :, :seen]
    vf = paged_gather(pool_v, page_table[:, :n_need])[:, :, :seen]
    kf = _repeat_kv(kf.transpose(0, 2, 1, 3).astype(x.dtype), n_heads)
    vf = _repeat_kv(vf.transpose(0, 2, 1, 3).astype(x.dtype), n_heads)
    out = naive_attention(q, kf, vf, causal=True, q_offset=pos0)
    y = out.reshape(B, C, n_heads * head_dim) @ params["wo"]
    return y, pool_k, pool_v
