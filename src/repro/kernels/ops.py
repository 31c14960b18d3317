"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` (the default) follows the platform: the kernels compile
through Mosaic when JAX's default backend is a TPU and run in the Pallas
interpreter everywhere else (CPU tests).  Pass ``interpret=`` to force
either.  ``ShardingConfig.use_pallas`` gates whether the model layers call
these instead of the XLA chunked paths.
"""

from __future__ import annotations

import functools

import jax

from .flash_attention import flash_attention as _flash
from .moe_gmm import grouped_matmul as _gmm
from .paged_attention import paged_attention as _paged
from .rglru_scan import rglru_scan as _rglru


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_diff(q, k, v, causal, block_q, block_k, interpret):
    return _flash(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out = _flash_diff(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    # Backward via differentiable reference recompute (XLA). On a TPU
    # deployment the flash backward kernel would slot in here; numerics are
    # identical either way and the fwd kernel already avoids the O(S²)
    # materialization where it matters (activations under remat recompute).
    from .ref import flash_attention_ref

    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention_ref(q_, k_, v_, causal=causal),
        q, k, v,
    )
    return vjp(g)


_flash_diff.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash_diff(q, k, v, causal, block_q, block_k, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, page_table, lengths, *,
                    interpret: bool | None = None):
    """Paged decode attention: the Mosaic kernel on a TPU runtime; under
    interpret mode (off-TPU) it falls back to the reference gather —
    the exact arithmetic the serving decode path uses — instead of
    interpreting the kernel body token-by-token."""
    interpret = _default_interpret() if interpret is None else interpret
    if interpret:
        from .ref import paged_attention_ref

        return paged_attention_ref(q, k_pool, v_pool, page_table, lengths)
    return _paged(
        q, k_pool, v_pool, page_table, lengths, interpret=False
    )


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "block_d", "interpret")
)
def grouped_matmul(x, w, group_sizes=None, *, block_c: int = 128,
                   block_f: int = 128, block_d: int = 512,
                   interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _gmm(
        x, w, group_sizes, block_c=block_c, block_f=block_f, block_d=block_d,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def rglru_scan(a, b, *, chunk: int = 256, block_d: int = 512,
               interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _rglru(a, b, chunk=chunk, block_d=block_d, interpret=interpret)
