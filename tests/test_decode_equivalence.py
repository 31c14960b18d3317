"""Decode ≡ parallel forward, and prefill → decode handoff (all families)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.config import get_arch, reduced
from repro.models import build_model

# capacity_factor pushed high so MoE never drops tokens (capacity dropping
# legitimately differs between prefill and one-token decode batches)
ARCHS = [
    "qwen3-0.6b",
    "glm4-9b",
    "qwen2-moe-a2.7b",
    "qwen3-moe-30b-a3b",
    "xlstm-125m",
    "recurrentgemma-9b",
    "pixtral-12b",
]


def _model(arch):
    cfg = reduced(get_arch(arch))
    if cfg.is_moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
        )
    return build_model(cfg)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch):
    model = _model(arch)
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(0))
    B, T = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    tr = model.impl
    h, _, _ = tr.forward(params, toks)
    ref = (h @ tr.head(params).astype(h.dtype)).astype(jnp.float32)
    cache = model.init_cache(B, T, cache_dtype=jnp.float32)
    for t in range(T):
        lg, cache = model.decode_step(params, toks[:, t], cache, t)
        err = float(jnp.max(jnp.abs(lg - ref[:, t])))
        assert err < 5e-4, f"{arch}: decode diverges at t={t}: {err}"


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "xlstm-125m",
                                  "recurrentgemma-9b"])
def test_prefill_handoff(arch):
    model = _model(arch)
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(0))
    B, T, P = 2, 12, 7
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    tr = model.impl
    h, _, _ = tr.forward(params, toks)
    ref = (h @ tr.head(params).astype(h.dtype)).astype(jnp.float32)
    lg, cache = tr.prefill(params, toks[:, :P], cache_len=T,
                           cache_dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(lg - ref[:, P - 1]))) < 5e-4
    for t in range(P, T):
        lg, cache = tr.decode_step(params, toks[:, t], cache, t)
        assert float(jnp.max(jnp.abs(lg - ref[:, t]))) < 5e-4


def test_encdec_decode_matches_forward():
    model = _model("seamless-m4t-medium")
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(0))
    B, T, E, P = 2, 10, 6, 5
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    frames = jax.random.normal(jax.random.PRNGKey(2), (B, E, cfg.d_model))
    mem = model.impl.encode(params, frames)
    h, _ = model.impl.decode_forward(params, toks, mem)
    ref = (h @ params["lm_head"].astype(h.dtype)).astype(jnp.float32)
    lg, cache = model.impl.prefill(params, toks[:, :P], frames, cache_len=T,
                                   cache_dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(lg - ref[:, P - 1]))) < 5e-4
    for t in range(P, T):
        lg, cache = model.impl.decode_step(params, toks[:, t], cache, t)
        assert float(jnp.max(jnp.abs(lg - ref[:, t]))) < 5e-4


def test_attention_impls_agree():
    """naive / chunked / flash-kernel paths agree on the same inputs."""
    from repro.models.attention import chunked_attention, naive_attention
    from repro.kernels import flash_attention as flash_ops

    B, H, S, hd = 2, 4, 96, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, hd))
    a_naive = naive_attention(q, k, v, causal=True)
    a_chunk = chunked_attention(q, k, v, causal=True, q_chunk=32, kv_chunk=32)
    assert float(jnp.max(jnp.abs(a_naive - a_chunk))) < 2e-5
    # kernel uses head-major layout
    qm, km, vm = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    a_flash = flash_ops(qm, km, vm, causal=True, block_q=32, block_k=32)
    assert float(jnp.max(jnp.abs(a_flash.transpose(0, 2, 1, 3) - a_naive))) < 2e-5


def test_chunked_attention_gradient_skips_the_running_max():
    """The chunked path's gradient equals the naive one and never flows
    through the online-softmax max: a max's gradient divides by how many
    scores tie with it, and a TPU recompute under remat can round the
    scores so that none does (NaN)."""
    from repro.models.attention import chunked_attention, naive_attention

    B, H, S, hd = 2, 4, 96, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd)) for kk in ks[:3])
    g = jax.random.normal(ks[3], (B, S, H, hd))

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True, **kw) * g)

    chunk = jax.jit(jax.grad(loss(chunked_attention, q_chunk=32, kv_chunk=32),
                             argnums=(0, 1, 2)))
    want = jax.grad(loss(naive_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(chunk(q, k, v), want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
    # no score block is compared for equality with its max in the backward
    block = f"tensor<{B}x{H}x32x32xf32>"
    ties = [line for line in chunk.lower(q, k, v).as_text().splitlines()
            if "compare  EQ" in line and block in line]
    assert not ties, ties
