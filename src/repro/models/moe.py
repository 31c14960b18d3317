"""Mixture-of-Experts FFN with expert parallelism (Qwen-MoE family).

Routing: softmax top-k with capacity-bounded scatter dispatch (no (T,E,C)
one-hot einsum — dispatch goes through a position-in-expert scatter, so peak
memory is O(E·C·d) per shard, not O(T·E·C)).

Parallelization: experts shard over the ``model`` axis.  Under a mesh, the
layer runs inside ``shard_map``: activations are replicated across the model
axis (they are sharded over data only), each model shard routes the local
tokens, dispatches to *its* experts, applies them, combines, and a ``psum``
over the model axis merges the partial outputs — the TPU-native analogue of
all-to-all EP for replicated-activation layouts (DESIGN.md §3).

Shared experts (qwen2-moe) run as a dense SwiGLU on every token.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import ArchConfig
from .layers import dense_init, mlp_apply, mlp_init


def moe_init(rng, cfg: ArchConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    E = m.n_physical  # padded (dead) experts are zero-init, never routed
    ks = jax.random.split(rng, 5)

    def pad_dead(w):
        if E == m.n_experts:
            return w
        return jnp.concatenate(
            [w, jnp.zeros((E - m.n_experts,) + w.shape[1:], w.dtype)], axis=0
        )

    params = {
        "router": dense_init(ks[0], d, m.n_experts, jnp.float32),
        "we_gate": pad_dead(_expert_init(ks[1], m.n_experts, d, m.d_ff_expert, dtype)),
        "we_up": pad_dead(_expert_init(ks[2], m.n_experts, d, m.d_ff_expert, dtype)),
        "we_down": pad_dead(_expert_init(ks[3], m.n_experts, m.d_ff_expert, d, dtype)),
    }
    if m.n_shared_experts > 0:
        params["shared"] = mlp_init(
            ks[4], d, m.d_ff_expert * m.n_shared_experts, dtype
        )
    return params


def _expert_init(rng, e, d_in, d_out, dtype):
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(rng, (e, d_in, d_out)) * scale).astype(dtype)


# ---------------------------------------------------------------------------


def _route(router_w, x2d, n_experts: int, top_k: int):
    """Returns (gates (T,k), idx (T,k), aux_loss scalar)."""
    logits = (x2d.astype(jnp.float32)) @ router_w  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)  # (E,)
    assign = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32).sum(1)  # (T,E)
    ce = jnp.mean(assign, axis=0)
    aux = n_experts * jnp.sum(me * ce)
    return gates, idx, aux


def _dispatch_compute_combine(
    x2d, gates, idx, we_gate, we_up, we_down, capacity: int, e_base: int
):
    """Capacity-bounded scatter dispatch for the experts [e_base, e_base+E_loc).

    x2d (T,d); returns (T,d) partial output covering only the local experts.
    """
    T, d = x2d.shape
    E_loc = we_gate.shape[0]
    k = idx.shape[1]
    local = idx - e_base  # (T,k) in [0, E_loc) if owned here
    owned = (local >= 0) & (local < E_loc)
    local = jnp.where(owned, local, 0)
    # position of each assignment within its expert: cumsum over flattened (T*k)
    onehot = jax.nn.one_hot(local, E_loc, dtype=jnp.int32) * owned[..., None]
    flat = onehot.reshape(T * k, E_loc)
    pos_flat = jnp.cumsum(flat, axis=0) - flat  # exclusive prefix count
    pos = jnp.sum(pos_flat.reshape(T, k, E_loc) * onehot, axis=-1)  # (T,k)
    keep = owned & (pos < capacity)
    # scatter tokens into (E_loc, C, d)
    e_idx = jnp.where(keep, local, E_loc)  # overflow bucket
    p_idx = jnp.where(keep, pos, 0)
    buf = jnp.zeros((E_loc + 1, capacity, d), dtype=x2d.dtype)
    tok = jnp.broadcast_to(x2d[:, None, :], (T, k, d))
    buf = buf.at[e_idx.reshape(-1), p_idx.reshape(-1)].set(
        tok.reshape(T * k, d), mode="drop"
    )
    h = buf[:E_loc]  # (E_loc, C, d)
    # expert SwiGLU
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, we_gate))
    u = jnp.einsum("ecd,edf->ecf", h, we_up)
    y = jnp.einsum("ecf,efd->ecd", g * u, we_down)  # (E_loc, C, d)
    # combine: gather back and weight
    out_tok = y[e_idx.reshape(-1), p_idx.reshape(-1)]  # (T*k, d)
    out_tok = out_tok * (gates.reshape(-1, 1) * keep.reshape(-1, 1)).astype(y.dtype)
    return jnp.sum(out_tok.reshape(T, k, d), axis=1)


def moe_apply(
    params,
    x,
    cfg: ArchConfig,
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    model_axis: str = "model",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN on (B, S, d). Returns (out, aux_loss)."""
    m = cfg.moe
    B, S, d = x.shape

    def local_fn(x3d, router_w, wg, wu, wd, e_base_arr):
        x2d = x3d.reshape(-1, d)
        gates, idx, aux = _route(router_w, x2d, m.n_experts, m.top_k)
        t_shard = x2d.shape[0]
        cap_l = max(int(t_shard * m.top_k / m.n_experts * m.capacity_factor), m.top_k)
        out = _dispatch_compute_combine(
            x2d, gates, idx, wg, wu, wd, cap_l, e_base_arr[0]
        )
        return out.reshape(x3d.shape), aux

    if mesh is not None and model_axis in mesh.axis_names and (
        mesh.devices.shape[mesh.axis_names.index(model_axis)] > 1
        and m.n_physical % mesh.devices.shape[mesh.axis_names.index(model_axis)] == 0
    ):
        from jax.sharding import PartitionSpec as P

        ax = mesh.axis_names.index(model_axis)
        n_shards = mesh.devices.shape[ax]
        e_loc = m.n_physical // n_shards
        e_base = jnp.arange(n_shards, dtype=jnp.int32) * e_loc  # (shards,)
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

        def shmap_fn(x3d, router_w, wg, wu, wd, e_base_arr):
            out, aux = local_fn(x3d, router_w, wg, wu, wd, e_base_arr)
            out = jax.lax.psum(out, model_axis)
            aux = jax.lax.pmean(aux, model_axis)
            if batch_axes:
                aux = jax.lax.pmean(aux, batch_axes)
            return out, aux

        out, aux = jax.shard_map(
            shmap_fn,
            mesh=mesh,
            in_specs=(
                P(batch_axes or None, None, None),
                P(),  # router replicated
                P(model_axis, None, None),
                P(model_axis, None, None),
                P(model_axis, None, None),
                P(model_axis),
            ),
            out_specs=(P(batch_axes or None, None, None), P()),
        )(x, params["router"], params["we_gate"], params["we_up"], params["we_down"], e_base)
    else:
        out, aux = local_fn(
            x,
            params["router"],
            params["we_gate"],
            params["we_up"],
            params["we_down"],
            jnp.zeros((1,), jnp.int32),
        )

    if "shared" in params:
        out = out + mlp_apply(params["shared"], x)
    return out, aux
