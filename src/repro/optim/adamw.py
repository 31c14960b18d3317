"""AdamW with dtype policy, global-norm clipping and decoupled weight decay.

Moments are kept in ``moment_dtype`` (fp32 default; bf16 for the 405B-class
archs so a pod fits — the ArchConfig.opt_dtype knob).  The update math runs
in fp32 regardless; moments are cast on store.  ``update`` is pure and jit-
friendly; state is a plain pytree so the checkpoint layer needs no special
casing.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


@dataclass(frozen=True)
class AdamW:
    lr: Callable | float = 3e-4  # float or schedule(step) -> lr
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0  # global-norm clip; 0 disables
    moment_dtype: Any = jnp.float32

    # ------------------------------------------------------------------
    def init(self, params) -> "OptState":
        def zeros(p):
            return jnp.zeros(p.shape, self.moment_dtype)

        return OptState(
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params),
            count=jnp.zeros((), jnp.int32),
        )

    def _lr(self, step):
        return self.lr(step) if callable(self.lr) else jnp.asarray(self.lr)

    def update(self, grads, state: "OptState", params) -> Tuple[Any, "OptState"]:
        """Returns (new_params, new_state).

        Memory note: the clip scale is computed from a per-leaf fused
        norm reduction and applied INSIDE each leaf's update — the fp32
        gradient tree is never materialized (a whole-tree fp32 cast put a
        2×|params| transient on the 405B cell's HBM peak —
        EXPERIMENTS.md §Perf cell 2)."""
        count = state.count + 1
        scale = jnp.asarray(1.0, jnp.float32)
        if self.grad_clip > 0:
            with TraceAnnotation("spindle.optim.clip"):
                gnorm = global_norm(grads)  # scalar; per-leaf fused reductions
                scale = jnp.minimum(1.0, self.grad_clip / (gnorm + 1e-9))

        b1, b2 = self.b1, self.b2
        c = count.astype(jnp.float32)
        bc1 = 1 - b1**c
        bc2 = 1 - b2**c
        lr = self._lr(count)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32) * scale
            m32 = m.astype(jnp.float32) * b1 + g * (1 - b1)
            v32 = v.astype(jnp.float32) * b2 + jnp.square(g) * (1 - b2)
            step = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + self.eps)
            p32 = p.astype(jnp.float32)
            p_new = p32 - lr * (step + self.weight_decay * _decay_mask(p) * p32)
            return p_new.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype)

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        return new_p, OptState(mu=new_m, nu=new_v, count=count)


def _decay_mask(p) -> float:
    """No weight decay on 1-D params (norms/biases/gates)."""
    return 0.0 if p.ndim <= 1 else 1.0


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    )


@jax.tree_util.register_pytree_node_class
class OptState:
    """Plain pytree optimizer state (mu, nu, count)."""

    def __init__(self, mu, nu, count):
        self.mu, self.nu, self.count = mu, nu, count

    def tree_flatten(self):
        return (self.mu, self.nu, self.count), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"OptState(count={self.count})"


def adamw(**kw) -> AdamW:
    return AdamW(**kw)
