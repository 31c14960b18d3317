"""Plain reference of a cell's training step: the yardstick for ``correct``.

Straightforward ``jax.numpy`` over the plain spec of ``bench/spec.py``, with
nothing of the program imported: the loss of the configuration's
architecture (``loss`` of ``bench/arch/<arch>/reference.py``, found by
``spec.arch``) and AdamW with global-norm clipping, shared by every
architecture.

Everything is float32. The reference takes every matrix product at
``highest`` precision, as the configurations state. The control
(``control=True``) is the same step with every matrix product taken in three
bfloat16 passes, the precision one below (``high``): each operand split into
a bfloat16 head and a bfloat16 tail, and the tail-by-tail product dropped.
It is spelled out here, not asked of the compiler, so that it computes the
same on any backend.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp

from . import spec as specmod

F32 = jnp.float32
BF16 = jnp.bfloat16
Dot = Callable[[str, jax.Array, jax.Array], jax.Array]


def dot(eq: str, a, b):
    """A matrix product at the precision of the surrounding context."""
    return jnp.einsum(eq, a, b)


def dot_3pass(eq: str, a, b):
    """The same product in three bfloat16 passes, accumulated in float32."""
    def split(x):
        head = x.astype(BF16)
        return head, (x - head.astype(F32)).astype(BF16)

    (ah, al), (bh, bl) = split(a), split(b)

    def one(x, y):
        return jnp.einsum(eq, x, y, preferred_element_type=F32)

    return one(ah, bh) + (one(ah, bl) + one(al, bh))


# ------------------------------------------------------------------- AdamW
def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


def adamw(opt: Dict[str, float], params, grads, mu, nu, count):
    """One AdamW step with global-norm clipping; no decay on 1-D leaves."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    count = count + 1
    c = count.astype(F32)
    bc1, bc2 = 1 - opt["b1"] ** c, 1 - opt["b2"] ** c

    def upd(p, g, m, v):
        g = g * scale
        m = m * opt["b1"] + g * (1 - opt["b1"])
        v = v * opt["b2"] + jnp.square(g) * (1 - opt["b2"])
        step = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        decay = opt["weight_decay"] if p.ndim > 1 else 0.0
        return p - opt["lr"] * (step + decay * p), m, v

    flat, treedef = jax.tree.flatten(params)
    out = [upd(*leaf) for leaf in zip(flat, *(treedef.flatten_up_to(t)
                                             for t in (grads, mu, nu)))]
    new = [treedef.unflatten([o[i] for o in out]) for i in range(3)]
    return (*new, count, scale)


@functools.lru_cache(maxsize=4)
def _step_fn(key: str, control: bool):
    """The jitted reference step for a frozen spec, or the control's."""
    spec = json.loads(key)
    opt = spec["optimizer"]
    mm = dot_3pass if control else dot
    loss = specmod.arch(spec).loss

    def step(params, mu, nu, count, batch):
        lval, grads = jax.value_and_grad(
            lambda q: loss(spec, q, batch, mm))(params)
        params, mu, nu, count, scale = adamw(opt, params, grads, mu, nu, count)
        return params, mu, nu, count, lval, leaf_norms(grads), scale

    return jax.jit(step, donate_argnums=(0, 1, 2))


def readings(spec: Dict[str, Any], params0, batches: List[Dict], steps: int = 3,
             control: bool = False) -> Dict[str, Any]:
    """Run ``steps`` AdamW steps from ``params0``, step ``i`` on
    ``batches[i % len(batches)]`` (the program's data cursor).

    Returns each step's loss, the per-leaf norms of the first gradient as
    the optimizer takes it (clipped) and before clipping, and the per-leaf
    norms of the parameters' change after the last step."""
    jstep = _step_fn(json.dumps(spec, sort_keys=True), control)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(jnp.copy, params0)
        mu = jax.tree.map(jnp.zeros_like, params0)
        nu = jax.tree.map(jnp.zeros_like, params0)
        count = jnp.zeros((), jnp.int32)
        losses = []
        for i in range(steps):
            params, mu, nu, count, lval, gn, scale = jstep(
                params, mu, nu, count, batches[i % len(batches)])
            losses.append(lval)
            if i == 0:
                g_raw = jax.device_get(gn)
                g_clipped = jax.device_get(gn * scale)
        delta = jax.device_get(_delta_norms(params, params0))
    return {"losses": [float(x) for x in losses], "grad": g_clipped,
            "grad_raw": g_raw, "delta": delta}


@jax.jit
def _delta_norms(a, b):
    return leaf_norms(jax.tree.map(jnp.subtract, a, b))
