"""Plain reference of a cell's training step: the yardstick for ``correct``.

Straightforward ``jax.numpy`` over the plain spec of ``bench/spec.py``, with
nothing of the program imported: the layer (RMSNorm, attention with RoPE,
SwiGLU), the two joins (a symmetric InfoNCE loss over pooled tower outputs; a
prefix-conditioned causal decoder with its LM loss, over the union batch when
the decoder is merged), the mean over the task losses, and AdamW with
global-norm clipping. The semantics follow what the configuration files
describe, so a change to the program's layers cannot move both sides of the
comparison.

Everything is float32. The reference takes every matrix product at
``highest`` precision, as the configurations state. The control
(``control=True``) is the same step with every matrix product taken in three
bfloat16 passes, the precision one below (``high``): each operand split into
a bfloat16 head and a bfloat16 tail, and the tail-by-tail product dropped.
It is spelled out here, not asked of the compiler, so that it computes the
same on any backend.

Layers run as one ``lax.scan`` per component under ``jax.checkpoint``, so the
step keeps one layer's activations at a time and fits beside the state.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp

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


def rmsnorm(x, scale, eps: float = 1e-6):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(x.dtype)


def rope(x, theta: float = 1e4):
    """x: (B, S, H, hd); rotate the two halves of each head by position."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


LIN = "...i,ij->...j"


def attention(p, x, n_heads: int, causal: bool, mm: Dot = dot):
    B, S, d = x.shape
    hd = d // n_heads
    q = rope(mm(LIN, x, p["wq"]).reshape(B, S, n_heads, hd))
    k = rope(mm(LIN, x, p["wk"]).reshape(B, S, n_heads, hd))
    v = mm(LIN, x, p["wv"]).reshape(B, S, n_heads, hd)
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", probs, v).reshape(B, S, d)
    return mm(LIN, o, p["wo"])


def layer(lp, h, n_heads: int, causal: bool, mm: Dot = dot):
    h = h + attention(lp["attn"], rmsnorm(h, lp["norm1"]["scale"]), n_heads,
                      causal, mm)
    x = rmsnorm(h, lp["norm2"]["scale"])
    m = lp["mlp"]
    gated = jax.nn.silu(mm(LIN, x, m["w_gate"])) * mm(LIN, x, m["w_up"])
    return h + mm(LIN, gated, m["w_down"])


def layers(c: Dict[str, Any], ps: List[Dict], h, mm: Dot = dot):
    """All of a component's layers, one at a time, recomputed on the way back."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ps)
    causal = c["kind"] == "decoder"

    def body(h, lp):
        return layer(lp, h, c["n_heads"], causal, mm), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, stacked)
    return h


def cross_entropy(logits, labels):
    logits = logits.astype(F32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _inst(spec, task, comp):
    c = spec["components"][comp]
    return comp if (c["shared"] or c["merge_shared"]) else f"{task}:{comp}"


def loss(spec: Dict[str, Any], p: Dict[str, Any], batch: Dict[str, Dict],
         mm: Dot = dot):
    """Mean over the joins' losses; a merged decoder is one loss over the
    union of its tasks' batches, in flow order."""
    comps = spec["components"]
    losses, merged = [], {}
    for f in spec["flows"]:
        task, b = f["task"], batch[f["task"]]
        outs = {}
        for br in f["branches"]:
            h, prev = None, None
            for comp in br:
                ip = p[_inst(spec, task, comp)]
                h = b[comp] if prev is None else h
                h = layers(comps[comp], ip["layers"], h, mm)
                prev = comp
            outs[br[-1]] = h
        jname = f["join"][0]
        jc = comps[jname]
        ip = p[_inst(spec, task, jname)]
        if jc["kind"] == "contrastive":
            (sa, ha), (sb, hb) = sorted(outs.items())
            za = mm(LIN, jnp.mean(ha, axis=1), ip["proj"][sa])
            zb = mm(LIN, jnp.mean(hb, axis=1), ip["proj"][sb])
            za = za / (jnp.linalg.norm(za, axis=-1, keepdims=True) + 1e-6)
            zb = zb / (jnp.linalg.norm(zb, axis=-1, keepdims=True) + 1e-6)
            logits = mm("ad,bd->ab", za, zb) * jnp.exp(ip["logit_scale"])
            lab = jnp.arange(za.shape[0])
            losses.append(0.5 * (cross_entropy(logits, lab)
                                 + cross_entropy(logits.T, lab)))
            continue
        # decoder entry: token embeddings plus the projected pooled prefix
        h = jnp.take(ip["tok_embed"], b["tokens"], axis=0)
        prefix = jnp.zeros((h.shape[0], jc["d_model"]), F32)
        for src, act in sorted(outs.items()):
            prefix = prefix + mm(LIN, jnp.mean(act, axis=1),
                                 ip["prefix_proj"][src])
        h = h + prefix[:, None, :]
        if jc["merge_shared"]:
            merged.setdefault(jname, []).append((h, b["labels"]))
            continue
        losses.append(_decoder_loss(jc, ip, h, b["labels"], mm))
    for jname, uses in merged.items():
        h = jnp.concatenate([u[0] for u in uses], axis=0)
        labels = jnp.concatenate([u[1] for u in uses], axis=0)
        losses.append(_decoder_loss(comps[jname], p[jname], h, labels, mm))
    return jnp.mean(jnp.stack(losses))


def _decoder_loss(c, ip, h, labels, mm: Dot):
    h = layers(c, ip["layers"], h, mm)
    h = rmsnorm(h, ip["final_norm"]["scale"])
    return cross_entropy(mm(LIN, h, ip["lm_head"]), labels)


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
