"""Architecture ``mt_dense``: modality towers and joins built from one dense
pre-norm layer (RMSNorm, multi-head attention with RoPE, SwiGLU).

Plain ``jax.numpy`` over the spec of ``bench/spec.py``, with nothing of the
program imported; the harness finds this file by the configuration's
``"arch"``. It gives:

- ``param_shapes``: the parameter tree in the program's layout, as
  ``(shape, init)`` leaves;
- ``loss``: the reference forward pass and loss: the towers, the two joins
  (a symmetric InfoNCE loss over pooled tower outputs; a prefix-conditioned
  causal decoder with its LM loss, over the union batch when the decoder is
  merged), and the mean over the task losses;
- ``forward_flops``: the model FLOPs of one forward pass;
- ``tiny``: the configuration shrunk for the CPU tests.

The semantics follow what the configuration files describe, so a change to
the program's layers cannot move both sides of the comparison. Layers run as
one ``lax.scan`` per component under ``jax.checkpoint``, so the step keeps one
layer's activations at a time and fits beside the state.
"""

from __future__ import annotations

import copy
import math
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp

from bench import spec as specmod

F32 = jnp.float32
Dot = Callable[[str, jax.Array, jax.Array], jax.Array]
LIN = "...i,ij->...j"


# ------------------------------------------------------------------ layout
def in_dims(spec: Dict[str, Any], comp: str) -> Dict[str, int]:
    """Widths of the components that feed ``comp`` in any flow (the
    program keeps one projection per feeding component and instance)."""
    dims = {}
    comps = spec["components"]
    for f in spec["flows"]:
        for chain in f["branches"] + [f["join"]]:
            for a, b in zip(chain, chain[1:]):
                if b == comp:
                    dims[a] = comps[a]["d_model"]
        if f["join"] and f["join"][0] == comp:
            for br in f["branches"]:
                if br:
                    dims[br[-1]] = comps[br[-1]]["d_model"]
    return dims


def param_shapes(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Parameter tree of ``(shape, init)`` leaves in the program's layout.

    ``init`` is ``("normal", scale)`` or ``("const", value)``: the program's
    own initialisation rule (normal weights scaled by 1/sqrt(fan-in),
    embeddings by 0.02, norms at one, the contrastive temperature at
    log 10)."""
    tree: Dict[str, Any] = {}
    for inst, comp in specmod.instances(spec):
        c = spec["components"][comp]
        d, ff = c["d_model"], c["d_ff"]

        def dense(a, b):
            return ((a, b), ("normal", 1.0 / math.sqrt(a)))

        if c["kind"] == "contrastive":
            tree[inst] = {
                "proj": {src: dense(w, d)
                         for src, w in sorted(in_dims(spec, comp).items())},
                "logit_scale": ((), ("const", math.log(10.0))),
            }
            continue
        p: Dict[str, Any] = {}
        if c["kind"] == "decoder":
            p["tok_embed"] = ((c["vocab"], d), ("normal", 0.02))
            p["lm_head"] = dense(d, c["vocab"])
            p["prefix_proj"] = {src: dense(w, d)
                                for src, w in sorted(in_dims(spec, comp).items())}
        ones = ((d,), ("const", 1.0))
        p["layers"] = [
            {
                "norm1": {"scale": ones},
                "attn": {"wq": dense(d, d), "wk": dense(d, d),
                         "wv": dense(d, d), "wo": dense(d, d)},
                "norm2": {"scale": ones},
                "mlp": {"w_gate": dense(d, ff), "w_up": dense(d, ff),
                        "w_down": dense(ff, d)},
            }
            for _ in range(c["n_layers"])
        ]
        p["final_norm"] = {"scale": ones}
        tree[inst] = p
    return tree


# ------------------------------------------------------------------- layers
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


def attention(p, x, n_heads: int, causal: bool, mm: Dot):
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


def layer(lp, h, n_heads: int, causal: bool, mm: Dot):
    h = h + attention(lp["attn"], rmsnorm(h, lp["norm1"]["scale"]), n_heads,
                      causal, mm)
    x = rmsnorm(h, lp["norm2"]["scale"])
    m = lp["mlp"]
    gated = jax.nn.silu(mm(LIN, x, m["w_gate"])) * mm(LIN, x, m["w_up"])
    return h + mm(LIN, gated, m["w_down"])


def layers(c: Dict[str, Any], ps: List[Dict], h, mm: Dot):
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


def loss(spec: Dict[str, Any], p: Dict[str, Any], batch: Dict[str, Dict],
         mm: Dot):
    """Mean over the joins' losses; a merged decoder is one loss over the
    union of its tasks' batches, in flow order. ``mm(eq, a, b)`` takes every
    matrix product (``bench/reference.py``'s ``dot`` or the control's)."""
    comps = spec["components"]
    losses, merged = [], {}
    for f in spec["flows"]:
        task, b = f["task"], batch[f["task"]]
        outs = {}
        for br in f["branches"]:
            h, prev = None, None
            for comp in br:
                ip = p[specmod.instance_of(spec, task, comp)]
                h = b[comp] if prev is None else h
                h = layers(comps[comp], ip["layers"], h, mm)
                prev = comp
            outs[br[-1]] = h
        jname = f["join"][0]
        jc = comps[jname]
        ip = p[specmod.instance_of(spec, task, jname)]
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


# -------------------------------------------------------------------- FLOPs
def layer_forward(d: int, ff: int, batch: int, seq: int) -> float:
    """One layer's forward FLOPs over ``batch`` x ``seq`` tokens: the four
    attention projections, the three SwiGLU matrices, and the attention
    scores and weighted sum (QK^T and AV, over the full S x S as the naive
    attention computes them)."""
    tokens = batch * seq
    return 2.0 * tokens * (4 * d * d + 3 * d * ff) + 4.0 * batch * seq * seq * d


def component_forward(spec: Dict[str, Any], comp: str, batch: int,
                      in_widths: Dict[str, int]) -> float:
    """Forward FLOPs of one execution of ``comp`` over ``batch`` samples;
    ``in_widths`` are the widths of the components feeding a join."""
    c = spec["components"][comp]
    d = c["d_model"]
    if c["kind"] == "contrastive":
        proj = sum(2.0 * batch * w * d for w in in_widths.values())
        return proj + 2.0 * batch * batch * d
    seq = c["seq"]
    f = c["n_layers"] * layer_forward(d, c["d_ff"], batch, seq)
    if c["kind"] == "decoder":
        f += sum(2.0 * batch * w * d for w in in_widths.values())
        f += 2.0 * batch * seq * d * c["vocab"]
    return f


def forward_flops(spec: Dict[str, Any]) -> float:
    """Model FLOPs of one forward pass over every flow of the spec: the
    matrix products above, the decoder's prefix projections and LM head,
    the contrastive projections and logits. Norms, RoPE and softmax are left
    out."""
    comps = spec["components"]
    total = 0.0
    for f in spec["flows"]:
        b = f["batch"]
        for br in f["branches"]:
            for comp in br:
                total += component_forward(spec, comp, b, {})
        widths = {br[-1]: comps[br[-1]]["d_model"] for br in f["branches"] if br}
        # a merged decoder over the union batch costs the sum of its tasks'
        # shares: every term is linear in the batch
        total += component_forward(spec, f["join"][0], b, widths)
    return total


# ------------------------------------------------------------------- tests
def tiny(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's components, tasks and sharing at width 32, 2
    layers, 8 tokens and a 97-word vocabulary (one width and length keep the
    engine's compilations few)."""
    cfg = copy.deepcopy(cfg)
    for name, c in cfg["components"].items():
        c.update(d_model=32, n_heads=4, d_ff=64, seq=8)
        if c["kind"] == "decoder":
            c.update(vocab=97)
        if name in cfg["layers"]:
            cfg["layers"][name] = 2
    return cfg
