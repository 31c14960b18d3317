"""Drive the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: train, wavefront, kernels, serve
    python chip_smoke.py --four-chips  # a 4-chip host: the multi-chip paths only

One process, no subprocess; every phase goes through the entry points a
user calls and raises on failure, so the script exits non-zero.  Without a
TPU it refuses to start: nothing here falls back to the CPU.

One chip:

* **train** -- ``repro.launch.train.train`` on qwen3-0.6b at its published
  width (28 layers, d=1024, 16/8 heads, head_dim 128, vocab 151936) with
  random weights: every loss finite, the first within 1.0 of ln(vocab),
  parameters on the chip.
* **wavefront** -- a bound ``SpindleSession`` on ``tiny_ofasys`` and then
  ``tiny_multitask_clip`` over a ``ClusterSpec`` of the devices present:
  step 0's loss equals ``MTModel.reference_loss`` (float32 matmuls at
  ``highest`` precision) to 1e-4 relative.
* **kernels** -- ``ops.flash_attention`` forward and backward at qwen3-0.6b
  attention shapes (bf16, S=2048) and ``ops.paged_attention`` at its decode
  shapes, compiled through Mosaic (``tpu_custom_call`` in each program) and
  compared with ``kernels/ref.py``.
* **serve** -- a full-width qwen3-0.6b ``ServingSession`` on the paged KV
  cache: 8 requests of 64-token prompts, 16 greedy tokens each; every token
  is the argmax of a no-cache forward over the same sequence, up to bf16
  noise.

``--four-chips``: the wavefront session on ``mesh_over_devices(range(4))``
against ``reference_loss`` on one device (its steps must land on more than
one device), and the trainer on ``make_debug_mesh(4, 1)`` with FSDP-sharded
parameters against a one-device run of the same seed and batch.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-0.6b"
# One chip holds ~9.6 GB of float32 parameters, gradients and AdamW
# moments; 4 x 1024 tokens leave the rest of the 16 GB for activations
# (compile-time memory_analysis of the step on a described v5e).
TRAIN_BATCH, TRAIN_SEQ = 4, 1024
#: bf16 tolerance: max |got - want| over max |want| (bf16 eps is 2^-8)
BF16_TOL = 2e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def memory_stats(device) -> dict:
    """The device's allocator statistics (empty where it reports none)."""
    return device.memory_stats() or {}


def bytes_limit() -> float:
    import jax

    return float(memory_stats(jax.devices()[0]).get("bytes_limit", 16e9))


def report(phase: str, t0: float) -> None:
    import jax

    peaks = [memory_stats(d).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    log(f"{phase}: done in {time.perf_counter() - t0:.1f} s wall "
        f"(compiles included); peak_bytes_in_use per device {peaks}")


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# --------------------------------------------------------------------- train
def phase_train(*, reduced_cfg: bool = False, steps: int = 4,
                batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ,
                mesh=None, seed: int = 0) -> dict:
    """Train a few steps through ``repro.launch.train.train``."""
    import jax
    import numpy as np

    from repro.config import get_arch, reduced
    from repro.launch.train import train

    cfg = get_arch(ARCH)
    if reduced_cfg:
        cfg = reduced(cfg)
    log(f"train: {ARCH} {'reduced' if reduced_cfg else 'published width'} "
        f"L={cfg.n_layers} d={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads} head_dim={cfg.head_dim} vocab={cfg.vocab}; "
        f"batch={batch} seq={seq} steps={steps} "
        f"mesh={None if mesh is None else dict(mesh.shape)}")
    out = train(ARCH, reduced_cfg=reduced_cfg, steps=steps, batch=batch,
                seq=seq, seed=seed, mesh=mesh, log_every=1)
    hist = out["history"]
    log(f"train: losses {hist}")
    _require(len(hist) == steps and all(math.isfinite(x) for x in hist),
             f"non-finite or missing losses: {hist}")
    ln_v = math.log(cfg.vocab)
    _require(abs(hist[0] - ln_v) <= 1.0,
             f"first loss {hist[0]} is not within 1.0 of ln(vocab)={ln_v:.4f}")
    platform = jax.devices()[0].platform
    leaves = jax.tree.leaves(out["params"])
    _require(all(d.platform == platform for x in leaves for d in x.devices()),
             f"parameters are not all on the {platform} devices")
    n_params = sum(int(np.prod(x.shape)) for x in leaves)
    per_dev: dict = {}
    for x in leaves:
        for s in x.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    total = sum(int(x.nbytes) for x in leaves)
    log(f"train: {n_params} parameters ({total} bytes); parameter bytes "
        f"per device {dict(sorted(per_dev.items()))}")
    return {"history": hist, "per_device_bytes": per_dev,
            "param_bytes": total}


# ----------------------------------------------------------------- wavefront
def phase_wavefront(factory, tasks, *, mesh=None, steps: int = 3) -> dict:
    """Bind a SpindleSession over the present devices; check step 0 against
    the single-program reference loss."""
    import jax
    import numpy as np

    from repro.core.placement import ClusterSpec
    from repro.session import SessionConfig, SpindleSession

    n = 1 if mesh is None else mesh.devices.size
    cluster = ClusterSpec(n_devices=n, island_size=n, mem_bytes=bytes_limit())
    session = SpindleSession(
        SessionConfig(cluster=cluster, mesh=mesh),
        model_factory=lambda ts: factory(n_tasks=len(ts)),
        tasks=tasks,
    ).bind()
    model, p = session.model, session.current_plan
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(session.params))
    widths = {c.name: c.d_model for c in model.components.values()}
    log(f"wavefront {factory.__name__}: demo model, {n_params} parameters, "
        f"widths {widths}; plan {len(p.waves())} waves / {len(p.steps)} "
        f"steps on {n} device(s)")
    with jax.default_matmul_precision("highest"):
        ref = float(model.reference_loss(session.params, session.batches))
        losses = [session.step() for _ in range(steps)]
    rel = abs(losses[0] - ref) / abs(ref)
    used = sorted(session.engine.output_devices)
    log(f"wavefront {factory.__name__}: losses {losses}; reference "
        f"{ref}; step-0 relative difference {rel:.3e}; step outputs on "
        f"devices {used}")
    _require(all(math.isfinite(x) for x in losses), f"non-finite: {losses}")
    _require(rel <= 1e-4, f"step-0 loss {losses[0]} != reference {ref} "
             f"(relative difference {rel:.3e} > 1e-4)")
    return {"losses": losses, "ref": ref, "rel": rel, "devices": used}


# ------------------------------------------------------------------- kernels
def _require_kernel(compiled, name: str) -> None:
    _require("tpu_custom_call" in compiled.as_text(),
             f"{name}: no tpu_custom_call in the compiled program")


def phase_kernels(*, batch: int = 1, seq: int = 2048, heads: int = 16,
                  kv_heads: int = 8, head_dim: int = 128, rows: int = 8,
                  page_size: int = 16, pages_per_row: int = 64,
                  seed: int = 0, expect_kernel: bool = True) -> dict:
    """Mosaic kernels through ``repro.kernels.ops`` against ``kernels/ref``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    bf16, f32 = jnp.bfloat16, jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.normal(ks[0], (batch, heads, seq, head_dim), bf16)
    k = jax.random.normal(ks[1], (batch, kv_heads, seq, head_dim), bf16)
    v = jax.random.normal(ks[2], (batch, kv_heads, seq, head_dim), bf16)
    g = jax.random.normal(ks[3], q.shape, f32)

    def up(*xs):
        return [x.astype(f32) for x in xs]

    fwd = ops.flash_attention.lower(q, k, v).compile()
    if expect_kernel:
        _require_kernel(fwd, "flash forward")
    with jax.default_matmul_precision("highest"):
        want = ref.flash_attention_ref(*up(q, k, v))
    e_fwd = _rel_err(fwd(q, k, v), want)

    def loss(fn, q_, k_, v_):
        return jnp.sum(fn(q_, k_, v_).astype(f32) * g)

    # value_and_grad: the custom_vjp backward recomputes through the
    # reference, so with grad alone XLA drops the forward kernel
    bwd = jax.jit(jax.value_and_grad(
        lambda *a: loss(ops.flash_attention, *a), argnums=(0, 1, 2)
    )).lower(q, k, v).compile()
    if expect_kernel:
        _require_kernel(bwd, "flash forward+backward")
    _, got_g = bwd(q, k, v)
    with jax.default_matmul_precision("highest"):
        _, want_g = jax.value_and_grad(
            lambda *a: loss(ref.flash_attention_ref, *a), argnums=(0, 1, 2)
        )(*up(q, k, v))
    e_bwd = [_rel_err(a, b) for a, b in zip(got_g, want_g)]

    # paged decode: each row owns pages_per_row distinct physical pages
    n_pages = rows * pages_per_row + 1  # page 0 is the trash page
    qd = jax.random.normal(ks[4], (rows, heads, head_dim), bf16)
    kp = jax.random.normal(ks[5], (n_pages, kv_heads, page_size, head_dim), bf16)
    vp = jax.random.normal(ks[6], kp.shape, bf16)
    perm = np.random.default_rng(seed).permutation(n_pages - 1) + 1
    table = jnp.asarray(perm.reshape(rows, pages_per_row), jnp.int32)
    lengths = jnp.asarray(np.random.default_rng(seed + 1).integers(
        1, pages_per_row * page_size, rows), jnp.int32)
    paged = ops.paged_attention.lower(qd, kp, vp, table, lengths).compile()
    if expect_kernel:
        _require_kernel(paged, "paged decode")
    with jax.default_matmul_precision("highest"):
        want_p = ref.paged_attention_ref(*up(qd, kp, vp), table, lengths)
    e_paged = _rel_err(paged(qd, kp, vp, table, lengths), want_p)

    log(f"kernels: flash fwd B={batch} H={heads}/{kv_heads} S={seq} "
        f"hd={head_dim} bf16 rel err {e_fwd:.3e}; backward dq/dk/dv rel err "
        f"{[f'{e:.3e}' for e in e_bwd]}; paged decode rows={rows} "
        f"pages/row={pages_per_row} page={page_size} rel err {e_paged:.3e} "
        f"(tolerance {BF16_TOL})")
    for name, e in [("flash forward", e_fwd), ("flash dq", e_bwd[0]),
                    ("flash dk", e_bwd[1]), ("flash dv", e_bwd[2]),
                    ("paged decode", e_paged)]:
        _require(e <= BF16_TOL, f"{name}: relative error {e:.3e} > {BF16_TOL}")
    return {"flash_fwd": e_fwd, "flash_bwd": e_bwd, "paged": e_paged}


# --------------------------------------------------------------------- serve
def phase_serve(*, reduced_cfg: bool = False, n_requests: int = 8,
                prompt_len: int = 64, gen_len: int = 16,
                seed: int = 0) -> dict:
    """Continuous batching on the paged KV cache; check the greedy tokens
    against a no-cache forward over the same sequences."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.placement import ClusterSpec
    from repro.serving import Request, ServingConfig, ServingSession

    session = ServingSession(ServingConfig(
        arch=ARCH, reduced_cfg=reduced_cfg, seed=seed, max_slots=n_requests,
        cache_len=prompt_len + gen_len, kv_layout="paged",
        cluster=ClusterSpec(n_devices=1, island_size=1, mem_bytes=bytes_limit()),
    ))
    vocab = session.model.cfg.vocab
    key = jax.random.PRNGKey(seed + 1)
    reqs = [Request(rid=i, max_new_tokens=gen_len, tokens=jax.random.randint(
        jax.random.fold_in(key, i), (prompt_len,), 0, vocab))
        for i in range(n_requests)]
    m = session.run(reqs)
    outs = [session.results[r.rid].tokens for r in reqs]
    log(f"serve: {m['requests']} requests, {m['output_tokens']} tokens, "
        f"{m['decode_steps']} decode steps, {m['prefill_calls']} prefill "
        f"calls; first request's tokens {outs[0]}")
    _require(all(len(t) == gen_len for t in outs),
             f"short outputs: {[len(t) for t in outs]}")

    # teacher-forced reference: every served token must be the argmax of
    # the no-cache forward at its position, up to bf16 noise (a shortfall
    # below 0.1 standard deviations of that position's logits)
    seqs = jnp.stack([jnp.concatenate([r.tokens, jnp.asarray(t, r.tokens.dtype)])
                      for r, t in zip(reqs, outs)])
    impl = session.model.impl

    @jax.jit
    def shortfall(params, seqs):
        h, _, _ = impl.forward(params, seqs[:, :-1])
        h = h[:, prompt_len - 1:].astype(jnp.float32)
        logits = h @ impl.head(params).astype(jnp.float32)
        got = jnp.take_along_axis(logits, seqs[:, prompt_len:, None], -1)[..., 0]
        gap = jnp.max(logits, -1) - got
        return gap / jnp.std(logits, -1), jnp.mean(gap == 0)

    gap, exact = shortfall(session.params, seqs)
    worst = float(np.max(np.asarray(gap)))
    log(f"serve: tokens that are the exact no-cache argmax {float(exact):.4f}; "
        f"worst shortfall {worst:.4f} logit std")
    _require(worst <= 0.1, f"served token {worst:.4f} std below the argmax")
    return {"worst_shortfall": worst, "exact": float(exact)}


# ---------------------------------------------------------------------- main
def run_one_chip() -> None:
    from repro.runtime import tiny_multitask_clip, tiny_ofasys

    t0 = time.perf_counter()
    phase_train()
    report("train", t0)
    t0 = time.perf_counter()
    phase_wavefront(tiny_ofasys, ("caption", "asr", "summ"))
    phase_wavefront(tiny_multitask_clip,
                    ("img_text", "audio_text", "audio_vision"))
    report("wavefront", t0)
    t0 = time.perf_counter()
    phase_kernels()
    report("kernels", t0)
    t0 = time.perf_counter()
    phase_serve()
    report("serve", t0)


def run_four_chips(*, reduced_cfg: bool = False, batch: int = TRAIN_BATCH,
                   seq: int = TRAIN_SEQ) -> None:
    import jax

    from repro.launch.mesh import make_debug_mesh
    from repro.parallel import mesh_over_devices
    from repro.runtime import tiny_multitask_clip

    n = jax.device_count()
    t0 = time.perf_counter()
    r = phase_wavefront(tiny_multitask_clip,
                        ("img_text", "audio_text", "audio_vision"),
                        mesh=mesh_over_devices(range(n)))
    _require(len(r["devices"]) > 1,
             f"plan steps ran on devices {r['devices']} only")
    report("wavefront (mesh)", t0)

    t0 = time.perf_counter()
    one = phase_train(reduced_cfg=reduced_cfg, steps=2, batch=batch, seq=seq)
    report("train (one device)", t0)
    t0 = time.perf_counter()
    sharded = phase_train(reduced_cfg=reduced_cfg, steps=2, batch=batch,
                          seq=seq, mesh=make_debug_mesh(n, 1))
    diff = abs(sharded["history"][0] - one["history"][0])
    biggest = max(sharded["per_device_bytes"].values())
    share = biggest / sharded["param_bytes"]
    log(f"train (mesh): first loss {sharded['history'][0]} vs one device "
        f"{one['history'][0]} (difference {diff:.3e}); largest per-device "
        f"parameter share {share:.4f}")
    _require(diff <= 1e-2, f"sharded first loss differs by {diff:.3e}")
    _require(len(sharded["per_device_bytes"]) == n and share <= 1.2 / n,
             f"parameters are not sharded over {n} devices: "
             f"{sharded['per_device_bytes']}")
    report("train (mesh)", t0)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths, on 4 chips")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (default device is "
                         f"{dev.platform}); nothing runs on the CPU")
    need = 4 if args.four_chips else 1
    if jax.device_count() < need:
        raise SystemExit(f"chip_smoke: needs {need} chips, JAX sees "
                         f"{jax.device_count()}")
    cache = enable_compile_cache()
    log(f"jax {jax.__version__}; {jax.device_count()} x {dev.device_kind}; "
        f"compile cache {cache}")
    if args.four_chips:
        run_four_chips()
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
