"""Serving driver: a thin shell over the queue-driven ServingSession.

Requests are submitted to a :class:`repro.serving.ServingSession` — a
request queue with admission control, **continuous batching** (arriving
requests are prefilled and paged into free batch slots while the rest of
the batch keeps decoding; finished requests are evicted and their slots
reclaimed), and replanning through the Spindle lifecycle: the active
request mix is bucketized into a workload signature, planned through the
``PlanCache``, and replanned via ``session.signal`` whenever the mix
drifts (DESIGN.md §11).  Every arch family serves through the same path
(KV caches for attn, recurrent states for ssm/hybrid, cross-attention
memories for enc-dec).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --requests 8 --prompt-len 32 --gen-len 16

KV memory defaults to the **paged** layout (``--slab`` restores the PR 3
per-slot slab), admission prefills are **stacked** per prompt length
(``--no-batched-prefill`` restores batch-1 joins), and ``--prefill-chunk N``
streams long prompts into the page pool in N-token chunks interleaved with
decode steps (``--prefill-duty`` sets the chunk:decode duty cycle).
``--static`` switches admission to classic drain-then-refill batching and
``--no-replan`` serves on the initial plan only (two of the baselines
``benchmarks/bench_serving.py`` measures against).  Exits non-zero when no
output tokens were generated (the CI serve-smoke contract).
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..config import get_arch
from ..serving import Request, ServingConfig, ServingSession
from .compile_cache import enable_compile_cache


def _build_requests(session: ServingSession, *, n_requests: int,
                    prompt_len: int, gen_len: int, seed: int,
                    arrival_every: float, shared_prefix: int = 0) -> list:
    cfg = session.model.cfg
    rng = jax.random.PRNGKey(seed + 1)
    prefix = None
    if shared_prefix:
        # every request opens with the same system-prompt-like prefix and
        # diverges into a private suffix — the prefix-sharing workload
        prefix = jax.random.randint(
            jax.random.fold_in(rng, 10**6), (shared_prefix,), 0, cfg.vocab
        )
    reqs = []
    for i in range(n_requests):
        key = jax.random.fold_in(rng, i)
        toks = jax.random.randint(key, (prompt_len,), 0, cfg.vocab)
        if prefix is not None:
            toks = jnp.concatenate([prefix, toks[shared_prefix:]])
        extras: Dict[str, Any] = {}
        if cfg.is_encdec:
            extras["frames"] = jax.random.normal(
                jax.random.fold_in(key, 1),
                (session.batcher.enc_len, cfg.d_model),
            )
        elif cfg.family == "vlm":
            P = min(cfg.frontend_stub_len, 8)
            extras["embeds"] = jax.random.normal(
                jax.random.fold_in(key, 2), (P, cfg.d_model)
            )
        reqs.append(
            Request(
                rid=i,
                tokens=toks,
                max_new_tokens=gen_len,
                arrival=i * arrival_every,
                extras=extras,
            )
        )
    return reqs


def serve(
    arch: str = "qwen3-0.6b",
    *,
    reduced_cfg: bool = True,
    n_requests: int = 8,
    prompt_len: int = 32,
    gen_len: int = 16,
    seed: int = 0,
    verbose: bool = True,
    max_slots: Optional[int] = None,
    admission: str = "continuous",
    replan: str = "mix",
    arrival_every: float = 0.0,
    kv_layout: str = "paged",
    page_size: int = 16,
    kv_pages: int = 0,
    prefill_chunk: int = 0,
    prefill_duty: float = 1.0,
    batched_prefill: bool = True,
    prefix_sharing: bool = False,
    kv_admission: str = "reserve",
    shared_prefix: int = 0,
    cache_dtype: str = "bfloat16",
) -> Dict[str, Any]:
    """Serve ``n_requests`` random prompts; returns tokens + metrics."""
    cfg_full = get_arch(arch)
    stub = min(cfg_full.frontend_stub_len, 8) if cfg_full.family == "vlm" else 0
    cache_len = prompt_len + stub + gen_len
    session = ServingSession(
        ServingConfig(
            arch=arch,
            reduced_cfg=reduced_cfg,
            seed=seed,
            max_slots=max_slots or n_requests,
            cache_len=cache_len,
            enc_len=max(prompt_len // 4, 1),
            admission=admission,
            replan=replan,
            kv_layout=kv_layout,
            page_size=page_size,
            kv_pages=kv_pages,
            prefill_chunk=prefill_chunk,
            prefill_duty=prefill_duty,
            batched_prefill=batched_prefill,
            prefix_sharing=prefix_sharing,
            kv_admission=kv_admission,
            cache_dtype=cache_dtype,
        )
    )
    reqs = _build_requests(
        session, n_requests=n_requests, prompt_len=prompt_len,
        gen_len=gen_len, seed=seed, arrival_every=arrival_every,
        shared_prefix=shared_prefix,
    )
    t0 = time.perf_counter()
    metrics = session.run(reqs)
    wall = time.perf_counter() - t0
    # rejected (admission control) or cut-off requests have no result row
    done = [session.results[r.rid].tokens for r in reqs
            if r.rid in session.results]
    out_tokens = (
        jnp.asarray(done, jnp.int32)
        if done else jnp.zeros((0, gen_len), jnp.int32)
    )
    if verbose:
        b = session.batcher
        tps = metrics["output_tokens"] / max(b.decode_seconds, 1e-9)
        print(
            f"[serve] {arch}: {metrics['requests']} requests "
            f"({admission} batching, replan={replan}) in {wall*1e3:.0f} ms; "
            f"{b.decode_steps} decode steps at {tps:.0f} tok/s; "
            f"{metrics['replans']} replans {metrics['replan_modes']}"
        )
        print(
            f"[serve] prefill: {metrics['prefill_calls']} calls "
            f"({b.chunk_steps} chunk steps, {b.interleaved_chunks} "
            f"interleaved with decode)"
        )
        if metrics.get("kv_page_hw") is not None:
            print(
                f"[serve] kv pages: high-water "
                f"{metrics['kv_page_hw_tokens']} tokens over a "
                f"{metrics['kv_slab_tokens']}-token slab footprint "
                f"({100 * metrics['kv_mem_saving']:.0f}% saved)"
            )
        if metrics.get("prefix_sharing"):
            print(
                f"[serve] prefix sharing: prefix_hit_rate="
                f"{metrics['prefix_hit_rate']:.3f} "
                f"({metrics['prefix_hits']}/{metrics['prefix_requests']} "
                f"requests, {metrics['prefix_hit_tokens']} tokens mapped); "
                f"kv_compression={metrics['kv_compression']:.2f}x, "
                f"{metrics['kv_shared_maps']} shared maps, "
                f"{metrics['kv_cow_forks']} cow forks"
            )
        if metrics.get("kv_admission") == "grow":
            print(
                f"[serve] grow admission: {metrics['kv_grow_allocs']} "
                f"pages grown, {metrics['kv_grow_defers']} paused steps, "
                f"{metrics['kv_preemptions']} preemptions"
            )
        sample = out_tokens[0][:12].tolist() if len(done) else []
        print(f"[serve] generated {metrics['output_tokens']} tokens; "
              f"sample: {sample}")
    # metrics already carries prefill_seconds/decode_seconds from the batcher
    return {"arch": arch, "tokens": out_tokens, **metrics}


def prefix_smoke(args) -> int:
    """The CI prefix-smoke contract: serve one shared-prefix trace twice —
    paged+shared (grow admission) and the unshared paged baseline — and
    fail unless sharing actually hit (``prefix_hit_rate > 0``), its KV
    high-water came in strictly below the unshared run, and the generated
    tokens are EXACTLY the baseline's (fp32 cache pins the arithmetic)."""
    common = dict(
        reduced_cfg=args.reduced,
        n_requests=args.requests,
        prompt_len=args.prompt_len,
        gen_len=args.gen_len,
        seed=args.seed,
        max_slots=args.slots or None,
        replan="off",
        kv_layout="paged",
        page_size=args.page_size,
        prefill_chunk=args.prefill_chunk,
        shared_prefix=args.shared_prefix,
        cache_dtype="float32",
    )
    base = serve(args.arch, **common)
    shared = serve(
        args.arch, prefix_sharing=True, kv_admission="grow", **common
    )
    exact = (
        base["tokens"].shape == shared["tokens"].shape
        and bool(jnp.array_equal(base["tokens"], shared["tokens"]))
    )
    hit = shared.get("prefix_hit_rate", 0.0)
    hw_base, hw_shared = base["kv_page_hw"], shared["kv_page_hw"]
    print(
        f"[prefix-smoke] prefix_hit_rate={hit:.3f} "
        f"kv_page_hw shared={hw_shared} unshared={hw_base} "
        f"token_exact={exact}"
    )
    ok = exact and hit > 0 and hw_shared < hw_base
    print(f"[prefix-smoke] {'PASSED' if ok else 'FAILED'}")
    return 0 if ok else 1


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=0,
                    help="batch slots (default: --requests)")
    ap.add_argument("--arrival-every", type=float, default=0.0,
                    help="stagger arrivals by N decode steps")
    ap.add_argument("--static", action="store_true",
                    help="classic drain-then-refill batching")
    ap.add_argument("--no-replan", action="store_true",
                    help="serve on the initial plan only")
    ap.add_argument("--slab", action="store_true",
                    help="PR 3 per-slot KV slabs instead of the page pool")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size in token positions")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="physical page budget (0 = full coverage)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunk long prompts into N-token prefill chunks "
                         "interleaved with decode (0 = one-shot)")
    ap.add_argument("--prefill-duty", type=float, default=1.0,
                    help="prefill chunks allowed per decode step")
    ap.add_argument("--no-batched-prefill", action="store_true",
                    help="batch-1 admission prefills (the PR 3 join path)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="map hot prompt prefixes through the radix index "
                         "instead of re-prefilling them (paged only)")
    ap.add_argument("--kv-admission", choices=("reserve", "grow"),
                    default="reserve",
                    help="page admission: reserve the full reach up front, "
                         "or grow pages as decode writes them")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every request the same first N prompt tokens")
    ap.add_argument("--prefix-smoke", action="store_true",
                    help="CI gate: serve a shared-prefix trace with and "
                         "without sharing; fail unless hits > 0, KV "
                         "high-water shrinks, and tokens match exactly")
    args = ap.parse_args()
    enable_compile_cache()
    if args.prefix_smoke:
        sys.exit(prefix_smoke(args))
    out = serve(
        args.arch,
        reduced_cfg=args.reduced,
        n_requests=args.requests,
        prompt_len=args.prompt_len,
        gen_len=args.gen_len,
        seed=args.seed,
        max_slots=args.slots or None,
        admission="static" if args.static else "continuous",
        replan="initial" if args.no_replan else "mix",
        arrival_every=args.arrival_every,
        kv_layout="slab" if args.slab else "paged",
        page_size=args.page_size,
        kv_pages=args.kv_pages,
        prefill_chunk=args.prefill_chunk,
        prefill_duty=args.prefill_duty,
        batched_prefill=not args.no_batched_prefill,
        prefix_sharing=args.prefix_sharing,
        kv_admission=args.kv_admission,
        shared_prefix=args.shared_prefix,
    )
    if out["output_tokens"] <= 0 or out["requests"] <= 0:
        print("[serve] FAILED: no output tokens generated", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
