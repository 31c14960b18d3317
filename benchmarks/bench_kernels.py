"""Kernel micro-benchmarks: Pallas (interpret) vs pure-jnp oracle.

On CPU the wall times are NOT TPU-representative (interpret mode executes
the kernel body in Python); the purpose here is (a) correctness at bench
shapes and (b) the FLOP accounting used by the roofline.  On a TPU runtime
the same calls compile through Mosaic (``repro.kernels.ops`` picks by
platform).
"""

from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention, grouped_matmul, rglru_scan
from repro.kernels import ref


def _time(fn, *args, iters: int = 3) -> float:
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run() -> List[Dict]:
    rows = []
    # flash attention
    B, H, K, S, hd = 1, 8, 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, K, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, K, S, hd), jnp.float32)
    t_kernel = _time(lambda a, b, c: flash_attention(a, b, c, block_q=128,
                                                     block_k=128), q, k, v)
    t_ref = _time(lambda a, b, c: ref.flash_attention_ref(a, b, c), q, k, v)
    err = float(jnp.max(jnp.abs(
        flash_attention(q, k, v, block_q=128, block_k=128)
        - ref.flash_attention_ref(q, k, v))))
    flops = 4 * B * H * S * S * hd / 2  # causal
    rows.append({"bench": "kernels", "kernel": "flash_attention",
                 "shape": f"B{B} H{H} K{K} S{S} hd{hd}",
                 "t_kernel_us": t_kernel * 1e6, "t_ref_us": t_ref * 1e6,
                 "max_err": err, "flops": flops})

    # grouped matmul
    E, C, d, f = 8, 256, 256, 512
    x = jax.random.normal(ks[0], (E, C, d), jnp.float32)
    w = jax.random.normal(ks[1], (E, d, f), jnp.float32)
    sizes = jnp.asarray([C, C // 2, C // 4, 0, C, 10, C, C // 8], jnp.int32)
    t_kernel = _time(lambda a, b, s: grouped_matmul(a, b, s), x, w, sizes)
    t_ref = _time(lambda a, b, s: ref.grouped_matmul_ref(a, b, s), x, w, sizes)
    err = float(jnp.max(jnp.abs(grouped_matmul(x, w, sizes)
                                - ref.grouped_matmul_ref(x, w, sizes))))
    rows.append({"bench": "kernels", "kernel": "moe_gmm",
                 "shape": f"E{E} C{C} d{d} f{f} ragged",
                 "t_kernel_us": t_kernel * 1e6, "t_ref_us": t_ref * 1e6,
                 "max_err": err,
                 "flops": float(2 * int(jnp.sum(sizes)) * d * f)})

    # rglru scan
    B, S, D = 2, 1024, 512
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, D)))
    b = jax.random.normal(ks[1], (B, S, D))
    t_kernel = _time(lambda u, w_: rglru_scan(u, w_), a, b)
    t_ref = _time(lambda u, w_: ref.rglru_scan_ref(u, w_), a, b)
    err = float(jnp.max(jnp.abs(rglru_scan(a, b) - ref.rglru_scan_ref(a, b))))
    rows.append({"bench": "kernels", "kernel": "rglru_scan",
                 "shape": f"B{B} S{S} D{D}",
                 "t_kernel_us": t_kernel * 1e6, "t_ref_us": t_ref * 1e6,
                 "max_err": err, "flops": float(3 * B * S * D)})
    return rows


def main(rows=None) -> None:
    for r in (run() if rows is None else rows):
        print(f"{r['kernel']:16s} {r['shape']:26s} "
              f"kernel {r['t_kernel_us']:10.0f} us  ref {r['t_ref_us']:10.0f} us  "
              f"max_err {r['max_err']:.2e}")


if __name__ == "__main__":
    main()
