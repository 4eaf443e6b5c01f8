"""Kernel micro-benchmarks: Pallas (interpret on CPU hosts) vs reference.

Wall time on this host measures the *reference* path (interpret mode runs
the kernel body in Python and is not a performance number); the TPU-side
story is the modeled VMEM-resident chaining (see bench_dataflow) plus the
kernel's per-shape MXU utilisation from the perf model, reported here as
`derived`.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import perf_model
from repro.kernels import ref


def _time(fn, *args, iters=5) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters


def run(print_fn=print) -> list[dict]:
    hw = perf_model.TPU_V5E
    rows = []
    # rank-8 as an OUTPUT dim (m=8) is the MXU-starved case TNN steps hit;
    # rank-8 as the contracted dim (k=8) stays efficient.
    shapes = [("gemm-512", 512, 512, 512), ("gemm-odd", 384, 768, 192),
              ("gemm-rank8-out", 8, 2048, 2048),
              ("gemm-rank8-contract", 2048, 8, 2048)]
    for name, m, k, n in shapes:
        x = jax.random.normal(jax.random.key(0), (m, k), jnp.bfloat16)
        w = jax.random.normal(jax.random.key(1), (k, n), jnp.bfloat16)
        us = _time(lambda a, b: ref.matmul(a, b), x, w) * 1e6
        util = hw.mxu_utilisation(m, n, k)
        rows.append({"name": f"matmul/{name}", "us_per_call": us,
                     "derived": f"mxu_util={util:.3f}"})
    # chain kernel: modeled HBM saving of VMEM-resident intermediate
    x = jax.random.normal(jax.random.key(0), (1024, 256), jnp.bfloat16)
    a = jax.random.normal(jax.random.key(1), (256, 64), jnp.bfloat16)
    b = jax.random.normal(jax.random.key(2), (64, 1024), jnp.bfloat16)
    us = _time(lambda *t: ref.chain(*t), x, a, b) * 1e6
    saved = 1024 * 64 * 2 * 2      # intermediate write+read avoided, bytes
    rows.append({"name": "chain/1024x256x64x1024", "us_per_call": us,
                 "derived": f"hbm_saved_bytes={saved}"})
    # ssm scan: chunked vs sequential oracle speed ratio on host
    bh, t, dk, dv = 4, 512, 32, 64
    q = jax.random.normal(jax.random.key(0), (bh, t, dk)) * 0.5
    k2 = jax.random.normal(jax.random.key(1), (bh, t, dk)) * 0.5
    v = jax.random.normal(jax.random.key(2), (bh, t, dv)) * 0.5
    ld = -jnp.ones((bh, t)) * 0.05       # ssd mode: one decay per token
    us_chunk = _time(jax.jit(
        lambda *args: ref.chunked_linear_scan(*args, chunk=128)),
        q, k2, v, ld) * 1e6
    us_seq = _time(jax.jit(ref.linear_scan_batched), q, k2, v, ld) * 1e6
    rows.append({"name": "ssm/chunked-vs-sequential", "us_per_call": us_chunk,
                 "derived": f"speedup={us_seq/us_chunk:.2f}x"})
    for r in rows:
        print_fn(f"{r['name']:28s} {r['us_per_call']:10.1f} us  {r['derived']}")
    return rows


def validate(rows) -> list[str]:
    failures = []
    for r in rows:
        if "rank8-out" in r["name"] and "util" in r["derived"]:
            util = float(r["derived"].split("=")[1])
            if util > 0.2:
                failures.append("rank-8 GEMM should show low MXU util")
    return failures


if __name__ == "__main__":
    failures = validate(run())
    print("\nclaim checks:", "ALL PASS" if not failures else failures)
