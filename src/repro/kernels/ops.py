"""Public jit'd wrappers for the Pallas kernels.

These are the entry points the model/executor layers call; each has the
same signature contract as its ``ref.py`` oracle and dispatches to the
Pallas implementation (interpret mode on CPU hosts, compiled on TPU).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import fused_contraction, ref, ssm_scan


@partial(jax.jit, static_argnames=("transpose_rhs", "block_m", "block_n",
                                   "block_k", "use_pallas"))
def fused_matmul(x: jax.Array, w: jax.Array, *, transpose_rhs: bool = False,
                 block_m: int = 128, block_n: int = 128, block_k: int = 128,
                 use_pallas: bool = True) -> jax.Array:
    """C = X @ W (W optionally stored [N, K]) — MXU-tiled, f32 accumulate."""
    if not use_pallas:
        return ref.matmul(x, w, transpose_rhs=transpose_rhs)
    return fused_contraction.matmul_pallas(
        x, w, transpose_rhs=transpose_rhs,
        block_m=block_m, block_n=block_n, block_k=block_k)


@partial(jax.jit, static_argnames=("block_m", "block_n", "use_pallas"))
def fused_chain(x: jax.Array, a: jax.Array, b: jax.Array, *,
                block_m: int = 128, block_n: int = 128,
                use_pallas: bool = True) -> jax.Array:
    """Y = (X @ A) @ B with the intermediate held in VMEM (never in HBM)."""
    if not use_pallas:
        return ref.chain(x, a, b)
    return fused_contraction.chain_pallas(x, a, b, block_m=block_m,
                                          block_n=block_n)


USE_PALLAS_DEFAULT = jax.default_backend() == "tpu"


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _linear_scan(q, k, v, log_decay, u, mode: str, chunk: int,
                 use_pallas: bool):
    if not use_pallas:
        return ref.chunked_linear_scan(q, k, v, log_decay, u, mode=mode,
                                       chunk=chunk)
    return ssm_scan.linear_scan_pallas(q, k, v, log_decay, u,
                                       mode=mode, chunk=chunk)


def _linear_scan_fwd(q, k, v, log_decay, u, mode, chunk, use_pallas):
    out = _linear_scan(q, k, v, log_decay, u, mode, chunk, use_pallas)
    return out, (q, k, v, log_decay, u)


def _linear_scan_bwd(mode, chunk, use_pallas, res, cts):
    """Backward = autodiff of the chunked-jnp twin (rematerialised).

    The Pallas forward is not auto-differentiable; the jnp twin computes
    identical values, so its VJP is the exact gradient of the kernel.
    """
    q, k, v, log_decay, u = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_, ld_, u_: ref.chunked_linear_scan(
            q_, k_, v_, ld_, u_, mode=mode, chunk=chunk),
        q, k, v, log_decay, u)
    return vjp(cts)


_linear_scan.defvjp(_linear_scan_fwd, _linear_scan_bwd)


@partial(jax.jit, static_argnames=("mode", "chunk", "use_pallas"))
def linear_scan(q: jax.Array, k: jax.Array, v: jax.Array,
                log_decay: jax.Array, u: jax.Array | None = None, *,
                mode: str = "ssd", chunk: int = 128,
                use_pallas: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Chunked linear recurrence over [BH, T, d*] streams (ssd / rwkv6).

    ``log_decay`` is [BH, T] in ``ssd`` mode (Mamba-2: one decay per stream
    and token) and [BH, T, dk] in ``rwkv6`` mode (one per channel).
    Returns (o: [BH, T, dv], final_state: [BH, dk, dv] f32).  Differentiable
    (custom VJP through the chunked-jnp twin).  ``use_pallas=None`` picks
    the Pallas kernel on TPU and the identical chunked-jnp twin elsewhere
    (interpret-mode grid loops distort compile-time cost analysis)."""
    decay_shape = q.shape[:2] if mode == "ssd" else q.shape
    if log_decay.shape != decay_shape:
        raise ValueError(f"{mode} mode takes a log-decay of shape "
                         f"{decay_shape}, got {log_decay.shape}")
    if use_pallas is None:
        use_pallas = USE_PALLAS_DEFAULT
    if u is None:
        u = jnp.zeros((q.shape[0], q.shape[-1]), jnp.float32)
    return _linear_scan(q, k, v, log_decay, u, mode, chunk, use_pallas)
