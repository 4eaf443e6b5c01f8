"""Pallas quantize / dequantize kernels.

Elementwise scale-and-cast passes over 2D operands, blocked along rows so
arbitrarily large activations stream through VMEM.  The *scales are
inputs*: under delayed scaling they come from the amax history (no
same-step reduction), under just-in-time scaling the caller computes the
amax with one jnp reduction first.  Scale application inside contractions
does NOT use these kernels — the GEMM/chain epilogues in
:mod:`repro.kernels.fused_contraction` fuse it — these cover the plan
*boundaries*: quantizing input nodes and dequantizing final outputs.

Validated against the jnp reference ops in :mod:`repro.precision.quant`
(``tests/test_precision.py``); on CPU hosts they run under
``interpret=True`` like every other kernel in this package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_contraction import INTERPRET
from repro.precision.policy import QuantPolicy


def _quantize_kernel(x_ref, s_ref, q_ref, *, qmax: float, rnd: bool):
    y = x_ref[...].astype(jnp.float32) / s_ref[...]
    y = jnp.clip(y, -qmax, qmax)
    if rnd:
        y = jnp.round(y)
    q_ref[...] = y.astype(q_ref.dtype)


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]
                  ).astype(o_ref.dtype)


def _row_block(rows: int, block_rows: int) -> int:
    return min(block_rows, rows)


def quantize_pallas(x: jax.Array, scale: jax.Array, policy: QuantPolicy, *,
                    block_rows: int = 256,
                    interpret: bool | None = None) -> jax.Array:
    """``q[R, C] = saturate(x / scale)`` cast to ``policy.operand_dtype``.

    ``scale`` is f32 ``[R, 1]`` (per-row, any granularity expanded) — the
    same form the matmul epilogues consume.  int8 rounds to nearest; fp8
    rounding is the cast itself.
    """
    r, c = x.shape
    assert scale.shape == (r, 1), scale.shape
    interpret = INTERPRET if interpret is None else interpret
    br = _row_block(r, block_rows)
    rp = -r % br
    if rp:
        x = jnp.pad(x, ((0, rp), (0, 0)))
        scale = jnp.pad(scale, ((0, rp), (0, 0)), constant_values=1.0)
    q = pl.pallas_call(
        functools.partial(_quantize_kernel, qmax=policy.qmax,
                          rnd=policy.dtype == "int8"),
        grid=((r + rp) // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r + rp, c), policy.operand_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, scale)
    return q[:r]


def dequantize_pallas(q: jax.Array, scale: jax.Array, *,
                      out_dtype=jnp.float32, block_rows: int = 256,
                      interpret: bool | None = None) -> jax.Array:
    """``x[R, C] = q * scale`` back to a real dtype (f32 by default)."""
    r, c = q.shape
    assert scale.shape == (r, 1), scale.shape
    interpret = INTERPRET if interpret is None else interpret
    br = _row_block(r, block_rows)
    rp = -r % br
    if rp:
        q = jnp.pad(q, ((0, rp), (0, 0)))
        scale = jnp.pad(scale, ((0, rp), (0, 0)), constant_values=1.0)
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=((r + rp) // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r + rp, c), out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q, scale)
    return out[:r]
