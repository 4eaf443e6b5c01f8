"""Chunked linear-recurrence Pallas kernel (RWKV-6 / Mamba-2 token mixing).

The state-space hot loop shared by the rwkv6 and zamba2 architectures:

    S_t = diag(d_t) S_{t-1} + k_t^T v_t          (state:  [dk, dv])
    o_t = q_t (diag(a_t) S_{t-1} + diag(g_t) k_t^T v_t)

with mode

* ``ssd``   (Mamba-2): a_t = d_t, g_t = 1  ->  o_t = q_t S_t, with d_t
  one scalar per stream and token (the log-decay is [BH, T])
* ``rwkv6``          : a_t = 1,  g_t = u   (the "bonus" weight on the
  current token; the state the output sees is the *un-decayed* S_{t-1})

A naive ``lax.scan`` is a length-T sequential chain of rank-1 updates —
memory-bound and MXU-hostile.  The kernel processes the sequence in chunks
of C tokens: within a chunk the recurrence unrolls into two MXU GEMMs
(an intra-chunk masked attention and a state projection), and only the
[dk, dv] state crosses chunk boundaries — held in VMEM scratch across grid
steps, never touching HBM.  Decay products are computed in log space.  In
``ssd`` mode the intra-chunk ratio matrix exp(lc_i - lc_j) (j <= i) is
formed as such and never overflows, whatever the chunk's summed decay; the
per-channel ``rwkv6`` decay is split as exp(lc_i) * exp(-lc_j), which holds
while a chunk's summed log-decay stays above about -80.  Each mode has its
own kernel body.

Grid: (batch*heads, T/C); the chunk axis is ``arbitrary`` (sequential), the
batch*head axis ``parallel``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_contraction import INTERPRET


def _ssd_kernel(q_ref, k_ref, v_ref, ld_ref, o_ref, sout_ref, state_ref, *,
                num_chunks: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0].astype(jnp.float32)           # [C, dk]
    k = k_ref[0].astype(jnp.float32)           # [C, dk]
    v = v_ref[0].astype(jnp.float32)           # [C, dv]
    ld = ld_ref[0, 0].astype(jnp.float32)      # [1, C] log-decay (<= 0)
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    # Inclusive log cumprod along the lanes as an upper-triangular matmul
    # (the TPU lowering has no cumsum); HIGHEST keeps the f32 sums exact
    # to f32 rounding.  lc_row[i, j] = lc_j; its transpose holds lc_i.
    lc = jnp.dot(ld, (row <= col).astype(jnp.float32),
                 preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)               # [1, C]
    lc_row = jnp.broadcast_to(lc, (c, c))
    lc_col = lc_row.T
    ratio = jnp.exp(jnp.where(row >= col, lc_col - lc_row, -jnp.inf))
    att = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * ratio
    lci = lc_col[:, :1]                        # [C, 1]
    total = jnp.sum(ld, axis=1, keepdims=True)  # [1, 1] the chunk's decay

    inter = jnp.dot(q * jnp.exp(lci), state_ref[...],
                    preferred_element_type=jnp.float32)            # [C, dv]
    o_ref[0] = (jnp.dot(att, v, preferred_element_type=jnp.float32)
                + inter).astype(o_ref.dtype)

    # S_out = exp(total) S_in + (k * exp(total - lc))^T v
    k_s = k * jnp.exp(total - lci)
    state_ref[...] = (state_ref[...] * jnp.exp(total)
                      + jnp.dot(k_s.T, v, preferred_element_type=jnp.float32))

    @pl.when(pl.program_id(1) == num_chunks - 1)
    def _flush_state():
        sout_ref[0] = state_ref[...]


def _rwkv6_kernel(q_ref, k_ref, v_ref, ld_ref, u_ref, o_ref, sout_ref,
                  state_ref, *, num_chunks: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0].astype(jnp.float32)           # [C, dk]
    k = k_ref[0].astype(jnp.float32)           # [C, dk]
    v = v_ref[0].astype(jnp.float32)           # [C, dv]
    ld = ld_ref[0].astype(jnp.float32)         # [C, dk] log-decay (<= 0)
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    # Inclusive log cumprod as a lower-triangular matmul: the TPU lowering
    # has no cumsum.  HIGHEST keeps the f32 sums exact to f32 rounding.
    lc = jnp.dot((row >= col).astype(jnp.float32), ld,
                 preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)

    q_t = q * jnp.exp(lc - ld)                 # output sees S_{t-1}
    k_t = k * jnp.exp(-lc)                     # [C, dk]
    att = jnp.dot(q_t, k_t.T, preferred_element_type=jnp.float32)  # [C, C]
    att = jnp.where(row > col, att, 0.0)
    u = u_ref[0].astype(jnp.float32)           # [1, dk] bonus
    diag = jnp.sum(q * u * k, axis=-1, keepdims=True)     # [C, 1]
    att += jnp.where(row == col, diag, 0.0)

    inter = jnp.dot(q_t, state_ref[...],
                    preferred_element_type=jnp.float32)            # [C, dv]
    o_ref[0] = (jnp.dot(att, v, preferred_element_type=jnp.float32)
                + inter).astype(o_ref.dtype)

    # State update: S_out = diag(exp(lc[-1])) S_in + (k*exp(lc[-1]-lc))^T v
    # lc[-1] is the chunk's whole log-decay, summed as a row for k_s and
    # as a column (over ld^T) for the state rows.
    k_s = k * jnp.exp(jnp.sum(ld, axis=0, keepdims=True) - lc)   # [C, dk]
    decay = jnp.exp(jnp.sum(ld.T, axis=1, keepdims=True))        # [dk, 1]
    state_ref[...] = (state_ref[...] * decay
                      + jnp.dot(k_s.T, v, preferred_element_type=jnp.float32))

    @pl.when(pl.program_id(1) == num_chunks - 1)
    def _flush_state():
        sout_ref[0] = state_ref[...]


def linear_scan_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                       log_decay: jax.Array, u: jax.Array | None = None, *,
                       mode: str = "ssd", chunk: int = 128,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Batched chunked scan.

    Shapes: q, k: [BH, T, dk]; v: [BH, T, dv]; log_decay: [BH, T] in
    ``ssd`` mode (one decay per stream and token), [BH, T, dk] in
    ``rwkv6`` mode; u: [BH, dk] (required for mode="rwkv6", unused by
    ``ssd``).  T must be a multiple of ``chunk`` (pad upstream; decode
    paths use the single-step recurrence instead).  Returns (o: [BH, T,
    dv] in v.dtype, final_state: [BH, dk, dv] f32) — the state output is
    what prefill hands to the decode loop.
    """
    assert mode in ("ssd", "rwkv6")
    bh, t, dk = q.shape
    dv = v.shape[-1]
    assert t % chunk == 0, f"T={t} not a multiple of chunk={chunk}"
    interpret = INTERPRET if interpret is None else interpret
    num_chunks = t // chunk

    def seq_spec(d):
        return pl.BlockSpec((1, chunk, d), lambda b, s: (b, s, 0))

    if mode == "ssd":
        assert log_decay.shape == (bh, t), log_decay.shape
        kernel = _ssd_kernel
        # [BH, T/C, 1, C]: each block's last two dims are the array's
        extra = (log_decay.reshape(bh, num_chunks, 1, chunk),)
        extra_specs = [pl.BlockSpec((1, 1, 1, chunk),
                                    lambda b, s: (b, s, 0, 0))]
    else:
        assert log_decay.shape == q.shape, log_decay.shape
        assert u is not None, "rwkv6 mode requires the u bonus vector"
        kernel = _rwkv6_kernel
        extra = (log_decay, u[:, None, :])      # u: [BH, 1, dk]
        extra_specs = [seq_spec(dk),
                       pl.BlockSpec((1, 1, dk), lambda b, s: (b, 0, 0))]

    out, state = pl.pallas_call(
        functools.partial(kernel, num_chunks=num_chunks),
        grid=(bh, num_chunks),
        in_specs=[seq_spec(dk), seq_spec(dk), seq_spec(dv), *extra_specs],
        out_specs=[
            seq_spec(dv),
            pl.BlockSpec((1, dk, dv), lambda b, s: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), v.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, *extra)
    return out, state
