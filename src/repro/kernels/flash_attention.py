"""Pallas flash-attention forward kernel (TPU).

The §Perf analysis (EXPERIMENTS.md H1/H2) shows the residual training
memory term is attention probability tiles streaming through HBM between
the XLA-lowered exp and the PV dot.  This kernel is the fix on real
hardware: scores, softmax stats and probabilities live entirely in VMEM —
one [q_chunk, kv_chunk] tile at a time — with the online-softmax
accumulator carried across the sequential kv grid axis.

Grid: (B * KV * G, Tq / q_chunk, Tk / kv_chunk) — kv innermost
("arbitrary" = sequential), so scratch persists across kv steps for a fixed
(head, q-tile).  GQA is handled in the index map: query-head ``h`` reads
KV head ``h // G``.

Semantics match ``repro.models.blocks._blockwise_attention_fwd_only`` (the
jnp twin used off-TPU and for the custom-VJP backward); validated against
it in interpret mode across causal/GQA/chunk sweeps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_contraction import INTERPRET


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, causal: bool, q_chunk: int,
                  kv_chunk: int, nk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # [qc, d]
    k = k_ref[0].astype(jnp.float32)              # [kc, d]
    v = v_ref[0].astype(jnp.float32)              # [kc, d]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        qi = pl.program_id(1)
        q_pos = qi * q_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (q_chunk, kv_chunk), 0)
        k_pos = j * kv_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (q_chunk, kv_chunk), 1)
        s = jnp.where(q_pos >= k_pos, s, -1e30)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jnp.dot(p.astype(v_ref.dtype).astype(jnp.float32), v,
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...]
                      + jnp.log(jnp.maximum(l_ref[...], 1e-30)))[None, :]


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, q_chunk: int = 512,
                        kv_chunk: int = 512, softmax_scale: float | None = None,
                        interpret: bool | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """GQA flash attention forward.

    q: [B, Tq, H, D]; k, v: [B, Tk, KV, D] with H = KV * G.
    Returns (out [B, Tq, H, D] in q.dtype, lse [B, Tq, KV, G] f32 — the
    softmax stats the flash backward consumes).
    """
    B, Tq, H, D = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = softmax_scale or 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, Tq)
    kv_chunk = min(kv_chunk, Tk)
    assert Tq % q_chunk == 0 and Tk % kv_chunk == 0
    nq, nk = Tq // q_chunk, Tk // kv_chunk
    interpret = INTERPRET if interpret is None else interpret

    # [B, T, H, D] -> [B*H, T, D] with H-major grouping for the kv map.
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Tk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Tk, D)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          q_chunk=q_chunk, kv_chunk=kv_chunk, nk=nk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_chunk, D), lambda h, i, j: (h, i, 0)),
            # GQA: query head h uses kv head (h % H) // G of batch h // H
            pl.BlockSpec((1, kv_chunk, D),
                         lambda h, i, j, G=G, H=H, KV=KV:
                         ((h // H) * KV + (h % H) // G, j, 0)),
            pl.BlockSpec((1, kv_chunk, D),
                         lambda h, i, j, G=G, H=H, KV=KV:
                         ((h // H) * KV + (h % H) // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q_chunk, D), lambda h, i, j: (h, i, 0)),
            # lse is [B*H, 1, Tq]: q positions on the lanes, so the
            # block's last two dims (1, q_chunk) tile the TPU layout.
            pl.BlockSpec((1, 1, q_chunk), lambda h, i, j: (h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_chunk,), jnp.float32),      # running max
            pltpu.VMEM((q_chunk,), jnp.float32),      # running denom
            pltpu.VMEM((q_chunk, D), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    out, lse = out if isinstance(out, (tuple, list)) else (out, None)
    out = out.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)
    # [B*H, 1, Tq] -> [B, Tq, KV, G]   (H is KV-major: h = kv * G + g)
    lse = lse.reshape(B, KV, G, Tq).transpose(0, 3, 1, 2)
    return out, lse
