"""Pallas TPU kernels for tensor-contraction hot spots.

Two kernel families realise FETTA's micro-architectural ideas on the TPU
memory hierarchy (HBM -> VMEM -> MXU):

* ``matmul_pallas`` — an MXU-tiled GEMM whose rhs may be stored transposed
  (``[N, K]`` layout).  The transpose happens **in VMEM after the DMA**,
  never as a standalone HBM kernel — the TPU analogue of FETTA's
  transposable systolic datapath ("implicit data layout reordering during
  computation", §V-B).  Grid = (M/bm, N/bn, K/bk) with a revisiting f32
  accumulator, K innermost ("output-stationary": the Psum tile stays
  resident while operand tiles stream, exactly the OS dataflow of Fig. 9).

* ``chain_n_pallas`` — an N-step contraction chain
  ``(((X @ W1) @ W2) ... @ Wn)`` with every ``[bm, H_i]`` intermediate held
  in VMEM scratch, so no intermediate tensor of a TT/TTM chain ever
  round-trips HBM (FETTA's butterfly-fed CE array / ETTE's look-ahead
  registers).  Two ping-pong scratch buffers double-buffer the chain: link
  ``i+1`` reads one buffer while the other is free to accept the next
  write, and Pallas's grid pipeline prefetches the next grid cell's
  operand tiles while the current cell computes.  ``chain_pallas`` is the
  historical two-step entry point, now a thin wrapper.  This is what
  ``fused_chain=True`` / ``max_chain_len`` in the CSSE stage-2 model
  assume the runtime can do.

Quantized variants fold dequantization into per-link epilogues: operands
stream at fp8/int8 width, every VMEM intermediate holds *dequantized* real
values (bf16 between MXU passes), and the chain's quantized inputs never
materialize at full width in HBM.

Both use 128-aligned BlockSpecs (MXU edge) and f32 accumulation over bf16
operands.  On CPU hosts they run under ``interpret=True`` (pure-Python
execution of the kernel body) and are validated against ``ref.py``.

Shape/budget violations raise :class:`ChainLoweringError` (a typed
``ValueError``) instead of bare asserts — the plan compiler catches it and
falls back to the unfused GEMM path, and the checks survive ``python -O``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = jax.default_backend() != "tpu"

# VMEM the chain kernel is compiled under (``vmem_limit_bytes``) and the
# budget its footprint model (:func:`chain_vmem_bytes`) is checked against:
# half of a v5e core's 128 MiB, the residency ``perf_model.TPU_V5E``
# assumes.  The plan compiler (repro.core.plan_compiler) consults the same
# model when deciding whether a run of adjacent steps may fuse, so a chain
# it emits is one the TPU compiler accepts.
CHAIN_VMEM_BUDGET_BYTES = 64 * 2 ** 20


class ChainLoweringError(ValueError):
    """A kernel launch was asked for shapes/scales it cannot lower.

    Raised (instead of a bare ``assert``, which vanishes under
    ``python -O``) by the kernel wrappers on contraction-dim mismatches,
    malformed scale vectors and VMEM-budget violations.  The plan compiler
    treats it as "do not fuse": ``compile_plan`` skips the chain and
    ``plan_compiler.run`` re-executes a rejected chain as plain GEMMs, so
    a lowering refusal degrades to the unfused path instead of crashing.
    """


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise ChainLoweringError(msg)


def chain_plan(m0: int, shapes) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """Validate an N-link chain and derive its row geometry.

    ``shapes`` is the per-link matricized weight shape ``(k_i, n_i)``;
    ``m0`` is the first link's row count.  Link ``i+1`` consumes link
    ``i``'s ``[rows_i, n_i]`` output reshaped to ``[rows_i / g_i,
    g_i * n_i]`` where ``g_i = k_{i+1} / n_i`` — the contiguous row-major
    regrouping that folds trailing row axes into the next contraction
    (FETTA's "tensor shaping during computation"; ``g_i = 1`` is the
    classic fixed-M matmul chain).  Returns ``(rows, regroups)`` where
    ``rows[i]`` is link ``i``'s row count (``rows[-1]`` is the final
    output M) and ``regroups[i] = g_i``.  Raises
    :class:`ChainLoweringError` on non-integral regroups.
    """
    shapes = tuple((int(k), int(n)) for k, n in shapes)
    _require(len(shapes) >= 2,
             f"chain needs >= 2 links, got {len(shapes)}")
    rows, regroups = [m0], []
    for i in range(len(shapes) - 1):
        n_i, k_next = shapes[i][1], shapes[i + 1][0]
        _require(k_next % n_i == 0,
                 f"chain link {i + 1}: K={k_next} does not regroup "
                 f"[rows, {n_i}] (not a multiple)")
        g = k_next // n_i
        _require(rows[-1] % g == 0,
                 f"chain link {i + 1}: rows {rows[-1]} not divisible by "
                 f"regroup factor {g}")
        regroups.append(g)
        rows.append(rows[-1] // g)
    return tuple(rows), tuple(regroups)


def _tile_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of a ``[rows, cols]`` block: padded to the (8, 128) f32
    tile at 4 bytes per element, which bounds the bf16 (16, 128) and
    8-bit (32, 128) tilings of the same block from above."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def chain_vmem_bytes(m0: int, shapes,
                     block_m: int = 128, block_n: int = 128) -> int:
    """Upper bound on the VMEM one ``chain_n_pallas`` grid cell occupies.

    ``shapes`` is the per-link ``(k_i, n_i)`` weight shape tuple (see
    :func:`chain_plan`); ``m0`` the first link's row count.  Counted for
    any operand dtype: every pipelined block twice (Pallas double-buffers
    inputs and outputs, the resident interior weights included), the two
    ping-pong intermediate buffers, the scale blocks of the quantized
    kernel, and the widest link's values (the strided row pieces of a
    regroup, lhs, weight and two f32 accumulators) that the compiler
    keeps on its VMEM stack.
    """
    shapes = tuple(shapes)
    rows, _ = chain_plan(m0, shapes)
    m_final, n_last = rows[-1], shapes[-1][1]
    bm, bn = min(block_m, m_final), min(block_n, n_last)
    link_rows = [bm * (r // m_final) for r in rows]
    pipelined = (_tile_bytes(link_rows[0], shapes[0][0])
                 + sum(_tile_bytes(k, n) for k, n in shapes[:-1])
                 + _tile_bytes(shapes[-1][0], bn)
                 + _tile_bytes(link_rows[0], 1)
                 + (len(shapes) - 2) * _tile_bytes(1, 1)
                 + _tile_bytes(1, bn)
                 + _tile_bytes(bm, bn))
    scratch = 2 * _tile_bytes(max(link_rows[:-1]),
                              max(n for _, n in shapes[:-1]))
    values = []
    for i, (r, (k, n)) in enumerate(zip(link_rows, shapes)):
        g = k // shapes[i - 1][1] if i else 1
        pieces = g * _tile_bytes(r, shapes[i - 1][1]) if g > 1 else 0
        values.append(pieces + _tile_bytes(r, k) + _tile_bytes(k, n)
                      + 2 * _tile_bytes(r, n))
    return 2 * pipelined + scratch + max(values)


# ---------------------------------------------------------------------------
# Tiled GEMM with fused rhs transpose
# ---------------------------------------------------------------------------


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int,
                   transpose_rhs: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                       # [bm, bk]
    w = w_ref[...]                       # [bk, bn] or [bn, bk] (stored-T)
    if transpose_rhs:
        w = w.T                          # VMEM-local transpose, fused
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _matmul_scaled_kernel(x_ref, w_ref, sl_ref, sr_ref, o_ref, acc_ref, *,
                          k_steps: int, transpose_rhs: bool):
    """Quantized GEMM: fp8/int8 operand tiles, f32 accumulation, and the
    dequantization scales applied as an *output epilogue* — never a
    separate HBM pass.  Operand tiles upcast in VMEM before the dot (the
    TPU MXU consumes low-precision operands natively; the upcast keeps the
    kernel exact and portable under interpret mode — int8 products and
    fp8 values are all representable in f32)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)   # [bm, bk] quantized -> f32
    w = w_ref[...].astype(jnp.float32)
    if transpose_rhs:
        w = w.T                          # VMEM-local transpose, fused
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        # epilogue: per-row lhs scales x per-col rhs scales (outer product
        # broadcast) — valid because scales never vary along K.
        o_ref[...] = (acc_ref[...] * sl_ref[...] * sr_ref[...]
                      ).astype(o_ref.dtype)


def matmul_pallas(x: jax.Array, w: jax.Array, *, transpose_rhs: bool = False,
                  block_m: int = 128, block_n: int = 128, block_k: int = 128,
                  out_dtype=None, interpret: bool | None = None,
                  scales=None) -> jax.Array:
    """``C[M, N] = X[M, K] @ W`` with W stored ``[K, N]`` or ``[N, K]``.

    ``scales=(sl, sr)`` switches to the quantized kernel: ``x``/``w`` hold
    fp8/int8 values, ``sl`` is the lhs dequantization scale per M row
    (``[M, 1]`` f32), ``sr`` the rhs scale per N column (``[1, N]`` f32),
    and the epilogue computes ``C = (Xq @ Wq) * sl * sr`` in one pass —
    per-tensor scaling is the constant-vector special case.
    """
    m, k = x.shape
    if transpose_rhs:
        n, k2 = w.shape
    else:
        k2, n = w.shape
    _require(k == k2, f"contraction mismatch {k} vs {k2}")
    out_dtype = out_dtype or (x.dtype if scales is None else jnp.float32)
    interpret = INTERPRET if interpret is None else interpret

    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    # Pad to block multiples (zeros contribute nothing to the dot).
    mp, np_, kp = (-m % bm), (-n % bn), (-k % bk)
    if mp or kp:
        x = jnp.pad(x, ((0, mp), (0, kp)))
    if transpose_rhs and (np_ or kp):
        w = jnp.pad(w, ((0, np_), (0, kp)))
    elif not transpose_rhs and (np_ or kp):
        w = jnp.pad(w, ((0, kp), (0, np_)))
    M, K, N = m + mp, k + kp, n + np_
    k_steps = K // bk

    if transpose_rhs:
        w_spec = pl.BlockSpec((bn, bk), lambda i, j, s: (j, s))
    else:
        w_spec = pl.BlockSpec((bk, bn), lambda i, j, s: (s, j))

    # One launch configuration; the quantized variant only swaps the kernel
    # body and appends the scale-vector operands.
    if scales is None:
        kernel = functools.partial(_matmul_kernel, k_steps=k_steps,
                                   transpose_rhs=transpose_rhs)
        scale_specs, scale_ops = [], ()
    else:
        sl, sr = scales
        _require(sl.shape == (m, 1) and sr.shape == (1, n),
                 f"bad GEMM scale shapes {sl.shape}/{sr.shape} for "
                 f"[{m}x{k}] @ [{k}x{n}]")
        if mp:
            sl = jnp.pad(sl, ((0, mp), (0, 0)))
        if np_:
            sr = jnp.pad(sr, ((0, 0), (0, np_)))
        kernel = functools.partial(_matmul_scaled_kernel, k_steps=k_steps,
                                   transpose_rhs=transpose_rhs)
        scale_specs = [pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)),
                       pl.BlockSpec((1, bn), lambda i, j, s: (0, j))]
        scale_ops = (sl, sr)

    out = pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, k_steps),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)), w_spec,
                  *scale_specs],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, *scale_ops)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Fused N-step contraction chain
# ---------------------------------------------------------------------------


def _chain_n_kernel(*refs, h_dtype, n_w: int, bm: int,
                    shapes: tuple[tuple[int, int], ...],
                    mults: tuple[int, ...], quant: bool):
    """N-link chain body over two ping-pong f32 scratch buffers.

    ``refs`` = x, w_1..w_n, [scale_1..scale_n,] out, t0, t1.  Link ``i``
    reads the buffer link ``i-1`` wrote (``t[(i-1) % 2]``) and writes the
    other, so consecutive MXU passes never contend on one buffer — the
    VMEM double-buffering half of the pipeline (operand-tile prefetch
    across grid cells is Pallas's BlockSpec pipeline).

    Link ``i`` computes on ``bm * mults[i]`` rows, and its intermediate is
    stored row-major as ``[bm * mults[i], n_i]``.  Where the next link
    regroups (``[r, n] -> [r/g, g*n]``, ``g = k_{i+1} / n_i``), it never
    reshapes the value: row ``f`` of the regrouped lhs is rows ``f*g ..
    f*g+g-1`` side by side, so the link reads the rows ``j, j+g, j+2g,
    ...`` for each ``j < g`` (strided VMEM reads) and concatenates them
    along the lanes.  The TPU's layout pass refuses the lane-changing
    reshape and accepts this; the dot that follows is the same single
    dot over ``K = g*n`` the reshape fed.  Quantized links multiply
    each dot by that link's folded dequantization scale before the
    downcast, so every resident intermediate holds *real* values.
    """
    x_ref = refs[0]
    w_refs = refs[1:1 + n_w]
    if quant:
        s_refs = refs[1 + n_w:1 + 2 * n_w]
        o_ref, t0_ref, t1_ref = refs[1 + 2 * n_w:]
    else:
        s_refs = None
        o_ref, t0_ref, t1_ref = refs[1 + n_w:]
    t_refs = (t0_ref, t1_ref)
    for i in range(n_w):
        k_i, n_i = shapes[i]
        rows = bm * mults[i]
        if i == 0:
            lhs, w = x_ref[...], w_refs[0][...]
            if quant:
                lhs, w = lhs.astype(jnp.float32), w.astype(jnp.float32)
            acc = jnp.dot(lhs, w, preferred_element_type=jnp.float32)
        else:
            n_prev = shapes[i - 1][1]
            g = k_i // n_prev
            prev = t_refs[(i - 1) % 2]
            if g == 1:
                lhs = prev[:rows, :n_prev]
            else:                                # regroup in VMEM
                lhs = jnp.concatenate(
                    [prev[pl.ds(j, rows, stride=g), :n_prev]
                     for j in range(g)], axis=1)
            w = w_refs[i][...]
            if quant:
                w = w.astype(h_dtype)
            acc = jnp.dot(lhs.astype(h_dtype), w,
                          preferred_element_type=jnp.float32)
        if quant:
            acc = acc * s_refs[i][...]
        if i == n_w - 1:
            o_ref[...] = acc.astype(o_ref.dtype)  # (bm, bn)
        else:
            t_refs[i % 2][:rows, :n_i] = acc


def chain_n_pallas(x: jax.Array, weights, *,
                   block_m: int = 128, block_n: int = 128,
                   out_dtype=None, interpret: bool | None = None,
                   scales=None) -> jax.Array:
    """N-step contraction chain with every intermediate VMEM-resident.

    ``weights`` is a sequence of >= 2 matrices ``W_i[k_i, n_i]`` with
    ``k_1 == x.shape[1]``.  Each link feeds the next either directly
    (``k_{i+1} == n_i``, the classic matmul chain) or through a contiguous
    row regrouping ``[r, n_i] -> [r / g, g * n_i]`` when ``k_{i+1} =
    g * n_i`` (see :func:`chain_plan`) — how a TT/TTM sweep's "consume a
    mode axis per step" structure becomes one on-chip chain.  The output
    is ``[m0 / prod(g), n_last]``.  Interior boundary operands must fit in
    VMEM alongside the tiles (true for TNN cores, where each boundary is a
    product of a few factor/rank dims); the wrapper raises
    :class:`ChainLoweringError` when :func:`chain_vmem_bytes` exceeds the
    VMEM limit the kernel is compiled under.

    ``scales`` switches to the quantized kernel: operands hold fp8/int8
    values and ``scales`` carries one folded dequantization factor per
    link — ``(s_first [m0, 1], c_2 [1, 1], ..., c_{n-1} [1, 1],
    s_last [1, n_last])`` where ``s_first`` is the lhs row scales already
    multiplied by W1's per-tensor scale, each interior ``c_i`` is W_i's
    per-tensor scale, and ``s_last`` W_n's scale per output column.  Each
    link's epilogue applies its factor before the bf16 downcast, so
    intermediates hold dequantized real values and quantized inputs never
    round-trip HBM at full width.
    """
    weights = tuple(weights)
    _require(len(weights) >= 2,
             f"chain needs >= 2 weights, got {len(weights)}")
    _require(x.ndim == 2, f"chain lhs must be 2-D, got shape {x.shape}")
    for i, w in enumerate(weights):
        _require(w.ndim == 2,
                 f"chain weight {i} must be 2-D, got shape {w.shape}")
    m0 = x.shape[0]
    shapes = tuple(w.shape for w in weights)
    _require(shapes[0][0] == x.shape[1],
             f"chain link 0: contraction mismatch "
             f"{shapes[0][0]} vs {x.shape[1]}")
    rows, _ = chain_plan(m0, shapes)     # raises on non-integral regroups
    m_final, n = rows[-1], shapes[-1][1]
    out_dtype = out_dtype or (x.dtype if scales is None else jnp.float32)
    interpret = INTERPRET if interpret is None else interpret

    bm, bn = min(block_m, m_final), min(block_n, n)
    vmem_bytes = chain_vmem_bytes(m0, shapes, block_m, block_n)
    _require(vmem_bytes <= CHAIN_VMEM_BUDGET_BYTES,
             f"chain operands exceed VMEM budget: {vmem_bytes} bytes")
    mults = tuple(r // m_final for r in rows)    # R_i: rows per final row

    mp, np_ = (-m_final % bm), (-n % bn)
    if mp:
        # Pad whole final-row groups so the per-link regrouping still
        # lines up (padded rows are zeros -> zero outputs, sliced off).
        x = jnp.pad(x, ((0, mp * mults[0]), (0, 0)))
    if np_:
        weights = weights[:-1] + (
            jnp.pad(weights[-1], ((0, 0), (0, np_))),)
    M, N = m_final + mp, n + np_

    n_w = len(weights)
    if scales is None:
        kernel = functools.partial(_chain_n_kernel, h_dtype=x.dtype,
                                   n_w=n_w, bm=bm, shapes=shapes,
                                   mults=mults, quant=False)
        scale_specs, scale_ops = [], ()
    else:
        scales = tuple(scales)
        _require(len(scales) == n_w,
                 f"expected {n_w} chain scales, got {len(scales)}")
        s_first, *mid, s_last = scales
        _require(s_first.shape == (m0, 1),
                 f"chain lhs scale must be [{m0}, 1], got {s_first.shape}")
        _require(s_last.shape == (1, n),
                 f"chain out scale must be [1, {n}], got {s_last.shape}")
        for j, s in enumerate(mid):
            _require(tuple(s.shape) == (1, 1),
                     f"chain interior scale {j + 1} must be [1, 1], "
                     f"got {s.shape}")
        if mp:
            s_first = jnp.pad(s_first, ((0, mp * mults[0]), (0, 0)))
        if np_:
            s_last = jnp.pad(s_last, ((0, 0), (0, np_)))
        # bf16 VMEM intermediates — operands are fp8/int8, which cannot
        # hold the dequantized intermediate values.
        kernel = functools.partial(_chain_n_kernel, h_dtype=jnp.bfloat16,
                                   n_w=n_w, bm=bm, shapes=shapes,
                                   mults=mults, quant=True)
        scale_specs = [pl.BlockSpec((bm * mults[0], 1),
                                    lambda i, j: (i, 0))]
        scale_specs += [pl.BlockSpec((1, 1), lambda i, j: (0, 0))
                        for _ in mid]
        scale_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
        scale_ops = (s_first, *mid, s_last)

    # Interior weights resident whole; the last weight streams per column
    # block (the only chain operand besides x/out that scales with the
    # grid).
    w_specs = [pl.BlockSpec(shapes[i], lambda i_, j_: (0, 0))
               for i in range(n_w - 1)]
    w_specs.append(pl.BlockSpec((shapes[-1][0], bn), lambda i, j: (0, j)))
    mid_rows = bm * max(mults[:-1])
    mid_cols = max(n for _, n in shapes[:-1])

    out = pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm * mults[0], shapes[0][0]),
                         lambda i, j: (i, 0)),
            *w_specs,
            *scale_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((mid_rows, mid_cols), jnp.float32),
                        pltpu.VMEM((mid_rows, mid_cols), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=CHAIN_VMEM_BUDGET_BYTES),
        interpret=interpret,
    )(x, *weights, *scale_ops)
    return out[:m_final, :n]


def chain_pallas(x: jax.Array, a: jax.Array, b: jax.Array, *,
                 block_m: int = 128, block_n: int = 128,
                 out_dtype=None, interpret: bool | None = None,
                 scales=None) -> jax.Array:
    """``Y[M, N] = (X[M, K] @ A[K, H]) @ B[H, N]`` — the historical
    two-step chain entry point, now the ``len(weights) == 2`` case of
    :func:`chain_n_pallas` (identical math, same scale convention:
    ``scales=(s1, s2)`` with ``s1 [M, 1]`` the lhs row scales folded with
    A's scale and ``s2 [1, N]`` B's per-column scale)."""
    return chain_n_pallas(x, (a, b), block_m=block_m, block_n=block_n,
                          out_dtype=out_dtype, interpret=interpret,
                          scales=scales)
