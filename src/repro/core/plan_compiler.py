"""Plan compiler — lowers a :class:`ContractionPlan` to Pallas kernel calls.

The CSSE search (``repro.core.csse``) picks contraction *sequences* under a
hardware model that assumes fused tensor shaping: operand layout flips folded
into the VMEM stage of the GEMM (FETTA's butterfly distribution/reduction
networks, §V-B) and chain intermediates that never round-trip HBM
(``fused_chain=True`` in stage 2).  This module is what makes those modeled
behaviours *real* on the executor side.  The pipeline is:

1. **Matricization** — each :class:`ContractionStep` is analysed into a GEMM
   ``C[M, N] = A[M, K] @ B[K, N]``: lhs-free axes flatten to M, rhs-free axes
   to N, contracted axes to K (in lhs order).  When the rhs is naturally laid
   out ``[N, K]`` the flip is *not* materialised — the step routes to
   ``matmul_pallas(transpose_rhs=True)``, which transposes the tile in VMEM
   after the DMA (the butterfly-network analogue).  Axis orders that no
   reshape can express are fixed with an explicit ``jnp.transpose`` and
   recorded as ``hbm_transposes`` in the lowering report.

2. **Chain fusion** — maximal runs of adjacent steps where each intermediate
   is consumed exactly once, feeds the next step as its lhs with compatible
   axis groups, and the operand set fits the VMEM budget are fused into a
   single ``chain_n_pallas`` call (up to ``max_chain_len`` links): every
   ``[bm, H_i]`` intermediate of ``((X @ W1) @ W2) ... @ Wn`` lives in VMEM
   scratch and never touches HBM.  This realises what CSSE stage-2 models
   as ``fused_chain=True`` with the matching ``max_chain_len``.  A chain
   the kernel refuses to lower (:class:`ChainLoweringError`) degrades to
   the unfused per-step GEMM path instead of crashing.

3. **Fallback** — steps that are not matricizable (batch axes shared by both
   operands and the output, e.g. BT's block hyperedge; single-operand
   reductions; repeated axes) lower to the reference ``jnp.einsum``.

Entry points: :func:`compile_plan` produces a :class:`CompiledPlan` whose
``report()`` summarises the lowering (op mix, fusion hit-rate, transpose
placement); :func:`run` executes it.  ``contraction.execute(...,
backend="pallas")`` is the public route.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Sequence, Union

import jax
import jax.numpy as jnp

from repro import telemetry as tm
from repro.core.contraction import _einsum_spec, _einsum_step
from repro.core.tnetwork import AxisId, ContractionPlan, ContractionStep
from repro.kernels.fused_contraction import (
    CHAIN_VMEM_BUDGET_BYTES, ChainLoweringError, chain_n_pallas,
    chain_plan, chain_vmem_bytes, matmul_pallas,
)

_log = tm.get_logger("plan_compiler")

#: ChainLoweringError degrades by site, always counted (tracer on or off)
#: so tests and postmortems get exact figures; mirrored into the tracer
#: as ``plan_compiler.chain_degrade.<site>`` counters when tracing.
DEGRADE_COUNTS = {"compile": 0, "runtime": 0, "runtime_quantized": 0}


def reset_degrade_counts() -> None:
    for k in DEGRADE_COUNTS:
        DEGRADE_COUNTS[k] = 0


def _degrade(site: str, err: Exception) -> None:
    """Count a ChainLoweringError degrade and warn once per site — the
    fallback is silent-by-design in the fast path, but it must never be
    *invisible*: a fleet that quietly unfuses every chain looks healthy
    while running the slow plan."""
    DEGRADE_COUNTS[site] += 1
    tm.inc(f"plan_compiler.chain_degrade.{site}")
    _log.warn_once(
        f"plan_compiler.chain_degrade.{site}",
        f"chain fusion degraded to unfused GEMMs at {site}: {err} "
        "(warning once; every occurrence is counted in "
        f"plan_compiler.chain_degrade.{site})")


# ---------------------------------------------------------------------------
# Lowered ops
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TileConfig:
    """Pallas grid tile sizes for one lowered op.

    ``None`` on an op means "kernel defaults" (128-aligned MXU tiles).  The
    autotuner (:mod:`repro.core.autotune`) measures real executions per
    (shape, backend, device) key and threads the winning config in here via
    ``compile_plan(..., tuner=...)``.
    """

    block_m: int = 128
    block_n: int = 128
    block_k: int = 128

    def as_kwargs(self, with_k: bool = True) -> dict:
        kw = {"block_m": self.block_m, "block_n": self.block_n}
        if with_k:
            kw["block_k"] = self.block_k
        return kw


def _perm_or_none(src: Sequence[AxisId], dst: Sequence[AxisId]
                  ) -> tuple[int, ...] | None:
    """Permutation taking ``src`` axis order to ``dst``; None if identity."""
    assert sorted(src) == sorted(dst), (src, dst)
    if tuple(src) == tuple(dst):
        return None
    return tuple(src.index(a) for a in dst)


@dataclass(frozen=True)
class Matricization:
    """How one step collapses to ``C[M, N] = A[M, K] @ B``.

    ``k_axes`` follow lhs order (both operands must flatten K identically).
    ``lhs_perm`` / ``rhs_perm`` are HBM-level transposes applied before the
    reshape; ``transpose_rhs`` means the rhs reshapes to ``[N, K]`` and the
    flip is fused into the kernel's VMEM stage instead.
    """

    m_axes: tuple[AxisId, ...]
    n_axes: tuple[AxisId, ...]
    k_axes: tuple[AxisId, ...]
    m: int
    n: int
    k: int
    lhs_perm: tuple[int, ...] | None
    rhs_perm: tuple[int, ...] | None
    transpose_rhs: bool
    out_perm: tuple[int, ...] | None    # [M-axes, N-axes] -> step.out_axes

    @property
    def hbm_transposes(self) -> int:
        return sum(p is not None
                   for p in (self.lhs_perm, self.rhs_perm, self.out_perm))


@dataclass(frozen=True)
class GemmOp:
    """One step lowered to ``matmul_pallas``."""

    step: ContractionStep
    mat: Matricization
    tiles: TileConfig | None = None      # autotuned grid tiles (None=defaults)


@dataclass(frozen=True)
class ChainOp:
    """>= 2 consecutive steps fused into one ``chain_n_pallas`` call.

    ``Y = (((X @ W1) @ W2) ... @ Wn)`` with every intermediate
    VMEM-resident: X is ``steps[0]``'s lhs matricized to ``[m0, k]``, W_i
    is ``steps[i]``'s rhs matricized to ``link_shapes[i]``.  Where a link
    folds trailing row axes of the previous intermediate into its
    contraction (TT/TTM sweeps), ``link_shapes`` encodes that regrouping
    (``k_{i+1} = g_i * n_i``, see ``kernels.fused_contraction.chain_plan``)
    and the kernel reshapes in VMEM; ``m`` is the *final* row count
    ``m0 / prod(g_i)``.
    """

    steps: tuple[ContractionStep, ...]
    m_axes: tuple[AxisId, ...]          # LAST step's free lhs axes
    h_axes: tuple[AxisId, ...]          # first boundary: steps[0]'s N
    n_axes: tuple[AxisId, ...]
    m: int                              # final output rows (last step's M)
    m0: int                             # first link's rows (x rows)
    n: int
    k: int                              # first's contraction size
    link_shapes: tuple[tuple[int, int], ...]   # (k_i, n_i) per link
    x_perm: tuple[int, ...] | None
    w_perms: tuple[tuple[int, ...] | None, ...]  # rhs_i -> [k_i, n_i]
    out_perm: tuple[int, ...] | None
    tiles: TileConfig | None = None      # autotuned grid tiles (None=defaults)

    # Historical two-step accessors, still used by describe()/cost code
    # that only cares about the chain's endpoints.
    @property
    def first(self) -> ContractionStep:
        return self.steps[0]

    @property
    def second(self) -> ContractionStep:
        return self.steps[-1]

    @property
    def length(self) -> int:
        return len(self.steps)

    @property
    def hs(self) -> tuple[int, ...]:
        """Interior boundary widths (link i's N for i < length-1)."""
        return tuple(n for _, n in self.link_shapes[:-1])

    @property
    def h(self) -> int:
        return self.hs[0]

    @property
    def a_perm(self) -> tuple[int, ...] | None:
        return self.w_perms[0]

    @property
    def b_perm(self) -> tuple[int, ...] | None:
        return self.w_perms[-1]

    @property
    def dims(self) -> tuple[int, ...]:
        """(m0, k_1, n_1, ..., k_L, n_L) — the autotuner's chain key.

        Flat and unambiguous: the regroup factors are implied by the
        (k, n) pairs, so two chains with equal ``dims`` lower to the same
        kernel."""
        return (self.m0,) + tuple(d for kn in self.link_shapes for d in kn)

    @property
    def hbm_transposes(self) -> int:
        return sum(p is not None
                   for p in (self.x_perm, *self.w_perms, self.out_perm))


@dataclass(frozen=True)
class EinsumOp:
    """Non-matricizable step kept on the reference einsum path."""

    step: ContractionStep
    spec: str
    reason: str


LoweredOp = Union[GemmOp, ChainOp, EinsumOp]


# ---------------------------------------------------------------------------
# Step analysis
# ---------------------------------------------------------------------------


def matricize(step: ContractionStep) -> Matricization | str:
    """Collapse a step to GEMM form, or return the reason it cannot be."""
    lhs, rhs, out = step.lhs_axes, step.rhs_axes, step.out_axes
    if len(set(lhs)) != len(lhs) or len(set(rhs)) != len(rhs):
        return "repeated axis within an operand (trace)"
    if step.batch_axes:
        return (f"batch axes {step.batch_axes} on both operands and the "
                "output (>2D residual)")
    out_set, rhs_set, lhs_set = set(out), set(rhs), set(lhs)
    for a in step.contracted_axes:
        if not (a in lhs_set and a in rhs_set):
            return f"axis {a!r} reduced on a single operand"

    m_axes = tuple(a for a in lhs if a in out_set)
    n_axes = tuple(a for a in rhs if a in out_set)
    k_axes = tuple(a for a in lhs if a not in out_set)   # lhs order

    lhs_perm = _perm_or_none(lhs, m_axes + k_axes)
    # rhs laid out [N, K]? -> fuse the flip in VMEM (transpose_rhs).
    if rhs == n_axes + k_axes and k_axes:
        rhs_perm, transpose_rhs = None, True
    else:
        rhs_perm, transpose_rhs = _perm_or_none(rhs, k_axes + n_axes), False
    out_perm = _perm_or_none(m_axes + n_axes, out)

    sizes = dict(zip(lhs + rhs, step.lhs_shape + step.rhs_shape))
    prod = lambda axes: math.prod(sizes[a] for a in axes)  # noqa: E731
    return Matricization(
        m_axes=m_axes, n_axes=n_axes, k_axes=k_axes,
        m=prod(m_axes), n=prod(n_axes), k=prod(k_axes),
        lhs_perm=lhs_perm, rhs_perm=rhs_perm, transpose_rhs=transpose_rhs,
        out_perm=out_perm)


def _consumed_exactly_once(plan: ContractionPlan, slot: int,
                           consumer: ContractionStep) -> bool:
    uses = sum((s.lhs == slot) + (s.rhs == slot) for s in plan.steps)
    return uses == 1 and slot in (consumer.lhs, consumer.rhs)


def _fusable_link(plan: ContractionPlan, g_prev: GemmOp,
                  g_next: GemmOp) -> bool:
    """May ``g_next`` extend an on-chip chain ending at ``g_prev``?

    The intermediate must be consumed once and feed the next step's lhs
    *in layout order*: the intermediate's axes are m_axes + n_axes
    (plan_from_tree emits lhs-major out orders), and the next step must
    keep a prefix of the m-group free while consuming the remaining
    m-suffix plus the whole n-group as its K, with no reshuffle in
    between.  The fixed-M matmul chain is the ``suffix == ()`` case; a
    non-empty suffix is the TT/TTM sweep pattern, realised in the kernel
    as a contiguous VMEM regrouping (``chain_plan``'s ``g_i``)."""
    s_prev, s_next = g_prev.step, g_next.step
    if s_next.lhs != s_prev.out:
        return False
    if not _consumed_exactly_once(plan, s_prev.out, s_next):
        return False
    m_prev, m_next = g_prev.mat, g_next.mat
    if m_next.lhs_perm is not None:
        return False
    if m_prev.out_perm is not None:
        return False
    keep = len(m_next.m_axes)
    if m_next.m_axes != m_prev.m_axes[:keep]:
        return False
    if m_next.k_axes != m_prev.m_axes[keep:] + m_prev.n_axes:
        return False
    return True


def _chain_shapes(run: Sequence[GemmOp]) -> tuple[tuple[int, int], ...]:
    """Per-link matricized weight shapes ``(k_i, n_i)`` of a chain run."""
    return tuple((g.mat.k, g.mat.n) for g in run)


def _chain_fits(run: Sequence[GemmOp], vmem_budget: int) -> bool:
    try:
        return chain_vmem_bytes(run[0].mat.m,
                                _chain_shapes(run)) <= vmem_budget
    except ChainLoweringError:
        return False


def _build_chain(run: Sequence[GemmOp]) -> ChainOp:
    """Assemble the ChainOp for a validated run of >= 2 fusable GEMMs.

    ``chain_n_pallas`` takes every weight as ``[k_i, n_i]``: operand
    perms are re-derived without the transpose_rhs option (the chain
    kernel has no stored-T arg)."""
    if len(run) < 2:
        raise ChainLoweringError(f"chain needs >= 2 steps, got {len(run)}")
    first, last = run[0], run[-1]
    shapes = _chain_shapes(run)
    # Re-validate the regroup geometry end to end — raises the typed
    # error the compiler catches to degrade to the unfused path.
    rows, _ = chain_plan(first.mat.m, shapes)
    if rows[-1] != last.mat.m:
        raise ChainLoweringError(
            f"chain row geometry mismatch: {rows[-1]} vs {last.mat.m}")
    w_perms = tuple(
        _perm_or_none(g.step.rhs_axes, g.mat.k_axes + g.mat.n_axes)
        for g in run)
    return ChainOp(
        steps=tuple(g.step for g in run),
        m_axes=last.mat.m_axes, h_axes=first.mat.n_axes,
        n_axes=last.mat.n_axes,
        m=last.mat.m, m0=first.mat.m,
        n=last.mat.n, k=first.mat.k, link_shapes=shapes,
        x_perm=first.mat.lhs_perm, w_perms=w_perms,
        out_perm=last.mat.out_perm)


def _tuned_chain(tuner, chain: ChainOp, run: Sequence[GemmOp],
                 dtype: str, ptag: str, phase: str) -> ChainOp | None:
    """Apply the measured fuse decision + tile winner to a structural chain.

    Two-step chains keep the historical ``should_fuse``/``chain_tiles``
    protocol exactly; longer chains use the N-ary ``should_fuse_n``/
    ``chain_n_tiles`` when the tuner provides them (duck-typed — a minimal
    tuner that only speaks the pairwise protocol keeps longer chains on
    structural defaults).  Regrouped two-step chains (``m != m0``) also
    use the N-ary protocol: the pairwise ``(m, k, h, n)`` key cannot
    express the row-fold and would alias distinct kernels."""
    if chain.length == 2 and chain.m == chain.m0:
        if tuner.should_fuse(chain.m, chain.k, chain.h, chain.n,
                             dtype=dtype,
                             transpose_rhs1=run[0].mat.transpose_rhs,
                             transpose_rhs2=run[1].mat.transpose_rhs,
                             policy=ptag, phase=phase):
            return dataclasses.replace(
                chain, tiles=tuner.chain_tiles(
                    chain.m, chain.k, chain.h, chain.n, dtype=dtype,
                    policy=ptag, phase=phase))
        return None                      # measured: two GEMMs beat the chain
    should_fuse_n = getattr(tuner, "should_fuse_n", None)
    if should_fuse_n is not None and not should_fuse_n(
            chain.dims, dtype=dtype,
            transpose_rhs=tuple(g.mat.transpose_rhs for g in run),
            policy=ptag, phase=phase):
        return None                 # measured: the GEMM split beats the chain
    chain_n_tiles = getattr(tuner, "chain_n_tiles", None)
    if chain_n_tiles is not None:
        return dataclasses.replace(
            chain, tiles=chain_n_tiles(chain.dims, dtype=dtype,
                                       policy=ptag, phase=phase))
    return chain


# ---------------------------------------------------------------------------
# Compilation
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CompiledPlan:
    """A :class:`ContractionPlan` lowered to kernel dispatches.

    ``mesh_factors`` is set when the plan being compiled is the *per-shard*
    view of an SPMD execution (``contraction.execute(..., mesh=...)``):
    ``((axis, ways), ...)`` recording how each sharded network axis was
    split.  The lowering itself is identical either way — every device runs
    these ops on its shard — but the report keeps the provenance visible so
    fusion/tile statistics are never mistaken for single-device ones.
    """

    plan: ContractionPlan
    ops: tuple[LoweredOp, ...]
    mesh_factors: tuple[tuple[AxisId, int], ...] | None = None
    #: quantized-execution policy (repro.precision.QuantPolicy); None/bf16
    #: keeps the historical full-precision dispatch.  The lowering itself
    #: (matricization, fusion) is dtype-independent — the policy changes
    #: what run() streams: fp8/int8 operands, scale epilogues in the
    #: kernels, per-tensor requantized intermediates.
    policy: object = None

    def report(self) -> dict:
        """Lowering summary — what the compiler actually did with the plan."""
        gemms = [op for op in self.ops if isinstance(op, GemmOp)]
        chains = [op for op in self.ops if isinstance(op, ChainOp)]
        einsums = [op for op in self.ops if isinstance(op, EinsumOp)]
        num_steps = len(self.plan.steps)
        fused_steps = sum(op.length for op in chains)
        return {
            "num_steps": num_steps,
            "num_ops": len(self.ops),
            "num_gemm": len(gemms),
            "num_chain": len(chains),
            "num_einsum_fallback": len(einsums),
            "fused_steps": fused_steps,
            "fusion_hit_rate": fused_steps / num_steps if num_steps else 0.0,
            "max_chain_len_emitted": max(
                (op.length for op in chains), default=0),
            "vmem_transposes": sum(g.mat.transpose_rhs for g in gemms),
            "hbm_transposes": (sum(g.mat.hbm_transposes for g in gemms)
                               + sum(c.hbm_transposes for c in chains)),
            "fallback_reasons": tuple(op.reason for op in einsums),
            "tuned_ops": sum(op.tiles is not None for op in self.ops
                             if not isinstance(op, EinsumOp)),
            "nondefault_tiles": sum(
                op.tiles is not None and op.tiles != TileConfig()
                for op in self.ops if not isinstance(op, EinsumOp)),
            "mesh_factors": (None if self.mesh_factors is None
                             else dict(self.mesh_factors)),
            "policy": (None if self.policy is None
                       or not self.policy.quantized else self.policy.tag),
        }

    def describe(self) -> str:
        lines = []
        for op in self.ops:
            if isinstance(op, GemmOp):
                t = "T(vmem)" if op.mat.transpose_rhs else ""
                lines.append(f"gemm{t} t{op.step.out}: "
                             f"[{op.mat.m}x{op.mat.k}] @ [{op.mat.k}x{op.mat.n}]")
            elif isinstance(op, ChainOp):
                links = " @ ".join(f"[{k}x{n}]" for k, n in op.link_shapes)
                lines.append(f"chain t{op.second.out} (len {op.length}): "
                             f"[{op.m0}x{op.k}] x ({links})  "
                             f"(intermediates VMEM-resident)")
            else:
                lines.append(f"einsum t{op.step.out}: {op.spec}  "
                             f"# {op.reason}")
        r = self.report()
        lines.append(f"fusion hit-rate {r['fusion_hit_rate']:.0%} "
                     f"({r['num_chain']} chain, {r['num_gemm']} gemm, "
                     f"{r['num_einsum_fallback']} einsum)")
        return "\n".join(lines)

    def hbm_bytes(self, dtype_bytes: int = 4) -> int:
        """HBM boundary traffic of the *emitted* kernel dispatches.

        Sums each op's operand + result footprint at ``dtype_bytes`` width.
        A ChainOp charges only its chain-boundary tensors (x, the weights,
        the final output) — the VMEM-resident intermediates move zero HBM
        bytes, which is exactly the saving the megakernel lowering exists
        to deliver.  This is the "measured from the lowering" counterpart
        to ``perf_model.evaluate``'s plan-level model: it reflects what the
        compiler actually emitted, fallbacks and fusion vetoes included.
        """
        total = 0
        for op in self.ops:
            if isinstance(op, ChainOp):
                elems = (op.m0 * op.k
                         + sum(k * n for k, n in op.link_shapes)
                         + op.m * op.n)
            elif isinstance(op, GemmOp):
                mat = op.mat
                elems = mat.m * mat.k + mat.k * mat.n + mat.m * mat.n
            else:
                s = op.step
                elems = (math.prod(s.lhs_shape) + math.prod(s.rhs_shape)
                         + math.prod(s.out_shape))
            total += elems * dtype_bytes
        return total


def compile_plan(plan: ContractionPlan, *, fuse: bool = True,
                 vmem_budget: int = CHAIN_VMEM_BUDGET_BYTES,
                 tuner=None, dtype: str = "float32",
                 mesh_factors=None, policy=None,
                 phase: str = "", max_chain_len: int = 2) -> CompiledPlan:
    """Lower every step; then (unless ``fuse=False``, the ablation CSSE
    stage-2 prices as ``fused_chain=False``) fuse maximal eligible runs of
    adjacent GEMMs into chains of up to ``max_chain_len`` links (the
    historical pairwise fusion is ``max_chain_len=2``, the default).
    ``vmem_budget`` may only tighten fusion: ``chain_n_pallas`` itself
    raises :class:`ChainLoweringError` against
    :data:`CHAIN_VMEM_BUDGET_BYTES`, so larger values are clamped rather
    than compiling chains the kernel would reject.

    ``tuner`` (an :class:`repro.core.autotune.Tuner`, duck-typed) replaces
    the fixed 128-tile defaults with measured winners: every GEMM/chain gets
    its cached best :class:`TileConfig`, and a structurally-fusable pair is
    only fused when the measured chain beats the measured two-GEMM split
    (unmeasured shapes keep the structural default).  ``dtype`` is the
    operand dtype name the measurements are keyed under.

    ``mesh_factors`` tags the result as a per-shard lowering (see
    :class:`CompiledPlan`); pass the localized plan — tile sweeps, fusion
    VMEM checks and measured fuse decisions then all happen at the shard
    shapes each device dispatches.

    ``policy`` may be a full :class:`repro.core.policy.ExecutionPolicy`
    (PR 7's unified planning object): ``fuse`` and ``phase`` are then
    taken from its fusion/phase axes and its precision axis threaded as
    below.  Or, legacy form, a :class:`repro.precision.QuantPolicy`,
    which makes ``run`` execute quantized: same op structure, fp8/int8
    operand streams with scale epilogues.  It also qualifies every tuner
    lookup (the measurement DB must never serve a bf16 tile winner to a
    quantized run — the kernels being timed are different).

    ``phase`` qualifies every tuner lookup the same way (serving's
    phase-specialized profiles tune prefill and decode independently;
    ``""`` is the training default)."""
    _t0 = tm.now_us()
    from repro.core.policy import ExecutionPolicy
    if isinstance(policy, ExecutionPolicy):
        fuse = policy.fused_chain
        phase = policy.phase
        max_chain_len = policy.max_chain_len
        policy = policy.quant_policy
    if policy is not None and not policy.quantized:
        policy = None
    ptag = "" if policy is None else policy.tag
    vmem_budget = min(vmem_budget, CHAIN_VMEM_BUDGET_BYTES)
    lowered: list[LoweredOp] = []
    for step in plan.steps:
        mat = matricize(step)
        if isinstance(mat, str):
            lowered.append(EinsumOp(step=step, spec=_einsum_spec(step),
                                    reason=mat))
        else:
            tiles = None
            if tuner is not None:
                tiles = tuner.gemm_tiles(mat.m, mat.n, mat.k,
                                         transpose_rhs=mat.transpose_rhs,
                                         dtype=dtype, policy=ptag,
                                         phase=phase)
            lowered.append(GemmOp(step=step, mat=mat, tiles=tiles))
    if mesh_factors is not None:
        mesh_factors = tuple(mesh_factors)
    if not fuse:
        return _emit_compile(
            CompiledPlan(plan=plan, ops=tuple(lowered),
                         mesh_factors=mesh_factors, policy=policy), _t0)

    fused: list[LoweredOp] = []
    i = 0
    while i < len(lowered):
        op0 = lowered[i]
        chain = None
        if isinstance(op0, GemmOp) and max_chain_len >= 2:
            # Greedy maximal chain: extend while the next step links, the
            # VMEM accounting admits the extended operand set, and the
            # policy's chain-length cap allows it.
            run = [op0]
            while (len(run) < max_chain_len
                   and i + len(run) < len(lowered)
                   and isinstance(lowered[i + len(run)], GemmOp)
                   and _fusable_link(plan, run[-1], lowered[i + len(run)])
                   and _chain_fits(run + [lowered[i + len(run)]],
                                   vmem_budget)):
                run.append(lowered[i + len(run)])
            if len(run) >= 2:
                try:
                    chain = _build_chain(run)
                except ChainLoweringError as err:
                    chain = None         # degrade to the unfused GEMMs
                    _degrade("compile", err)
                if chain is not None and tuner is not None:
                    chain = _tuned_chain(tuner, chain, run, dtype, ptag,
                                         phase)
        if chain is not None:
            fused.append(chain)
            i += chain.length
        else:
            fused.append(op0)
            i += 1
    return _emit_compile(
        CompiledPlan(plan=plan, ops=tuple(fused),
                     mesh_factors=mesh_factors, policy=policy), _t0)


def _emit_compile(compiled: CompiledPlan, t0: float) -> CompiledPlan:
    """Publish one compile's lowering summary to the tracer: a
    ``plan.compile`` span plus the fusion counters (hit rate and chain
    lengths as gauges — :meth:`CompiledPlan.report` re-expressed as
    trace currency)."""
    if not tm.enabled():
        return compiled
    tm.complete_span("plan.compile", t0, tm.now_us(),
                     steps=len(compiled.plan.steps),
                     ops=len(compiled.ops))
    rep = compiled.report()
    tm.inc("plan_compiler.compiled")
    tm.inc("plan_compiler.steps", rep["num_steps"])
    tm.inc("plan_compiler.fused_steps", rep["fused_steps"])
    tm.inc("plan_compiler.chains", rep["num_chain"])
    tm.inc("plan_compiler.einsum_fallbacks", rep["num_einsum_fallback"])
    tm.sample("plan_compiler.fusion_hit_rate", rep["fusion_hit_rate"])
    tm.sample("plan_compiler.max_chain_len", rep["max_chain_len_emitted"])
    return compiled


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------


def _as_2d(x: jax.Array, perm: tuple[int, ...] | None,
           rows: int, cols: int) -> jax.Array:
    if perm is not None:
        x = jnp.transpose(x, perm)
    return x.reshape(rows, cols)


def _op_reads(op: LoweredOp) -> tuple[int, ...]:
    if isinstance(op, ChainOp):
        return (op.steps[0].lhs, *(s.rhs for s in op.steps))
    return (op.step.lhs, op.step.rhs)


def run(compiled: CompiledPlan, tensors: Sequence[jax.Array],
        accum_dtype=jnp.float32, out_dtype=None,
        interpret: bool | None = None, input_scales=None) -> jax.Array:
    """Execute a compiled plan; semantics match ``contraction.execute``:
    f32 accumulation within a step, storage dtype between steps (the
    *policy* dtype between steps when the plan compiled quantized —
    ``input_scales`` then carries optional delayed per-node scales)."""
    plan = compiled.plan
    net = plan.network
    if out_dtype is None:
        out_dtype = tensors[0].dtype
    assert accum_dtype == jnp.float32, (
        "Pallas kernels accumulate in f32; use backend='einsum' for other "
        "accumulator dtypes")

    if compiled.policy is not None and compiled.policy.quantized:
        return _run_quantized(compiled, tensors, out_dtype=out_dtype,
                              interpret=interpret,
                              input_scales=input_scales)

    if not plan.steps:
        return tensors[0].astype(out_dtype)

    slots: dict[int, jax.Array] = dict(enumerate(tensors))
    sizes = net.sizes
    # Free operands after their last read (same liveness the einsum path
    # keeps) so the compiled backend's peak memory matches the reference.
    last_use: dict[int, int] = {}
    for t, op in enumerate(compiled.ops):
        for slot in _op_reads(op):
            last_use[slot] = t
    for t, op in enumerate(compiled.ops):
        if isinstance(op, EinsumOp):
            res = _einsum_step(op.step, slots[op.step.lhs],
                               slots[op.step.rhs], accum_dtype)
            out_slot = op.step.out
        elif isinstance(op, GemmOp):
            mat = op.mat
            x = _as_2d(slots[op.step.lhs], mat.lhs_perm, mat.m, mat.k)
            if mat.transpose_rhs:
                w = _as_2d(slots[op.step.rhs], mat.rhs_perm, mat.n, mat.k)
            else:
                w = _as_2d(slots[op.step.rhs], mat.rhs_perm, mat.k, mat.n)
            tile_kw = {} if op.tiles is None else op.tiles.as_kwargs()
            res = matmul_pallas(x, w, transpose_rhs=mat.transpose_rhs,
                                out_dtype=out_dtype, interpret=interpret,
                                **tile_kw)
            res = res.reshape(tuple(sizes[a] for a in mat.m_axes + mat.n_axes))
            if mat.out_perm is not None:
                res = jnp.transpose(res, mat.out_perm)
            out_slot = op.step.out
        else:                            # ChainOp
            x = _as_2d(slots[op.steps[0].lhs], op.x_perm, op.m0, op.k)
            ws = [_as_2d(slots[s.rhs], p, ki, ni)
                  for (s, p), (ki, ni) in zip(zip(op.steps, op.w_perms),
                                              op.link_shapes)]
            tile_kw = {} if op.tiles is None else op.tiles.as_kwargs(
                with_k=False)
            try:
                res = chain_n_pallas(x, ws, out_dtype=out_dtype,
                                     interpret=interpret, **tile_kw)
            except ChainLoweringError as err:
                _degrade("runtime", err)
                # Kernel refused the fused lowering (e.g. a VMEM budget
                # tightened after compile): degrade to the unfused path —
                # one GEMM per link, storage dtype between links, exactly
                # what fuse=False would have emitted for these steps.  The
                # reshape regroups trailing row axes into each link's K
                # (the HBM-level analogue of the kernel's VMEM regroup).
                res = x
                for w, (ki, _) in zip(ws, op.link_shapes):
                    res = matmul_pallas(res.reshape(-1, ki), w,
                                        out_dtype=out_dtype,
                                        interpret=interpret
                                        ).astype(out_dtype)
            res = res.reshape(tuple(sizes[ax] for ax in op.m_axes + op.n_axes))
            if op.out_perm is not None:
                res = jnp.transpose(res, op.out_perm)
            out_slot = op.second.out
        slots[out_slot] = res.astype(out_dtype)
        for slot in _op_reads(op):
            if slot != out_slot and last_use[slot] == t and slot in slots:
                del slots[slot]

    out = slots[plan.steps[-1].out]
    last_axes = plan.steps[-1].out_axes
    if last_axes != net.output:
        out = jnp.transpose(out, tuple(last_axes.index(a)
                                       for a in net.output))
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Quantized execution (CompiledPlan.policy set)
# ---------------------------------------------------------------------------


def _run_quantized(compiled: CompiledPlan, tensors: Sequence[jax.Array], *,
                   out_dtype, interpret: bool | None,
                   input_scales) -> jax.Array:
    """Quantized dispatch: operands live in the policy dtype end to end.

    Input nodes are quantized by the Pallas quantize kernel (delayed
    scales when ``input_scales`` provides them); GEMM/chain ops stream the
    quantized values with dequantization fused into their output epilogues
    (:func:`repro.kernels.fused_contraction.matmul_pallas` ``scales=``);
    intermediates requantize per-tensor between steps, so inter-step HBM
    traffic runs at the policy's 1-byte width — exactly what the
    precision-aware cost model charges.  Tile-granular input scales apply
    where the lhs reaches its GEMM as a pure reshape; a layout flip that
    would move the scale groups falls back to a per-tensor requantize
    (same guard-not-error convention as the rest of the compiler).
    Einsum-fallback steps dequantize, run the reference einsum, and
    requantize.
    """
    import dataclasses as _dc

    from repro.kernels.quantized import quantize_pallas
    from repro.precision import policy as _pol
    from repro.precision import quant as _q

    policy = compiled.policy
    inter_policy = _dc.replace(policy, granularity="tensor")
    plan = compiled.plan
    net = plan.network
    sizes = net.sizes

    def qin(x: jax.Array, scale) -> "_q.QTensor":
        if x.ndim < 2:
            return _q.quantize(x, policy, scale=scale)
        if scale is None:
            if policy.granularity == "tile":
                amax = _pol.tile_amax(x, policy.tile_rows)
            else:
                amax = _pol.amax_of(x)
            scale = _pol.compute_scale(amax, policy.qmax, policy.margin)
        else:
            scale = jnp.asarray(scale, jnp.float32)
        rows = x.shape[0]
        q2 = quantize_pallas(x.reshape(rows, -1), _q.expand_row_scales(scale, rows),
                             policy, interpret=interpret)
        return _q.QTensor(q=q2.reshape(x.shape), scale=scale)

    def per_tensor(t: "_q.QTensor") -> "_q.QTensor":
        return t if t.per_tensor else _q.requantize_per_tensor(t, policy)

    qslots: dict[int, _q.QTensor] = {
        i: qin(x, None if input_scales is None else input_scales[i])
        for i, x in enumerate(tensors)}
    if not plan.steps:
        return _q.dequantize(qslots[0], out_dtype)

    last_use: dict[int, int] = {}
    for t, op in enumerate(compiled.ops):
        for slot in _op_reads(op):
            last_use[slot] = t
    for t, op in enumerate(compiled.ops):
        if isinstance(op, EinsumOp):
            res = _einsum_step(op.step, _q.dequantize(qslots[op.step.lhs]),
                               _q.dequantize(qslots[op.step.rhs]),
                               jnp.float32)
            out_slot = op.step.out
        elif isinstance(op, GemmOp):
            mat = op.mat
            ql = qslots[op.step.lhs]
            if not ql.per_tensor and (mat.lhs_perm is not None
                                      or not mat.m_axes):
                ql = per_tensor(ql)
            x2 = _as_2d(ql.q, mat.lhs_perm, mat.m, mat.k)
            sl = _q.expand_row_scales(ql.scale, mat.m)
            qr = per_tensor(qslots[op.step.rhs])
            if mat.transpose_rhs:
                w2 = _as_2d(qr.q, mat.rhs_perm, mat.n, mat.k)
            else:
                w2 = _as_2d(qr.q, mat.rhs_perm, mat.k, mat.n)
            sr = jnp.full((1, mat.n), qr.scale, jnp.float32)
            tile_kw = {} if op.tiles is None else op.tiles.as_kwargs()
            res = matmul_pallas(x2, w2, transpose_rhs=mat.transpose_rhs,
                                out_dtype=jnp.float32, interpret=interpret,
                                scales=(sl, sr), **tile_kw)
            res = res.reshape(tuple(sizes[a] for a in mat.m_axes + mat.n_axes))
            if mat.out_perm is not None:
                res = jnp.transpose(res, mat.out_perm)
            out_slot = op.step.out
        else:                            # ChainOp
            qx = qslots[op.steps[0].lhs]
            if not qx.per_tensor and (op.x_perm is not None
                                      or not op.m_axes):
                qx = per_tensor(qx)
            qws = [per_tensor(qslots[s.rhs]) for s in op.steps]
            x2 = _as_2d(qx.q, op.x_perm, op.m0, op.k)
            w2s = [_as_2d(q.q, p, ki, ni)
                   for (q, p), (ki, ni) in zip(zip(qws, op.w_perms),
                                               op.link_shapes)]
            # Folded per-link dequantization: the lhs row scales absorb the
            # first weight's per-tensor scale; each interior weight
            # contributes a [1, 1] scalar; the last weight's scale applies
            # per output column.  Every VMEM intermediate therefore holds
            # dequantized real values and no full-width intermediate ever
            # reaches HBM.  (Per-tensor scalars commute with the kernel's
            # row regrouping, so the folding is regroup-safe.)
            s_first = _q.expand_row_scales(qx.scale, op.m0) * qws[0].scale
            mids = [jnp.full((1, 1), q.scale, jnp.float32)
                    for q in qws[1:-1]]
            s_last = jnp.full((1, op.n), qws[-1].scale, jnp.float32)
            scales = (s_first, *mids, s_last)
            tile_kw = {} if op.tiles is None else op.tiles.as_kwargs(
                with_k=False)
            try:
                res = chain_n_pallas(x2, w2s, out_dtype=jnp.float32,
                                     interpret=interpret, scales=scales,
                                     **tile_kw)
            except ChainLoweringError as err:
                _degrade("runtime_quantized", err)
                # Unfused fallback mirroring the kernel's link math exactly
                # (f32 first dot, bf16 intermediates, per-link scales,
                # row regrouping as an HBM-level reshape).
                res = jnp.dot(x2.astype(jnp.float32),
                              w2s[0].astype(jnp.float32),
                              preferred_element_type=jnp.float32) * s_first
                for w2, (ki, _), s in zip(w2s[1:], op.link_shapes[1:],
                                          (*mids, s_last)):
                    lhs = res.astype(jnp.bfloat16).reshape(-1, ki)
                    res = jnp.dot(lhs, w2.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32) * s
            res = res.reshape(tuple(sizes[ax] for ax in op.m_axes + op.n_axes))
            if op.out_perm is not None:
                res = jnp.transpose(res, op.out_perm)
            out_slot = op.second.out
        qslots[out_slot] = _q.quantize(res, inter_policy)
        for slot in _op_reads(op):
            if slot != out_slot and last_use[slot] == t and slot in qslots:
                del qslots[slot]

    out = _q.dequantize(qslots[plan.steps[-1].out])
    last_axes = plan.steps[-1].out_axes
    if last_axes != net.output:
        out = jnp.transpose(out, tuple(last_axes.index(a)
                                       for a in net.output))
    return out.astype(out_dtype)
