"""Analytic TPU performance model — CSSE's stage-2 cost predictor.

The paper's stage 2 ranks candidate contraction sequences with a
cycle-accurate ZigZag model of the FETTA ASIC (§IV, §VI-C).  Our target is a
TPU v5e chip, so the model is retargeted to the TPU execution model:

* per contraction step, collapse to a batched GEMM (B, M, N, K) and charge
    compute = FLOPs / (peak_flops * mxu_utilisation(M, N, K))
    memory  = bytes_moved / hbm_bw
    step    = max(compute, memory) + fixed step overhead
  — the same max() roofline the dry-run analysis uses at whole-model scale,
  so the search optimises the quantity we later report.

* ``mxu_utilisation`` penalises dims that pad badly to the 128x128 MXU and
  the (8, 128) VREG tile — this is exactly the paper's Fig. 6 observation
  (rank-8 contractions run a 128-wide systolic array at 6% utilisation)
  transplanted from their 4x4 CE to the TPU's fixed MXU.

* ``fused_chain=True`` models our Pallas fused-contraction execution, where
  an intermediate small enough for VMEM never round-trips HBM — the TPU
  analogue of FETTA's butterfly networks + ETTE's look-ahead registers.
  Off by default so the baseline matches a plain XLA einsum schedule.

Energy uses per-op/per-byte constants (bf16 MAC + HBM access at a 7nm-class
node) — like the paper's numbers these are model-derived, used for *relative*
comparisons (Fig. 13/14 reproductions), not absolute watts.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Mapping

from repro.analysis.roofline import ring_allreduce_bytes
from repro.core.tnetwork import (
    AxisId, ContractionPlan, ContractionStep, TensorNetwork, localize_network,
    plan_from_tree,
)

#: Bump whenever the analytic cost semantics change (byte accounting,
#: elision predicate, utilisation curve): cached sequence winners were
#: ranked by the old model and must be invalidated through the search
#: signature (csse._signature).
#: 2: chain elision restricted to once-consumed lhs links, mirroring the
#:    compiler's _fusable_link predicate.
MODEL_VERSION = 2


@dataclass(frozen=True)
class HardwareModel:
    """Roofline constants for one accelerator chip."""

    name: str = "tpu-v5e"
    peak_flops: float = 197e12          # bf16 MXU peak, FLOP/s
    hbm_bw: float = 819e9               # bytes/s
    ici_bw: float = 50e9                # bytes/s per link
    vmem_bytes: int = 64 * 2 ** 20      # usable VMEM for operand residency
    mxu_dim: int = 128                  # systolic array edge
    sublane: int = 8                    # VREG second-minor tile
    dtype_bytes: int = 2                # bf16
    step_overhead_s: float = 2e-6       # dispatch + pipeline fill per op
    e_flop: float = 0.35e-12            # J per FLOP (bf16 MAC, 7nm-class)
    e_hbm_byte: float = 25e-12          # J per HBM byte
    e_ici_byte: float = 10e-12          # J per ICI byte

    def mxu_utilisation(self, m: int, n: int, k: int) -> float:
        """Fraction of MXU MACs doing useful work for an (M,N,K) GEMM."""
        def eff(d: int, tile: int) -> float:
            return d / (tile * math.ceil(d / tile))
        # M and N pad to the 128 systolic edge; K streams through in
        # sublane-sized chunks (8 for bf16) — short K mostly costs pipeline
        # fill, modelled by the per-step overhead, so K uses the finer tile.
        return eff(m, self.mxu_dim) * eff(n, self.mxu_dim) * eff(k, self.sublane)


TPU_V5E = HardwareModel()

#: Planning models by the ``device_kind`` JAX reports for the chip.
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E}


def hardware_for(device_kind: str) -> HardwareModel:
    """The planning model of a chip; an unlisted chip is an error, never
    planned with another chip's peaks."""
    if device_kind not in DEVICE_KINDS:
        raise ValueError(f"no planning model for device kind "
                         f"{device_kind!r} (known: {sorted(DEVICE_KINDS)})")
    return DEVICE_KINDS[device_kind]


def apply_policy(hw: HardwareModel, policy) -> HardwareModel:
    """Retarget a hardware model to a quantization policy's storage width.

    The policy (:class:`repro.precision.QuantPolicy`) changes what the
    executor streams — fp8/int8 operands and intermediates — so every
    byte-denominated term (step HBM traffic, HBM energy, the deferred-psum
    ICI payload) reprices at ``policy.dtype_bytes``.  Compute terms keep
    the bf16 MXU peak: the quantized kernels upcast in VMEM, so FLOP
    throughput is unchanged — the win this model captures is pure traffic,
    which is exactly what the low-precision tensorized-training line of
    work banks on.  ``dtype_bytes`` is already part of every CSSE/autotune
    cache signature, so policy-retargeted searches can never collide with
    bf16 entries.

    Note the ICI term keeps :func:`collective_cost`'s storage-dtype
    convention: the sharded executor all-reduces **f32 partial sums**
    regardless of policy (exactness of the deferred reduction), so the
    repriced collective is a *modeled* quantity — consistent with every
    other byte term, which is all a ranking needs within one policy.
    Shipping quantized psum payloads (all-reduce the q tensors + a scale
    combine) is the open item that would realise it on the wire.
    """
    if policy is None or not policy.quantized:
        return hw
    return dataclasses.replace(hw, dtype_bytes=policy.dtype_bytes)

# The paper's evaluation scale (§VI-B): baselines normalised to 256 MACs
# (FETTA's 16 CEs x 4x4 PEs) at 1 GHz with LPDDR4.  Used to reproduce the
# Fig. 13/14 relative numbers under their methodology; absolute v5e numbers
# use TPU_V5E.  A 4x4 PE tile means small tensor dims stay efficient —
# exactly why TNN wins there while a 128x128 MXU is utilisation-starved.
FETTA_EDGE = HardwareModel(
    name="fetta-256mac",
    peak_flops=512e9,            # 256 MACs * 2 flops * 1 GHz
    hbm_bw=25.6e9,               # LPDDR4
    ici_bw=1e9,
    vmem_bytes=640 * 1024,       # 512 KB unified + 128 KB accumulator SRAM
    mxu_dim=4, sublane=4,
    step_overhead_s=0.2e-6,
    e_flop=0.5e-12, e_hbm_byte=40e-12,
)


# ---------------------------------------------------------------------------
# Pipeline spec — the bubble + stage-boundary term of staged execution
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PipelineSpec:
    """How a layer stack is cut into pipeline stages, for costing.

    The pure-Python mirror of the 1F1B executor
    (``repro.distributed.pipeline``), one level above :class:`MeshSpec`:
    the mesh splits one contraction across devices, the pipeline splits
    the *stack* across stage groups.  ``interconnect`` selects the
    boundary-activation bandwidth — ``"ici"`` for stages within one pod
    slice, ``"dcn"`` for the cross-host hop (``dcn_bw``), which is what
    makes deeper pipelines the planner's answer to topologies whose
    cross-host links are too slow for flat data-parallel all-reduces.
    """

    num_stages: int = 1
    num_microbatches: int = 1
    interconnect: str = "ici"      # "ici" | "dcn"
    dcn_bw: float = 25e9           # cross-host bytes/s (v5e pod DCN-class)

    def __post_init__(self):
        if self.num_stages < 1:
            raise ValueError(f"num_stages must be >= 1, got "
                             f"{self.num_stages}")
        if self.num_microbatches < 1:
            raise ValueError(f"num_microbatches must be >= 1, got "
                             f"{self.num_microbatches}")
        if self.interconnect not in ("ici", "dcn"):
            raise ValueError(f"interconnect must be 'ici' or 'dcn', got "
                             f"{self.interconnect!r}")

    def bubble_fraction(self) -> float:
        """Modeled 1F1B fill+drain idle fraction: ``(S-1)/(M+S-1)``."""
        return ((self.num_stages - 1)
                / (self.num_microbatches + self.num_stages - 1))

    def boundary_bw(self, hw: "HardwareModel") -> float:
        return hw.ici_bw if self.interconnect == "ici" else self.dcn_bw

    def signature_payload(self) -> tuple:
        """Hash-stable tuple for disk-cache keys (csse/autotune)."""
        return (self.num_stages, self.num_microbatches, self.interconnect,
                self.dcn_bw)


def pipeline_latency(base_s: float, act_bytes: int,
                     pipe: "PipelineSpec | None",
                     hw: "HardwareModel") -> float:
    """Makespan of one step under pipeline parallelism.

    ``base_s`` is the unpipelined whole-step latency (every per-plan term
    the rest of this model already prices); ``act_bytes`` the boundary
    activation a stage sends downstream per *global* batch.  Each of the
    ``S`` stages works ``base_s / (S*M)`` per microbatch (the stack
    divides across stage devices) plus the boundary send at
    :meth:`PipelineSpec.boundary_bw` and one dispatch overhead, and 1F1B
    fills/drains ``S-1`` extra slots::

        makespan = (M + S - 1) * (base_s/(S*M) + act_bytes/(M*bw) + o)

    so the returned latency embeds exactly
    :meth:`PipelineSpec.bubble_fraction` of idle time — letting the joint
    search trade stage-division gains against bubble + boundary traffic
    (docs/DISTRIBUTED.md derives the tradeoff).
    """
    if pipe is None or pipe.num_stages <= 1:
        return base_s
    s, m = pipe.num_stages, pipe.num_microbatches
    per_slot = (base_s / (s * m)
                + (act_bytes / m) / pipe.boundary_bw(hw)
                + hw.step_overhead_s)
    return (m + s - 1) * per_slot


# ---------------------------------------------------------------------------
# Mesh spec — the pure-Python mirror of a jax device mesh
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshSpec:
    """How a contraction network is laid out over a device mesh, for costing.

    A hashable, jax-free mirror of (jax Mesh, per-axis sharding intent) so
    CSSE searches stay pure-Python at trace time and memoise correctly:

    * ``axes`` — the mesh shape as ordered ``(name, size)`` pairs.
    * ``axis_sharding`` — network axis label -> the mesh axes it splits over
      (e.g. ``(("b", ("data",)),)`` for batch-parallel FP/BP and
      contraction-split WG — the butterfly-distribution analog).
    * ``device_kind`` — provenance tag; enters every disk-cache signature so
      single-device entries can never be served for sharded runs.

    Build one from a live mesh with
    :func:`repro.distributed.sharding.mesh_spec`.
    """

    axes: tuple[tuple[str, int], ...]
    axis_sharding: tuple[tuple[AxisId, tuple[str, ...]], ...] = ()
    device_kind: str = "unknown"

    @property
    def num_devices(self) -> int:
        return math.prod(s for _, s in self.axes)

    def mesh_size(self, names: tuple[str, ...]) -> int:
        shape = dict(self.axes)
        return math.prod(shape.get(n, 1) for n in names)

    def factor(self, axis: AxisId, sizes: Mapping[AxisId, int]) -> int:
        """Ways ``axis`` is split, honouring the divisibility guard the
        executor applies (non-dividing splits are dropped, not errors)."""
        for a, mesh_axes in self.axis_sharding:
            if a == axis:
                p = self.mesh_size(mesh_axes)
                if p > 1 and sizes.get(axis, 0) % p == 0:
                    return p
        return 1

    def factors(self, net: TensorNetwork) -> dict[AxisId, int]:
        return {a: self.factor(a, net.sizes) for a, _ in self.axis_sharding
                if a in net.sizes}

    def signature_payload(self) -> tuple:
        """Hash-stable tuple for disk-cache keys (csse/autotune)."""
        return (self.axes, self.axis_sharding, self.device_kind,
                self.num_devices)


def localize_plan(plan: ContractionPlan, mesh: MeshSpec | None
                  ) -> ContractionPlan:
    """The per-shard plan: same contraction tree, sharded axes scaled down.

    This is exactly what every device executes under
    ``contraction.execute(..., mesh=...)`` — the executor and the cost model
    lower through the same function so stage-2 prices real shard shapes.
    """
    if mesh is None:
        return plan
    factors = mesh.factors(plan.network)
    if all(p == 1 for p in factors.values()):
        return plan
    local = localize_network(plan.network, factors)
    if not plan.steps:
        return ContractionPlan(network=local, steps=(), tree=plan.tree)
    return plan_from_tree(local, plan.tree)


@dataclass(frozen=True)
class CollectiveCost:
    """The communication half of a sharded plan's cost."""

    bytes_ici: int
    latency_s: float
    psum_devices: int          # devices participating in the final psum


def collective_cost(plan: ContractionPlan, mesh: MeshSpec | None,
                    hw: "HardwareModel") -> CollectiveCost:
    """Price the deferred ``psum`` a sharded execution performs.

    The executor keeps partial sums device-local until the whole local plan
    has run (multilinearity makes that exact) and then all-reduces the
    *output*-shaped partials over every mesh axis that split a contracted
    network axis — the butterfly-reduction analog.  Ring all-reduce bytes
    over the per-shard output, at ICI bandwidth, plus one dispatch overhead.
    Phase networks whose sharded axes all survive into the output (FP/BP
    batch parallelism) cost nothing here.

    The payload is priced at ``hw.dtype_bytes`` — the same storage-dtype
    convention as every HBM term in this model (the executor actually psums
    in f32; rankings only need terms consistent *with each other*, and the
    measured objective charges this same function so the two can never
    rank one plan's collective differently).
    """
    if mesh is None:
        return CollectiveCost(0, 0.0, 1)
    net = plan.network
    out_set = set(net.output)
    psum = 1
    for a, _ in mesh.axis_sharding:
        if a in net.sizes and a not in out_set:
            psum *= mesh.factor(a, net.sizes)
    if psum <= 1:
        return CollectiveCost(0, 0.0, 1)
    factors = mesh.factors(net)
    local_out = 1
    for a in net.output:
        local_out *= net.sizes[a] // factors.get(a, 1)
    nbytes = local_out * hw.dtype_bytes
    moved = ring_allreduce_bytes(nbytes, psum)
    return CollectiveCost(bytes_ici=moved,
                          latency_s=moved / hw.ici_bw + hw.step_overhead_s,
                          psum_devices=psum)


def plan_peak_elems(plan: ContractionPlan) -> int:
    """Peak live-tensor footprint (elements) of executing ``plan``.

    Live-tensor accounting that mirrors the executor's slot lifetime rules
    exactly (``contraction.execute`` frees an operand after its last use):
    every input node is resident from the start, each step's output joins
    the live set before its operands can be freed, and the peak is taken at
    the step boundary where lhs, rhs and out coexist.  Elements, not bytes —
    the hardware model multiplies by its (policy-repriced) ``dtype_bytes``.
    One implementation, shared with ``peak_intermediate_elems``:
    :meth:`~repro.core.tnetwork.ContractionPlan.peak_live_elems`.
    """
    return plan.peak_live_elems(include_inputs=True)


def peak_bytes(plan: ContractionPlan, hw: "HardwareModel | None" = None,
               mesh: MeshSpec | None = None, policy=None) -> int:
    """Modeled peak memory (bytes) of one plan execution on one device.

    Composes the three axes the planner cares about: the contraction
    schedule (live-tensor accounting over steps), the quantization policy
    (fp8/int8 storage widths via :func:`apply_policy`) and the mesh (each
    device holds per-shard operands — :func:`localize_plan`).  This is the
    quantity CSSE's ``memory_budget`` constrains and the CPU fallback of
    the measured probe (``repro.memory.probe``) reports.
    """
    hw = apply_policy(hw or TPU_V5E, policy)
    return plan_peak_elems(localize_plan(plan, mesh)) * hw.dtype_bytes


@dataclass(frozen=True)
class StepCost:
    flops: int
    bytes_hbm: int
    compute_s: float
    memory_s: float
    latency_s: float
    bound: str               # "compute" | "memory" | "overhead"
    util: float


@dataclass(frozen=True)
class PlanCost:
    """Aggregate cost of a :class:`ContractionPlan` on one chip — or, with a
    :class:`MeshSpec`, the *per-device* cost of the sharded execution
    (``latency_s`` then includes ``collective_s``, the deferred-psum term).
    """

    latency_s: float
    energy_j: float
    flops: int
    bytes_hbm: int
    steps: tuple[StepCost, ...] = field(repr=False, default=())
    bytes_ici: int = 0
    collective_s: float = 0.0
    peak_bytes: int = 0      # live-tensor peak of the (localized) schedule

    @property
    def edp(self) -> float:
        return self.latency_s * self.energy_j

    @property
    def compute_s(self) -> float:
        return sum(s.compute_s for s in self.steps)

    @property
    def memory_s(self) -> float:
        return sum(s.memory_s for s in self.steps)

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.bytes_hbm, 1)

    @property
    def dominant(self) -> str:
        counts: dict[str, float] = {}
        for s in self.steps:
            counts[s.bound] = counts.get(s.bound, 0.0) + s.latency_s
        return max(counts, key=counts.get) if counts else "none"

    def metric(self, objective: str) -> float:
        return {
            "latency": self.latency_s,
            "energy": self.energy_j,
            "edp": self.edp,
            "flops": float(self.flops),
            "memory": float(self.bytes_hbm),
            "collective": float(self.bytes_ici),
            "peak_bytes": float(self.peak_bytes),
        }[objective]


def evaluate_step(step: ContractionStep, sizes, hw: HardwareModel,
                  read_elems: int | None = None,
                  write_elems: int | None = None) -> StepCost:
    b, m, n, k = step.gemm_dims(sizes)
    util = hw.mxu_utilisation(m, n, k)
    compute = step.flops / (hw.peak_flops * util)
    re = step.read_elems if read_elems is None else read_elems
    we = step.write_elems if write_elems is None else write_elems
    bytes_hbm = (re + we) * hw.dtype_bytes
    memory = bytes_hbm / hw.hbm_bw
    lat = max(compute, memory) + hw.step_overhead_s
    if hw.step_overhead_s > max(compute, memory):
        bound = "overhead"
    elif compute >= memory:
        bound = "compute"
    else:
        bound = "memory"
    return StepCost(flops=step.flops, bytes_hbm=bytes_hbm, compute_s=compute,
                    memory_s=memory, latency_s=lat, bound=bound, util=util)


def evaluate(plan: ContractionPlan, hw: HardwareModel = TPU_V5E,
             fused_chain: bool = False, max_chain_len: int = 2,
             mesh: MeshSpec | None = None, policy=None) -> PlanCost:
    """Cost a full contraction plan.

    With ``fused_chain``, an intermediate consumed by the next step and small
    enough for VMEM residency skips its HBM write+read (Pallas fused
    execution / FETTA butterfly analogue).  ``max_chain_len`` caps how many
    consecutive steps one VMEM-resident run may span, matching the
    compiler's megakernel chain-length cap: after ``max_chain_len`` fused
    links the intermediate is written back to HBM and a new chain begins
    (2 = the historical pairwise fusion).

    With ``policy`` (a quantization policy), every byte term reprices at
    the policy's storage width via :func:`apply_policy` — FP8/INT8 halve
    HBM traffic, the VMEM-residency window for chaining doubles, and the
    deferred-psum ICI payload shrinks by the same factor.

    With ``mesh``, the returned cost is *per device* of the SPMD execution:
    every step is priced at its per-shard dims (sharded axes scaled by their
    mesh factors — steps where no sharded axis is live run at full size on
    every device), and the deferred psum over contracted sharded axes adds
    ``collective_s`` / ``bytes_ici`` (ring all-reduce at ICI bandwidth).
    This is CSSE stage-2's communication-aware objective.
    """
    hw = apply_policy(hw, policy)
    coll = collective_cost(plan, mesh, hw)
    plan = localize_plan(plan, mesh)
    sizes = plan.network.sizes
    num_inputs = plan.network.num_nodes
    uses: dict[int, int] = {}    # slot -> consumption count across the plan
    for step in plan.steps:
        uses[step.lhs] = uses.get(step.lhs, 0) + 1
        uses[step.rhs] = uses.get(step.rhs, 0) + 1
    resident: set[int] = set()   # slots currently living in VMEM only
    step_costs: list[StepCost] = []
    run_len = 1                  # steps in the current VMEM-resident chain
    for i, step in enumerate(plan.steps):
        read = 0
        consumed_resident = False
        for slot, axes in ((step.lhs, step.lhs_shape), (step.rhs, step.rhs_shape)):
            if slot in resident:
                consumed_resident = True
                continue
            read += math.prod(axes)
        run_len = run_len + 1 if consumed_resident else 1
        write = math.prod(step.out_shape)
        if fused_chain and run_len < max_chain_len:
            out_elems = math.prod(step.out_shape)
            # Mirror the compiler's chain predicate (_fusable_link): only
            # an intermediate consumed exactly once, as the *next* step's
            # lhs, can stay VMEM-resident — rhs consumption never chains,
            # so crediting it here would steer the sequence search toward
            # plans the lowering then refuses to fuse.  (The layout-order
            # half of the predicate needs matricization and stays with the
            # compiler; _score prices the compiled plan, so any residual
            # optimism is corrected before candidates are ranked.)
            consumed_next = (i + 1 < len(plan.steps) and
                             plan.steps[i + 1].lhs == step.out and
                             uses.get(step.out, 0) == 1)
            if consumed_next and out_elems * hw.dtype_bytes <= hw.vmem_bytes // 2:
                resident.add(step.out)
                write = 0
        step_costs.append(evaluate_step(step, sizes, hw, read, write))
    flops = sum(s.flops for s in step_costs)
    bytes_hbm = sum(s.bytes_hbm for s in step_costs)
    latency = sum(s.latency_s for s in step_costs) + coll.latency_s
    energy = (flops * hw.e_flop + bytes_hbm * hw.e_hbm_byte
              + coll.bytes_ici * hw.e_ici_byte)
    return PlanCost(latency_s=latency, energy_j=energy, flops=flops,
                    bytes_hbm=bytes_hbm, steps=tuple(step_costs),
                    bytes_ici=coll.bytes_ici, collective_s=coll.latency_s,
                    peak_bytes=plan_peak_elems(plan) * hw.dtype_bytes)
