"""Logical-axis sharding rules -> concrete NamedShardings.

Three surfaces:

* **Activations** — models call ``shard(x, logical_axes)``;
  :func:`make_sharder` resolves each logical name through the rules table
  and applies ``with_sharding_constraint``, silently dropping any mesh axis
  that does not divide the tensor dim (e.g. 4 KV heads on a 16-way model
  axis) — the guard that lets one model code path serve every mesh.

* **Parameters / states** — :func:`param_specs` walks a params pytree and
  assigns PartitionSpecs from path+shape heuristics: column-parallel for
  input-side projections, row-parallel for output-side, expert-parallel for
  stacked expert weights, vocab-parallel embeddings, replicated norms and
  (small) TNN cores.  ``fsdp=True`` additionally shards the largest
  remaining dim of large params over ``data`` (ZeRO-3 style).

* **Contraction plans** — :func:`shard_plan` lays a CSSE
  ``ContractionPlan`` out over the mesh for SPMD execution
  (``contraction.execute(..., mesh=...)``): per input node a
  ``PartitionSpec`` derived from which *network* axes are split
  (batch-parallel ``b`` for FP/BP, contraction-split ``b`` + deferred
  ``psum`` for WG — the mesh-collective analog of FETTA's butterfly
  distribution/reduction networks, see ``docs/SHARDING.md``), plus the
  matching per-shard plan and the pure :class:`~repro.core.perf_model.
  MeshSpec` the communication-aware CSSE stage-2 costs it with.

Mesh axis names: ``("data", "model")`` single-pod, ``("pod", "data",
"model")`` multi-pod; ``pod`` is outer data parallelism (hierarchical
gradient reduction).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import perf_model
from repro.core.tnetwork import AxisId, ContractionPlan, TensorNetwork


# Logical activation axis -> mesh axis (tuple = combined axes).
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,                 # "data" under sequence parallelism
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "experts": "model",
    "moe_groups": ("pod", "data"),   # MoE dispatch groups (= batch rows)
    "vocab": "model",
    "embed": None,
}


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Sequence[Any] | None = None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the compiler propagates
    shardings between the ``with_sharding_constraint`` hints that
    :func:`make_sharder` places.  jax's own default is ``Explicit`` axes,
    which that constraint rejects.  Every mesh the trainer, the server and
    the elastic restart build comes from here."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def _axes_in(mesh: Mesh, spec) -> tuple[str, ...]:
    if spec is None:
        return ()
    axes = spec if isinstance(spec, tuple) else (spec,)
    return tuple(a for a in axes if a in mesh.axis_names)


def _mesh_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def make_sharder(mesh: Mesh | None, rules: dict[str, Any] | None = None):
    """Build the ``shard(x, logical_axes)`` callback models consume."""
    if mesh is None:
        return lambda x, axes: x
    rules = {**DEFAULT_RULES, **(rules or {})}

    def shard(x: jax.Array, axes: tuple[Optional[str], ...]) -> jax.Array:
        if len(axes) != x.ndim:
            return x
        parts = []
        used: set[str] = set()
        for dim, name in zip(x.shape, axes):
            cand = _axes_in(mesh, rules.get(name)) if name else ()
            cand = tuple(a for a in cand if a not in used)
            if cand and dim % _mesh_size(mesh, cand) == 0:
                parts.append(cand if len(cand) > 1 else cand[0])
                used.update(cand)
            else:
                parts.append(None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*parts)))

    shard.mesh = mesh      # read by batch_parallel's callers (attention)
    return shard


def batch_parallel(fn, mesh: Mesh | None, batch: int):
    """``fn`` run once per batch shard of ``mesh``: a ``shard_map`` that
    splits dim 0 of every operand and result over the mesh's batch axes.

    The compiler cannot partition a Pallas kernel, so a kernel that works
    row by row on the batch (flash attention) reaches a multi-device mesh
    through this.  Where the batch axes do not divide ``batch``, every
    device runs the whole batch.  One device or no mesh returns ``fn``."""
    if mesh is None or mesh.size == 1:
        return fn
    axes = _axes_in(mesh, DEFAULT_RULES["batch"])
    spec = P(axes) if axes and batch % _mesh_size(mesh, axes) == 0 else P()
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

# Projections whose *output* dim shards over `model` (column parallel)...
_COL_NAMES = {"q", "k", "v", "gate", "up", "cm_k", "in", "r", "g", "lm_head"}
# ...and whose *input* dim shards over `model` (row parallel).
_ROW_NAMES = {"o", "down", "cm_v", "out"}


def _path_names(path) -> list[str]:
    names = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            names.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            names.append(p.name)
        elif isinstance(p, jax.tree_util.SequenceKey):
            names.append(str(p.idx))
    return names


def _spec_for(names: list[str], shape: tuple[int, ...], mesh: Mesh,
              fsdp: bool, inference: bool = False) -> P:
    msize = mesh.shape.get("model", 1)
    # Leading layer-stack axis (present both under params/layers/... and
    # under optimizer-state mirrors like opt/m/layers/...).
    stacked = 1 if any(n in ("layers", "enc_layers", "dec_layers")
                       for n in names) else 0
    parts: list[Any] = [None] * len(shape)

    def ok(dim_idx: int, size: int = msize) -> bool:
        return 0 <= dim_idx < len(shape) and shape[dim_idx] % size == 0

    leaf = names[-1] if names else ""
    parent = names[-2] if len(names) >= 2 else ""
    path_str = "/".join(names)

    if leaf == "embed" and len(shape) == 2:
        if ok(0):
            parts[0] = "model"                   # vocab-parallel table
    elif "cores" in names:
        # TNN factor cores: small; replicate except the expert axis of
        # MoE-stacked cores ([L, E, ...]).
        if "experts" in names and ok(stacked):
            parts[stacked] = "model"
    elif leaf == "w" and len(shape) >= 2:
        if parent == "router":
            pass                                  # replicated router
        elif "experts" in names and len(shape) == stacked + 3:
            if inference and "data" in mesh.axis_names \
                    and shape[stacked] % (msize * mesh.shape["data"]) == 0:
                # serving: 2D expert sharding (E over model x data) — no
                # per-token weight gather, dispatch reshards instead
                parts[stacked] = ("model", "data")
            elif inference and "data" in mesh.axis_names \
                    and shape[stacked] % mesh.shape["data"] == 0 and ok(stacked):
                # E over data, d_ff over model: weights stay put; the MoE
                # combine's partial sums all-reduce tiny activations.
                # model goes on the expert FFN's wide dim: output side for
                # gate/up ([E, D, F] -> F), contracted side for down
                # ([E, F, D] -> F) so h stays F-sharded end to end.
                parts[stacked] = "data"
                wide = (len(shape) - 1 if parent in _COL_NAMES
                        else len(shape) - 2)
                if shape[wide] % msize == 0:
                    parts[wide] = "model"
            elif ok(stacked):
                parts[stacked] = "model"          # expert parallelism
        elif parent in _COL_NAMES and ok(len(shape) - 1):
            parts[-1] = "model"
        elif parent in _ROW_NAMES and ok(len(shape) - 2):
            parts[-2] = "model"
    elif leaf == "b" and parent in _COL_NAMES and ok(len(shape) - 1):
        parts[-1] = "model"
    # norms / scalars / mix coefficients / conv weights: replicated.

    if fsdp and not inference:
        daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dsize = 1
        for a in daxes:
            dsize *= mesh.shape[a]
        numel = 1
        for s in shape:
            numel *= s
        if numel >= (1 << 20):                   # only shard big tensors
            for i in range(stacked, len(shape)):
                if parts[i] is None and shape[i] % dsize == 0:
                    parts[i] = daxes if len(daxes) > 1 else daxes[0]
                    break
    return P(*parts)


def param_specs(params: Any, mesh: Mesh, fsdp: bool = False,
                inference: bool = False) -> Any:
    """PartitionSpec pytree matching ``params`` (works on ShapeDtypeStructs).

    ``inference=True`` switches to the serving layout: dense weights are
    TP-sharded over `model` and replicated over `data` (no per-token FSDP
    gathers), MoE experts shard over `data`/(model,data) so dispatch moves
    activations, never weights."""
    def assign(path, leaf):
        return _spec_for(_path_names(path), tuple(leaf.shape), mesh, fsdp,
                         inference)
    return jax.tree_util.tree_map_with_path(assign, params)


def named_shardings(specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------


def batch_spec(mesh: Mesh) -> P:
    """[B, T, ...] host batch: B over (pod, data)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return P(axes)


def cache_specs(cache: Any, mesh: Mesh) -> Any:
    """Decode-cache sharding: batch dims over (pod, data); the KV length
    dim over `model` (decode-time context parallelism — scores reduce with
    tiny collectives instead of replicating multi-GB caches)."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def assign(path, leaf):
        names = _path_names(path)
        shape = tuple(leaf.shape)
        parts: list[Any] = [None] * len(shape)
        leaf_name = names[-1] if names else ""
        if leaf_name in ("k", "v") and len(shape) >= 4:
            # [L?, B, max_len, KV, hd]
            b_idx = len(shape) - 4
            if shape[b_idx] % _mesh_size(mesh, dp) == 0:
                parts[b_idx] = dp if len(dp) > 1 else dp[0]
            if shape[b_idx + 1] % mesh.shape.get("model", 1) == 0:
                parts[b_idx + 1] = "model"
        elif leaf_name in ("wkv", "ssm") and len(shape) >= 4:
            # [L, B, H, dk, dv]: batch over dp, heads over model
            if shape[1] % _mesh_size(mesh, dp) == 0:
                parts[1] = dp if len(dp) > 1 else dp[0]
            if shape[2] % mesh.shape.get("model", 1) == 0:
                parts[2] = "model"
        elif leaf_name in ("shift_tm", "shift_cm", "conv") and len(shape) >= 2:
            if shape[1] % _mesh_size(mesh, dp) == 0:
                parts[1] = dp if len(dp) > 1 else dp[0]
        elif leaf_name == "enc_out" and len(shape) == 3:
            if shape[0] % _mesh_size(mesh, dp) == 0:
                parts[0] = dp if len(dp) > 1 else dp[0]
        return P(*parts)

    return jax.tree_util.tree_map_with_path(assign, cache)


# ---------------------------------------------------------------------------
# Contraction-plan sharding (SPMD execution of CSSE plans)
# ---------------------------------------------------------------------------

#: The network axis every phase network (FP/BP/WG/dW) uses for the token
#: batch — the one axis the default rules distribute.  FP/BP keep it in the
#: output (pure batch parallelism, no collective); the WG and dW networks
#: contract it, so their shards hold partial sums that a deferred ``psum``
#: reduces — the butterfly-reduction analog.
CONTRACTION_BATCH_AXIS: AxisId = "b"


def _part(axes: tuple[str, ...]):
    return axes if len(axes) > 1 else axes[0]


def resolve_batch_axes(mesh: Mesh,
                       batch_axes: Sequence[str] | None = None
                       ) -> tuple[str, ...]:
    """Mesh axes the contraction batch axis distributes over.

    ``batch_axes`` overrides the activation rules table's
    ``DEFAULT_RULES["batch"]`` (``pod``+``data``); either way the result is
    filtered to axes the mesh actually has.  The single source of truth for
    both the executor layout (:func:`plan_axis_sharding`) and the CSSE cost
    mirror (``TNNConfig.mesh_spec``) — they must never disagree.
    """
    want = tuple(batch_axes) if batch_axes else DEFAULT_RULES["batch"]
    return _axes_in(mesh, want)


def plan_axis_sharding(net: TensorNetwork, mesh: Mesh | None,
                       batch_axes: Sequence[str] | None = None
                       ) -> dict[AxisId, tuple[str, ...]]:
    """Default network-axis -> mesh-axes assignment for a contraction plan.

    Reuses the activation rules table: the batch axis ``b`` distributes over
    ``DEFAULT_RULES["batch"]`` (``pod``+``data``) unless ``batch_axes``
    overrides the target (``train --tnn-mesh data,model`` lands here).  The
    same divisibility guard as :func:`make_sharder` applies — an axis the
    mesh cannot split evenly is replicated, never an error — so one layer
    code path serves every (mesh, batch) combination.
    """
    if mesh is None:
        return {}
    axes = resolve_batch_axes(mesh, batch_axes)
    size = _mesh_size(mesh, axes)
    b = CONTRACTION_BATCH_AXIS
    if (not axes or size <= 1 or b not in net.sizes
            or net.sizes[b] % size != 0):
        return {}
    return {b: axes}


def _sharding_from_specs(net: TensorNetwork, mesh: Mesh,
                         in_specs: Sequence[P]
                         ) -> dict[AxisId, tuple[str, ...]]:
    """Derive (and validate) the axis->mesh-axes map behind explicit specs.

    Every node holding a sharded network axis must shard it over the same
    mesh axes — anything else would make per-shard contraction incorrect —
    and sharded sizes must divide.
    """
    assert len(in_specs) == net.num_nodes, (
        f"need one PartitionSpec per input node: got {len(in_specs)} "
        f"for {net.num_nodes}")
    sharding: dict[AxisId, tuple[str, ...]] = {}
    for i, spec in enumerate(in_specs):
        parts = tuple(spec) + (None,) * (len(net.nodes[i]) - len(tuple(spec)))
        for axis, part in zip(net.nodes[i], parts):
            got = (part if isinstance(part, tuple)
                   else (part,)) if part is not None else ()
            got = tuple(a for a in got if a is not None)
            prev = sharding.get(axis)
            if prev is not None:
                assert prev == got, (
                    f"axis {axis!r} sharded as {prev} on one node and "
                    f"{got} on node {net.node_names[i]} — all holders of "
                    "a network axis must agree")
            sharding[axis] = got
    out = {}
    used: dict[str, AxisId] = {}
    for axis, axes in sharding.items():
        if not axes:
            continue
        size = _mesh_size(mesh, axes)
        assert net.sizes[axis] % size == 0, (
            f"axis {axis!r} of size {net.sizes[axis]} does not divide "
            f"over mesh axes {axes} (size {size})")
        for m in axes:
            assert m not in used, (
                f"mesh axis {m!r} shards both network axes {used[m]!r} "
                f"and {axis!r} — distinct network axes need disjoint mesh "
                "axes (shards would pair different blocks and the psum "
                "would mix outputs)")
            used[m] = axis
        out[axis] = axes
    return out


def mesh_spec(mesh: Mesh | None,
              axis_sharding: Mapping[AxisId, Sequence[str]] | None = None
              ) -> perf_model.MeshSpec | None:
    """The pure costing mirror of a live mesh (+ sharding intent).

    Feeds ``SearchOptions.mesh`` so CSSE stage-2 ranks per-device
    compute+memory plus the collective term, and enters the CSSE disk-cache
    signature (mesh shape, per-axis assignment, device kind, device count).
    """
    if mesh is None:
        return None
    sharding = {} if axis_sharding is None else axis_sharding
    return perf_model.MeshSpec(
        axes=tuple((str(n), int(mesh.shape[n])) for n in mesh.axis_names),
        axis_sharding=tuple(sorted(
            (a, tuple(ax)) for a, ax in sharding.items())),
        device_kind=jax.devices()[0].device_kind)


@dataclasses.dataclass(frozen=True)
class ShardedPlan:
    """Everything ``contraction.execute`` needs to run one plan SPMD."""

    axis_sharding: tuple[tuple[AxisId, tuple[str, ...]], ...]
    in_specs: tuple[P, ...]           # one per input node
    out_spec: P                       # network output layout
    psum_axes: tuple[str, ...]        # deferred reduction (empty for FP/BP)
    spec: perf_model.MeshSpec         # the costing mirror
    local_plan: ContractionPlan       # what every shard executes
    factors: tuple[tuple[AxisId, int], ...] = ()   # global-axis split ways


def shard_plan(plan: ContractionPlan, mesh: Mesh | None,
               in_specs: Sequence[P] | None = None,
               batch_axes: Sequence[str] | None = None
               ) -> ShardedPlan | None:
    """Lay a contraction plan out over ``mesh``; None if nothing shards.

    With explicit ``in_specs`` the axis assignment is derived (and
    validated) from them; otherwise :func:`plan_axis_sharding` picks the
    default batch-parallel layout.  Mesh axes that split a *contracted*
    network axis become ``psum_axes``: each shard's local contraction then
    yields a partial sum, exact by multilinearity, reduced once at the end
    (cheapest placement — the final output is the smallest partial-carrying
    tensor).
    """
    if mesh is None:
        return None
    net = plan.network
    if in_specs is not None:
        axis_sharding = _sharding_from_specs(net, mesh, in_specs)
    else:
        axis_sharding = plan_axis_sharding(net, mesh, batch_axes)
    if not axis_sharding:
        return None
    in_specs = tuple(
        P(*[_part(axis_sharding[a]) if a in axis_sharding else None
            for a in node])
        for node in net.nodes)
    out_spec = P(*[_part(axis_sharding[a]) if a in axis_sharding else None
                   for a in net.output])
    out_set = set(net.output)
    psum_axes = tuple(ax for a, axes in sorted(axis_sharding.items())
                      if a not in out_set for ax in axes)
    spec = mesh_spec(mesh, axis_sharding)
    return ShardedPlan(
        axis_sharding=tuple(sorted(axis_sharding.items())),
        in_specs=in_specs, out_spec=out_spec, psum_axes=psum_axes,
        spec=spec, local_plan=perf_model.localize_plan(plan, spec),
        factors=tuple(sorted(spec.factors(net).items())))


def overlapped_psum(x: jax.Array, axes: Sequence[str],
                    num_chunks: int = 4) -> jax.Array:
    """Deferred partial-sum reduction, chunked to overlap with compute.

    The WG phase's one deferred ``psum`` is a single bulk collective at
    the very end of the per-shard plan — nothing for the scheduler to
    hide it behind.  Splitting the output along its leading dim into
    ``num_chunks`` independent ``psum``\\ s gives XLA's latency-hiding
    scheduler chunk boundaries at which reduction traffic can interleave
    with the tail of the megakernel chain still producing later rows —
    the mesh-collective analog of FETTA overlapping its butterfly
    reduction network with PE-array compute.

    Bitwise-identical to the single ``psum``: each chunk reduces exactly
    the same addends in the same order (``psum`` of a concatenation is
    the concatenation of per-chunk ``psum``\\ s).  Falls back to the
    plain collective when the output is a scalar, has a leading dim the
    chunk count does not divide, or ``num_chunks <= 1``.
    """
    axes = tuple(axes)
    if not axes:
        return x
    if (x.ndim == 0 or num_chunks <= 1
            or x.shape[0] % num_chunks != 0):
        return jax.lax.psum(x, axes)
    chunks = jnp.split(x, num_chunks, axis=0)
    return jnp.concatenate([jax.lax.psum(c, axes) for c in chunks],
                           axis=0)
