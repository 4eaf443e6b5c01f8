"""TensorizedLinear — the paper's technique as a composable JAX layer.

A drop-in replacement for ``y = x @ W.T`` where ``W[M, N]`` is stored as
TT / TTM / TR / HT / BT factor cores.  The training-specific contribution of
the paper (§III-A, §IV) is realised through ``jax.custom_vjp``:

* **FP** runs the CSSE-optimal sequence for the forward network
  ``Y[b,m..] = X[b,n..] · cores``.
* **BP** (dX) and **WG** (one network per core gradient) are *different*
  tensor networks over the same cores; each gets its own CSSE search instead
  of inheriting the autodiff transpose of the forward plan.  This is what
  "training support" means in the paper — FP/BP/WG have different optimal
  dataflows, and reusing the FP sequence for backward is exactly the
  inefficiency Fig. 5/6 profiles.

Set ``phase_paths=False`` to fall back to plain autodiff through the forward
plan — that is the ablation baseline benchmarked in
``benchmarks/bench_phase_paths.py``.

Searches run at trace time on static shapes and are memoised process-wide
(and on disk), so a jitted train step pays them once per distinct
(batch, layer-signature) pair.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import contraction, csse, factorizations, perf_model
from repro.core.factorizations import Factorization
from repro.core.tnetwork import TensorNetwork
from repro.memory.stash import STORE, StashPolicy, stash, stashed_amax, unstash
from repro.precision.policy import (
    AMAX_KEY, QuantPolicy, amax_of, scale_from_history,
)

# AMAX_KEY (re-exported from repro.precision.policy) names the params
# entry holding the delayed-scaling amax history of a quantized layer:
# f32 ``[2 + num_cores, amax_history_len]``, row 0 = x, row 1 = dy,
# rows 2+i = core i.  Updated through the gradient channel (the custom-vjp
# bwd returns ``hist - new_hist`` and the optimizer applies ``p - g`` to
# this key — see ``optim/adamw.py``), so the history advances once per
# training step with no side-channel state.


@dataclass(frozen=True)
class TNNConfig:
    """Config block attached to architecture configs (``cfg.tnn``)."""

    enabled: bool = False
    method: str = "tt"                    # tt|ttm|tr|ht|bt
    rank: int = 16
    num_factors: int = 3                  # how many factors to split M/N into
    targets: tuple[str, ...] = ("mlp",)   # which projections to tensorize
    phase_paths: bool = True              # per-phase CSSE (paper) vs autodiff
    objective: str = "edp"                # CSSE stage-2 metric
    fused_chain: bool = True              # model VMEM-resident chaining
    num_blocks: int = 2                   # BT only
    backend: str = "einsum"               # contraction executor: einsum|pallas
    autotune: bool = False                # measured stage-2 + tuned tiles
    mesh: Any = None                      # jax Mesh: SPMD contraction exec
                                          # (runtime-injected by the trainer,
                                          # never a checked-in config value)
    mesh_axes: tuple[str, ...] | None = None
                                          # mesh axes the contraction batch
                                          # shards over (None = pod+data;
                                          # `train --tnn-mesh data,model`)
    precision: QuantPolicy = QuantPolicy()
                                          # quantized contraction execution
                                          # (fp8_e4m3 | fp8_e5m2 | int8 with
                                          # delayed scaling); the bf16
                                          # default is the historical path.
                                          # `train --tnn-precision fp8`
    remat: str = "store"                  # activation stash policy of the
                                          # custom-vjp: store | recompute |
                                          # quantized[:dtype] (repro.memory.
                                          # StashPolicy; `train --tnn-remat
                                          # quantized`, docs/MEMORY.md)
    memory_budget: int | None = None      # bytes: CSSE stage-2 peak-
                                          # footprint constraint per plan +
                                          # the trainer's stash/microbatch
                                          # planner envelope
                                          # (`train --tnn-memory-budget`)
    phase: str = ""                       # execution-phase cache tag ("" =
                                          # training).  Serving builds one
                                          # model per phase ("prefill" /
                                          # "decode", repro.serving.
                                          # profiles): the tag rides into
                                          # SearchOptions and every CSSE/
                                          # autotune signature, so each
                                          # phase resolves its own plans
                                          # and tile winners.  Params are
                                          # phase-independent (the tag
                                          # never touches init).

    def stash_policy(self) -> StashPolicy:
        return StashPolicy.parse(self.remat)

    def execution_policy(self, compute_dtype=None) -> "ExecutionPolicy":
        """The unified :class:`repro.core.policy.ExecutionPolicy` this
        config describes — the construction hub every planning consumer
        (CSSE options, tuner grids, serving profiles, the joint search)
        derives from.

        Autotuning swaps the analytic stage-2 objective for measured step
        costs (repro.core.autotune); the executor side additionally gets
        tuned tile configs when backend == "pallas".  measure_dtype
        follows the layer's compute dtype so the tuner times (and caches)
        exactly the kernels the executor will run.  With a mesh attached,
        stage 2 turns communication-aware (the MeshSpec mirror rides in
        the policy); a quantized precision policy turns it
        precision-aware; the stash axis and memory budget feed the joint
        search's feasibility check (repro.core.search).
        """
        from repro.core.policy import ExecutionPolicy
        if self.precision.quantized:
            dtype = jnp.dtype(self.precision.operand_dtype).name
        else:
            dtype = jnp.dtype(compute_dtype or jnp.bfloat16).name
        return ExecutionPolicy(
            objective="measured" if self.autotune else self.objective,
            fused_chain=self.fused_chain,
            measure_dtype=dtype,
            mesh=self.mesh_spec(),
            precision=self.precision,
            stash=self.stash_policy(),
            memory_budget=self.memory_budget,
            phase=self.phase)

    def search_options(self, compute_dtype=None) -> csse.SearchOptions:
        """Legacy CSSE view of :meth:`execution_policy` (same axes)."""
        return csse.SearchOptions.from_policy(
            self.execution_policy(compute_dtype))

    def mesh_spec(self):
        """The costing MeshSpec for this config's mesh (None off-mesh)."""
        if self.mesh is None:
            return None
        from repro.distributed import sharding as shlib
        axes = shlib.resolve_batch_axes(self.mesh, self.mesh_axes)
        return shlib.mesh_spec(
            self.mesh, {shlib.CONTRACTION_BATCH_AXIS: axes} if axes else {})


# ---------------------------------------------------------------------------
# Gradient networks
# ---------------------------------------------------------------------------


def _bp_network(fact: Factorization, batch: int) -> TensorNetwork:
    """dX[b, n..] = sum_m dY[b, m..] * W[m.., n..]."""
    s, t = len(fact.out_dims), len(fact.in_dims)
    sizes = dict(fact.sizes)
    sizes["b"] = batch
    dy_axes = ("b",) + tuple(f"m{i}" for i in range(s))
    out = ("b",) + tuple(f"n{j}" for j in range(t))
    return TensorNetwork(sizes=sizes, nodes=(dy_axes,) + fact.core_axes,
                         node_names=("dY",) + fact.core_names, output=out)


def _wg_network(fact: Factorization, batch: int, core_idx: int
                ) -> TensorNetwork:
    """dG_i = contraction of {X, dY, cores j != i} with output = core i axes.

    Valid because W is multilinear in its cores:
    dL/dG_i = d(sum_b X_b dY_b : W)/dG_i contracted through the other cores.
    """
    s, t = len(fact.out_dims), len(fact.in_dims)
    sizes = dict(fact.sizes)
    sizes["b"] = batch
    x_axes = ("b",) + tuple(f"n{j}" for j in range(t))
    dy_axes = ("b",) + tuple(f"m{i}" for i in range(s))
    nodes = [x_axes, dy_axes]
    names = ["X", "dY"]
    for j, (nm, ax) in enumerate(zip(fact.core_names, fact.core_axes)):
        if j != core_idx:
            nodes.append(ax)
            names.append(nm)
    return TensorNetwork(sizes=sizes, nodes=tuple(nodes), node_names=tuple(names),
                         output=fact.core_axes[core_idx])


def _dw_network(fact: Factorization, batch: int) -> TensorNetwork:
    """Shared WG intermediate: dW[m.., n..] = sum_b X[b,n..] dY[b,m..]."""
    s, t = len(fact.out_dims), len(fact.in_dims)
    sizes = dict(fact.sizes)
    sizes["b"] = batch
    x_axes = ("b",) + tuple(f"n{j}" for j in range(t))
    dy_axes = ("b",) + tuple(f"m{i}" for i in range(s))
    out = tuple(f"m{i}" for i in range(s)) + tuple(f"n{j}" for j in range(t))
    return TensorNetwork(sizes=sizes, nodes=(x_axes, dy_axes),
                         node_names=("X", "dY"), output=out)


def _wg_from_dw_network(fact: Factorization, core_idx: int) -> TensorNetwork:
    """dG_i from the stashed dW: contraction of {dW, cores j != i}."""
    s, t = len(fact.out_dims), len(fact.in_dims)
    dw_axes = tuple(f"m{i}" for i in range(s)) + tuple(
        f"n{j}" for j in range(t))
    nodes = [dw_axes]
    names = ["dW"]
    for j, (nm, ax) in enumerate(zip(fact.core_names, fact.core_axes)):
        if j != core_idx:
            nodes.append(ax)
            names.append(nm)
    return TensorNetwork(sizes=dict(fact.sizes), nodes=tuple(nodes),
                         node_names=tuple(names),
                         output=fact.core_axes[core_idx])


# ---------------------------------------------------------------------------
# Plan cache (per layer signature x batch)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _plans(fact: Factorization, batch: int, opts: csse.SearchOptions,
           hw: perf_model.HardwareModel = perf_model.TPU_V5E):
    """FP/BP plans plus the cheaper of two WG strategies:

    * ``indep``  — one CSSE network per core gradient over {X, dY, others}
      (recompute everything; memory-minimal);
    * ``shared`` — stash dW = X·dY once, then per-core contractions over
      {dW, others}: the paper's "store intermediates for WG" policy (§III),
      which amortises the batch-sized contraction across all d cores.

    Selection is by total modeled latency — CSSE's stage-2 cost decides the
    stash policy, per layer and batch size.
    """
    fp = csse.search(fact.forward_network(batch_axes=(("b", batch),)), opts,
                     hw)
    bp = csse.search(_bp_network(fact, batch), opts, hw)
    wg_indep = tuple(csse.search(_wg_network(fact, batch, i), opts, hw)
                     for i in range(fact.num_cores))
    dw = csse.search(_dw_network(fact, batch), opts, hw)
    wg_shared = tuple(csse.search(_wg_from_dw_network(fact, i), opts, hw)
                      for i in range(fact.num_cores))
    cost_indep = sum(w.cost.latency_s for w in wg_indep)
    cost_shared = dw.cost.latency_s + sum(w.cost.latency_s
                                          for w in wg_shared)
    if cost_shared < cost_indep:
        wg = ("shared", dw, wg_shared)
    else:
        wg = ("indep", None, wg_indep)
    return fp, bp, wg


def layer_cost(fact: Factorization, batch: int,
               opts: csse.SearchOptions | None = None,
               hw: perf_model.HardwareModel = perf_model.TPU_V5E
               ) -> dict[str, perf_model.PlanCost]:
    """Modeled FP/BP/WG cost of one tensorized layer (benchmark helper)."""
    opts = opts or csse.SearchOptions()
    fp, bp, (wg_kind, dw, wg) = _plans(fact, batch, opts, hw)
    results = ([dw] if wg_kind == "shared" else []) + list(wg)
    ev = lambda r: perf_model.evaluate(  # noqa: E731
        r.plan, hw, fused_chain=opts.fused_chain, mesh=opts.mesh,
        policy=opts.policy)
    fp_c, bp_c = ev(fp), ev(bp)
    wg_cs = [ev(r) for r in results]
    return {"fp": fp_c, "bp": bp_c,
            "wg": perf_model.PlanCost(
                latency_s=sum(c.latency_s for c in wg_cs),
                energy_j=sum(c.energy_j for c in wg_cs),
                flops=sum(c.flops for c in wg_cs),
                bytes_hbm=sum(c.bytes_hbm for c in wg_cs),
                bytes_ici=sum(c.bytes_ici for c in wg_cs),
                collective_s=sum(c.collective_s for c in wg_cs),
                # WG contractions run one after another with frees in
                # between: the group's working-set peak is the worst
                # single plan, not the sum.
                peak_bytes=max((c.peak_bytes for c in wg_cs), default=0))}


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TensorizedLinear:
    """``x[..., N] -> y[..., M]`` with W factorized per ``fact``."""

    fact: Factorization
    use_bias: bool = False
    phase_paths: bool = True
    opts: csse.SearchOptions = csse.SearchOptions()
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16
    backend: str = "einsum"              # plan executor: einsum|pallas
    autotune: bool = False               # tuned tiles on the pallas executor
    mesh: Any = None                     # jax Mesh: shard_map every phase
    mesh_axes: tuple[str, ...] | None = None   # batch-axis mesh targets
    precision: QuantPolicy = QuantPolicy()     # fp8/int8 quantized execution
    remat: StashPolicy = STORE           # fwd->bwd activation stash policy

    # -- params -------------------------------------------------------------

    def init(self, key: jax.Array) -> dict:
        std = self.fact.init_std(1.0 / math.sqrt(self.fact.N))
        keys = jax.random.split(key, self.fact.num_cores)
        cores = tuple(
            (jax.random.normal(k, self.fact.core_shape(i), jnp.float32) * std
             ).astype(self.param_dtype)
            for i, k in enumerate(keys))
        params = {"cores": cores}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.fact.M,), self.param_dtype)
        if self.precision.quantized:
            # Delayed-scaling state: one amax-history row per quantized
            # tensor role (x, dy, each core); all-zero = bootstrap from the
            # current tensor on the first step.
            params[AMAX_KEY] = jnp.zeros(
                (2 + self.fact.num_cores, self.precision.amax_history_len),
                jnp.float32)
        return params

    def _tuner(self):
        if not (self.autotune and self.backend == "pallas"):
            return None
        from repro.core import autotune
        return autotune.default_tuner()

    def dense_weight(self, params: dict) -> jax.Array:
        """Reconstruct W[M, N] (tests / export / Scheme-2 baseline)."""
        net = self.fact.weight_network()
        res = csse.search(net, self.opts)
        # No mesh: the weight network has no batch axis to distribute.
        w = contraction.execute(res.plan, [c.astype(jnp.float32)
                                           for c in params["cores"]],
                                backend=self.backend,
                                fused_chain=self.opts.fused_chain,
                                tuner=self._tuner())
        return w.reshape(self.fact.M, self.fact.N)

    # -- forward ------------------------------------------------------------

    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        *lead, n = x.shape
        assert n == self.fact.N, f"input dim {n} != {self.fact.N}"
        batch = math.prod(lead) if lead else 1
        xt = x.reshape((batch,) + tuple(self.fact.in_dims))
        xt = xt.astype(self.compute_dtype)
        cores = tuple(c.astype(self.compute_dtype) for c in params["cores"])
        if self.precision.quantized and self.phase_paths:
            # Quantized execution with delayed scaling; a params dict
            # without the amax entry (e.g. a pre-precision checkpoint)
            # falls back to a zero history = just-in-time scales, and the
            # history "gradient" lands on a constant, where jax drops it.
            hist = params.get(AMAX_KEY, jnp.zeros(
                (2 + self.fact.num_cores, self.precision.amax_history_len),
                jnp.float32))
            y = _tnn_apply_q(self.fact, self.opts, self.backend,
                             self.autotune, self.mesh, self.mesh_axes,
                             self.precision, self.remat, xt, hist, *cores)
        elif self.phase_paths:
            y = _tnn_apply(self.fact, self.opts, self.backend,
                           self.autotune, self.mesh, self.mesh_axes,
                           self.remat, xt, *cores)
        else:
            fp, _, _ = _plans(self.fact, batch, self.opts)
            policy = (self.precision if self.precision.quantized else None)
            y = contraction.execute(fp.plan, [xt, *cores],
                                    backend=self.backend,
                                    fused_chain=self.opts.fused_chain,
                                    tuner=self._tuner(),
                                    mesh=self.mesh,
                                    mesh_batch_axes=self.mesh_axes,
                                    policy=policy)
        y = y.reshape(tuple(lead) + (self.fact.M,))
        if self.use_bias:
            y = y + params["bias"].astype(self.compute_dtype)
        return y.astype(x.dtype)


# custom_vjp core: functional over (x, *cores) so jax sees the cores as
# differentiable leaves.  fact/opts/backend/autotune/mesh are static
# (nondiff) arguments; backend routes every phase plan (FP here, BP/WG in
# the bwd rule) through the einsum reference or the Pallas plan compiler,
# autotune swaps the compiler's fixed tile defaults for measured winners,
# and mesh shard_maps every phase: FP/BP batch-parallel, WG/dW
# contraction-split with the deferred-psum gradient reduction.


def _exec_tuner(backend: str, autotune_flag: bool):
    if not (autotune_flag and backend == "pallas"):
        return None
    from repro.core import autotune
    return autotune.default_tuner()


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6))
def _tnn_apply(fact: Factorization, opts: csse.SearchOptions, backend: str,
               autotune_flag: bool, mesh, mesh_axes, remat: StashPolicy,
               x: jax.Array, *cores: jax.Array) -> jax.Array:
    fp, _, _ = _plans(fact, x.shape[0], opts)
    with jax.named_scope("tt_fp"):
        return contraction.execute(fp.plan, [x, *cores], backend=backend,
                                   fused_chain=opts.fused_chain,
                                   tuner=_exec_tuner(backend, autotune_flag),
                                   mesh=mesh, mesh_batch_axes=mesh_axes)


def _tnn_fwd(fact, opts, backend, autotune_flag, mesh, mesh_axes, remat,
             x, *cores):
    y = _tnn_apply(fact, opts, backend, autotune_flag, mesh, mesh_axes,
                   remat, x, *cores)
    # The stash policy decides what survives fwd->bwd: x as-is (store /
    # recompute — the latter is rematerialized by the model's per-layer
    # jax.checkpoint, so nothing here persists), or a quantized payload
    # (docs/MEMORY.md).  Cores are params — always alive, never "stash".
    return y, (stash(x, remat), cores)


def _tnn_bwd(fact, opts, backend, autotune_flag, mesh, mesh_axes, remat,
             res, dy):
    xres, cores = res
    x = unstash(xres, remat, cores[0].dtype if cores else dy.dtype)
    batch = x.shape[0]
    _, bp, (wg_kind, dw_res, wg) = _plans(fact, batch, opts)
    tuner = _exec_tuner(backend, autotune_flag)
    exec_kw = dict(backend=backend, fused_chain=opts.fused_chain,
                   tuner=tuner, mesh=mesh, mesh_batch_axes=mesh_axes)
    dy = dy.astype(x.dtype)
    with jax.named_scope("tt_bp"):
        dx = contraction.execute(bp.plan, [dy, *cores], **exec_kw)
    dcores = []
    with jax.named_scope("tt_wg"):
        if wg_kind == "shared":
            dw = contraction.execute(dw_res.plan, [x, dy], **exec_kw)
            for i, w in enumerate(wg):
                others = tuple(c for j, c in enumerate(cores) if j != i)
                # The wg-from-dW networks have no batch axis left: mesh
                # execution degenerates to the single-device path (dW was
                # already reduced).
                dcores.append(contraction.execute(w.plan, [dw, *others],
                                                  **exec_kw))
        else:
            for i, w in enumerate(wg):
                others = tuple(c for j, c in enumerate(cores) if j != i)
                dcores.append(contraction.execute(w.plan, [x, dy, *others],
                                                  **exec_kw))
    return (dx, *dcores)


_tnn_apply.defvjp(_tnn_fwd, _tnn_bwd)


# Quantized variant: same per-phase CSSE plans, executed under a
# QuantPolicy with *delayed scaling*.  The amax history rides as a
# differentiable argument purely to get a state-update channel: the bwd
# rule returns ``hist - new_hist`` as its "gradient", and the optimizer's
# quant_amax passthrough (``p - g``, see repro.optim.adamw) turns that
# into ``new_hist`` — the history advances exactly once per optimizer
# step, with no mutable side state and no change to the layer call
# signature.  Scales are genuinely non-differentiable (quantization is a
# straight-through identity at this granularity), so hijacking the
# cotangent loses nothing.


def _phase_scales(policy: QuantPolicy, hist, rows, tensors):
    """Delayed per-tensor scales for one phase's input nodes.

    ``rows[i]`` is the amax-history row backing ``tensors[i]`` (None =
    just-in-time, e.g. the stashed dW intermediate which has no
    cross-step identity).
    """
    out = []
    for row, t in zip(rows, tensors):
        if row is None:
            out.append(None)
        else:
            out.append(scale_from_history(hist[row], amax_of(t),
                                          policy.qmax, policy.margin))
    return out


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def _tnn_apply_q(fact: Factorization, opts: csse.SearchOptions, backend: str,
                 autotune_flag: bool, mesh, mesh_axes, policy: QuantPolicy,
                 remat: StashPolicy, x: jax.Array, amax_hist: jax.Array,
                 *cores: jax.Array) -> jax.Array:
    fp, _, _ = _plans(fact, x.shape[0], opts)
    core_rows = list(range(2, 2 + len(cores)))
    scales = _phase_scales(policy, amax_hist, [0] + core_rows, (x,) + cores)
    with jax.named_scope("tt_fp"):
        return contraction.execute(fp.plan, [x, *cores], backend=backend,
                                   fused_chain=opts.fused_chain,
                                   tuner=_exec_tuner(backend, autotune_flag),
                                   mesh=mesh, mesh_batch_axes=mesh_axes,
                                   policy=policy, input_scales=scales)


def _stash_policy_q(policy: QuantPolicy, remat: StashPolicy) -> StashPolicy:
    """Quantized-execution runs stash in the *execution* policy's dtype:
    the WG phase quantizes x with the same delayed scale anyway, so the
    stashed payload reproduces the executor's bits exactly (lossless vs
    ``store``) — the remat dtype only governs the bf16 path."""
    return StashPolicy(mode=remat.mode, dtype=policy.dtype)


def _tnn_q_fwd(fact, opts, backend, autotune_flag, mesh, mesh_axes, policy,
               remat, x, amax_hist, *cores):
    y = _tnn_apply_q(fact, opts, backend, autotune_flag, mesh, mesh_axes,
                     policy, remat, x, amax_hist, *cores)
    sp = _stash_policy_q(policy, remat)
    s_x = None
    if sp.quantized:
        # Pin the stash scale to the delayed scale the executor used, so
        # the backward's re-quantization of x-hat is bit-identical.
        s_x = scale_from_history(amax_hist[0], amax_of(x), policy.qmax,
                                 policy.margin)
    return y, (stash(x, sp, scale=s_x), amax_hist, cores)


def _tnn_q_bwd(fact, opts, backend, autotune_flag, mesh, mesh_axes, policy,
               remat, res, dy):
    xres, hist, cores = res
    sp = _stash_policy_q(policy, remat)
    x = unstash(xres, sp, cores[0].dtype if cores else dy.dtype)
    amax_x = stashed_amax(xres, x)
    batch = x.shape[0]
    _, bp, (wg_kind, dw_res, wg) = _plans(fact, batch, opts)
    exec_kw = dict(backend=backend, fused_chain=opts.fused_chain,
                   tuner=_exec_tuner(backend, autotune_flag), mesh=mesh,
                   mesh_batch_axes=mesh_axes, policy=policy)
    dy = dy.astype(x.dtype)
    core_rows = list(range(2, 2 + len(cores)))
    s_x = scale_from_history(hist[0], amax_x, policy.qmax, policy.margin)
    s_dy, *s_cores = _phase_scales(
        policy, hist, [1] + core_rows, (dy,) + cores)
    with jax.named_scope("tt_bp"):
        dx = contraction.execute(bp.plan, [dy, *cores],
                                 input_scales=[s_dy, *s_cores], **exec_kw)
    dcores = []
    with jax.named_scope("tt_wg"):
        if wg_kind == "shared":
            dw = contraction.execute(dw_res.plan, [x, dy],
                                     input_scales=[s_x, s_dy], **exec_kw)
            for i, w in enumerate(wg):
                others = tuple(c for j, c in enumerate(cores) if j != i)
                s_others = [s for j, s in enumerate(s_cores) if j != i]
                dcores.append(contraction.execute(
                    w.plan, [dw, *others], input_scales=[None, *s_others],
                    **exec_kw))
        else:
            for i, w in enumerate(wg):
                others = tuple(c for j, c in enumerate(cores) if j != i)
                s_others = [s for j, s in enumerate(s_cores) if j != i]
                dcores.append(contraction.execute(
                    w.plan, [x, dy, *others],
                    input_scales=[s_x, s_dy, *s_others], **exec_kw))
    # The state-update channel: roll every history row one step with this
    # step's observed amaxes and deliver the delta as the "gradient".
    # amax_x is the *forward* statistic (stashed exactly under a quantized
    # stash), so the delayed-scaling window never drifts with the stash.
    current = jnp.stack([amax_x, amax_of(dy)]
                        + [amax_of(c) for c in cores])
    new_hist = jnp.concatenate([current[:, None], hist[:, :-1]], axis=1)
    d_hist = hist - new_hist
    return (dx, d_hist, *dcores)


_tnn_apply_q.defvjp(_tnn_q_fwd, _tnn_q_bwd)


# ---------------------------------------------------------------------------
# Convenience constructor used by model configs
# ---------------------------------------------------------------------------


def make_tensorized_linear(out_features: int, in_features: int,
                           tnn: TNNConfig, use_bias: bool = False,
                           param_dtype=jnp.float32,
                           compute_dtype=jnp.bfloat16) -> TensorizedLinear:
    out_dims = factorizations.factorize_dim(out_features, tnn.num_factors)
    in_dims = factorizations.factorize_dim(in_features, tnn.num_factors)
    kw = {"num_blocks": tnn.num_blocks} if tnn.method == "bt" else {}
    fact = factorizations.make(tnn.method, out_dims, in_dims, tnn.rank, **kw)
    return TensorizedLinear(fact=fact, use_bias=use_bias,
                            phase_paths=tnn.phase_paths,
                            opts=tnn.search_options(compute_dtype),
                            param_dtype=param_dtype,
                            compute_dtype=compute_dtype,
                            backend=tnn.backend,
                            autotune=tnn.autotune,
                            mesh=tnn.mesh,
                            mesh_axes=tnn.mesh_axes,
                            precision=tnn.precision,
                            remat=tnn.stash_policy())
