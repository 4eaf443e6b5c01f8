"""End-to-end training driver: data -> sharded train loop -> checkpoints,
with watchdog, restart and elastic re-mesh.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1_1b --smoke \
      --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch rwkv6_7b --smoke --tnn \
      --steps 200
On a real pod the same entry point runs the full config (drop --smoke) under
the production mesh; on this host it uses the local device mesh.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import memory
from repro import telemetry as tm
from repro.checkpoint import store
from repro.checkpoint.manager import CheckpointManager
from repro.configs import base as cfgbase
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed import fault_tolerance as ft
from repro.distributed import sharding
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim.adamw import AdamW

_log = tm.get_logger("train")


def train(arch_id: str, *, smoke: bool, tnn: bool, steps: int,
          global_batch: int, seq_len: int, lr: float, ckpt_dir: str | None,
          ckpt_every: int, microbatches: int, production_mesh: bool,
          resume: bool = True, log_every: int = 10,
          tnn_backend: str | None = None,
          tnn_autotune: bool = False,
          tnn_mesh: str | None = None,
          tnn_precision: str | None = None,
          tnn_remat: str | None = None,
          tnn_memory_budget=None,
          tnn_search: str = "per-axis",
          tnn_pipeline: int | None = None,
          loss_scale: float = 1.0,
          trace_path: str | None = None,
          mesh=None) -> dict:
    """Train ``arch_id`` for ``steps`` steps; returns the loss history,
    per-step seconds (step 0 includes compilation) and the final state.
    ``mesh`` overrides the device mesh (default: every local device on
    the ``data`` axis, or the production mesh)."""
    # --tnn-trace: enable the telemetry tracer for this run (unless the
    # caller — or REPRO_TRACE — already did, in which case the run joins
    # the existing trace and does not own finalization).
    owns_trace = bool(trace_path) and not tm.enabled()
    if owns_trace:
        tm.configure(trace_path)
    arch = cfgbase.get(arch_id)
    if mesh is None:
        mesh = (make_production_mesh() if production_mesh
                else make_host_mesh())
    tnn_cfg = arch.tnn_default if tnn else None
    if tnn_cfg is not None and tnn_backend is not None:
        tnn_cfg = dataclasses.replace(tnn_cfg, backend=tnn_backend)
    if tnn_cfg is not None and tnn_autotune:
        # Autotuning implies the pallas executor (tile choices only exist
        # there) unless the caller explicitly pinned a backend.
        backend = tnn_backend or "pallas"
        tnn_cfg = dataclasses.replace(tnn_cfg, autotune=True,
                                      backend=backend)
    if tnn_cfg is not None and tnn_mesh:
        # SPMD contraction execution: every tensorized phase (FP/BP/WG)
        # shard_maps over the train mesh, with the contraction batch axis
        # distributed over the named mesh axes, and the per-phase CSSE
        # searches turn communication-aware for that layout.
        axes = tuple(a.strip() for a in tnn_mesh.split(",") if a.strip())
        unknown = [a for a in axes if a not in mesh.axis_names]
        if unknown:
            raise SystemExit(f"--tnn-mesh axes {unknown} not in mesh "
                             f"{mesh.axis_names}")
        tnn_cfg = dataclasses.replace(tnn_cfg, mesh=mesh, mesh_axes=axes)
    if tnn_cfg is not None and tnn_precision:
        # Quantized contraction execution (fp8/int8 with delayed scaling):
        # both executors run under the policy, CSSE prices every phase at
        # the policy's byte widths, and the layers carry amax history.
        from repro.precision import QuantPolicy
        tnn_cfg = dataclasses.replace(
            tnn_cfg, precision=QuantPolicy.parse(tnn_precision))
    budget = memory.parse_budget(tnn_memory_budget)
    if tnn_cfg is not None and tnn_remat:
        # Activation stash policy of every tensorized custom-vjp:
        # store (default) | recompute | quantized[:dtype].  Parsed here so
        # a bad flag fails before any compilation, and the *normalized*
        # tag is stored so downstream string comparisons (build_model's
        # recompute gate) can never miss a case/whitespace variant.
        tnn_cfg = dataclasses.replace(
            tnn_cfg, remat=memory.StashPolicy.parse(tnn_remat).tag())
    if tnn_cfg is not None and budget is not None:
        # The budget constrains both levels: CSSE stage-2 rejects plans
        # whose modeled live-tensor peak exceeds it, and the stash planner
        # below fits the per-step activation stash by microbatching.
        tnn_cfg = dataclasses.replace(tnn_cfg, memory_budget=budget)
    if tnn_cfg is not None and tnn_search == "joint":
        # Cross-layer joint plan search (repro.core.search, docs/SEARCH.md):
        # the per-axis flags above form the *base* ExecutionPolicy; the
        # joint loop then re-searches the contraction sequence under every
        # (fusion x precision x stash) combo and the winning combo
        # overrides those axes — which is the point: jointly-optimal plans
        # can disagree with any per-axis flag choice.
        from repro.core import factorizations as _facts
        from repro.core import search as _jsearch
        probe_cfg = arch.smoke(tnn_cfg) if smoke else arch.model(tnn_cfg)
        dims = _facts.factorize_dim(probe_cfg.d_model, tnn_cfg.num_factors)
        kw = {"num_blocks": tnn_cfg.num_blocks} if tnn_cfg.method == "bt" \
            else {}
        fact = _facts.make(tnn_cfg.method, dims, dims, tnn_cfg.rank, **kw)
        base = tnn_cfg.execution_policy()
        if base.objective == "measured":
            # Startup search stays model-scored; the measured rerank still
            # happens per-layer at trace time under the chosen combo.
            base = dataclasses.replace(base, objective="latency")
        res = _jsearch.joint_search(
            fact.forward_network((("b", global_batch * seq_len),)), base)
        win = res.best.policy
        tnn_cfg = dataclasses.replace(
            tnn_cfg, fused_chain=win.fused_chain, precision=win.precision,
            remat=win.stash.tag())
        _log.info(f"joint plan search: fused_chain={win.fused_chain} "
                  f"precision={win.precision.tag} stash={win.stash.tag()}"
                  f"{' (flipped vs per-axis)' if res.flipped else ''}")
    model, cfg = steps_lib.build_model(arch, tnn=tnn_cfg, smoke=smoke)
    shard = sharding.make_sharder(mesh)

    mem_probe = None
    if tnn_cfg is not None and hasattr(cfg, "num_layers"):
        stash_policy = tnn_cfg.stash_policy()
        # Data-parallel factor of the host batch, derived from the same
        # batch_spec the trainer lays data out with: each device stashes
        # only its batch slice, keeping planner numbers in the same
        # per-device units as the CSSE budget.
        batch_axes = sharding.batch_spec(mesh)[0] or ()
        if isinstance(batch_axes, str):
            batch_axes = (batch_axes,)
        dp = 1
        for a in batch_axes:
            dp *= mesh.shape[a]
        if budget is not None:
            planned, report = memory.plan_microbatches(
                cfg, global_batch, seq_len, budget, stash_policy,
                at_least=microbatches, shards=dp)
            if planned != microbatches:
                _log.info(f"memory planner: budget "
                          f"{memory.format_bytes(budget)} -> "
                          f"{planned} microbatches "
                          f"(stash {memory.format_bytes(report.peak_bytes)})")
                microbatches = planned
        mem_probe = memory.probe_training(cfg, global_batch, seq_len,
                                          microbatches, stash_policy,
                                          shards=dp)
        _log.info(f"activation stash [{stash_policy.tag()}]: "
                  f"{memory.format_bytes(mem_probe.peak_bytes)}/device "
                  f"({mem_probe.source})")
        tm.sample("train.peak_activation_bytes", mem_probe.peak_bytes)

    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=seq_len, global_batch=global_batch,
        embed_dim=cfg.d_model if arch.input_kind == "embeds" else None))

    opt = AdamW(lr=lr, total_steps=max(steps, 2), warmup_steps=min(20, steps),
                loss_scale=loss_scale)
    params = model.init(jax.random.key(0))
    state = {"params": params, "opt": opt.init(params)}

    pspecs = sharding.param_specs(jax.eval_shape(lambda: state["params"]),
                                  mesh)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    state_shard = {"params": pshard,
                   "opt": type(state["opt"])(m=pshard, v=pshard,
                                             step=NamedSharding(mesh, P()))}
    state = jax.device_put(state, state_shard)
    bspec = NamedSharding(mesh, sharding.batch_spec(mesh))

    pipe_step = None
    if tnn_pipeline is not None and tnn_pipeline > 1:
        # --tnn-pipeline: 1F1B staged execution of the layer stack
        # (docs/DISTRIBUTED.md).  The pipeline step is eager orchestration
        # over per-stage jits — same (state, batch) -> (state, metrics)
        # contract, so the loop below is unchanged; each step additionally
        # records a modeled-vs-measured bubble report through the
        # telemetry drift channel.
        from repro.distributed import pipeline as pipe_lib
        if not hasattr(model, "apply_layers"):
            raise SystemExit(
                f"--tnn-pipeline: arch {arch_id!r} ({type(model).__name__}) "
                f"has no stage-partitionable layer stack")
        mb = max(microbatches, tnn_pipeline)
        if mb != microbatches:
            _log.info(f"pipeline: raising microbatches {microbatches} -> "
                      f"{mb} (>= stages keeps the 1F1B bubble bounded)")
            microbatches = mb
        pipe_step = pipe_lib.make_pipeline_train_step(
            model, opt, shard, num_stages=tnn_pipeline,
            microbatches=microbatches)
        step_fn = pipe_step
    else:
        step_fn = jax.jit(
            steps_lib.make_train_step(model, opt, shard,
                                      microbatches=microbatches),
            in_shardings=(state_shard, None), donate_argnums=0)

    manager = (CheckpointManager(ckpt_dir, every=ckpt_every)
               if ckpt_dir else None)
    start = 0
    if ckpt_dir and resume and store.latest_step(ckpt_dir) is not None:
        start, state = store.restore(ckpt_dir, state, shardings=state_shard)
        _log.info(f"resumed from step {start}")

    watchdog = ft.StepWatchdog()
    history, step_s = [], []
    t_start = time.time()
    for step in range(start, steps):
        # Per-step phase breakdown: one train.step span with data-load
        # and step-fn (dispatch + the blocking loss fetch) children.
        with tm.span("train.step", step=step):
            with tm.span("train.data"):
                batch = data.batch(step)
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
            t0 = time.time()
            with tm.span("train.step_fn"):
                state, metrics = step_fn(state, batch)
                loss = float(metrics["loss"])
            dur = time.time() - t0
        watchdog.observe(step, dur)
        history.append(loss)
        step_s.append(dur)
        if manager:
            with tm.span("train.checkpoint", step=step):
                manager.maybe_save(step + 1, state)
        if step % log_every == 0 or step == steps - 1:
            tok_s = global_batch * seq_len / max(dur, 1e-9)
            _log.info(f"step {step:5d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"lr {float(metrics['lr']):.2e} {dur*1e3:7.1f}ms "
                      f"({tok_s:,.0f} tok/s)")
    if manager:
        manager.maybe_save(steps, state, force=True)
        manager.close()
    wall = time.time() - t_start
    if owns_trace:
        tm.finalize()
    return {"losses": history, "final_loss": history[-1] if history else None,
            "wall_s": wall, "step_s": step_s,
            "stragglers": len(watchdog.straggler_events),
            "peak_activation_bytes": (mem_probe.peak_bytes
                                      if mem_probe else None),
            "peak_source": mem_probe.source if mem_probe else None,
            "microbatches": microbatches,
            "pipeline_bubble": (pipe_step.last_report.to_json()
                                if pipe_step and pipe_step.last_report
                                else None),
            "state": state}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--tnn", action="store_true",
                    help="enable the paper's tensorized layers")
    ap.add_argument("--tnn-backend", choices=["einsum", "pallas"],
                    default=None,
                    help="contraction executor for tensorized layers "
                         "(default: the arch config's TNNConfig.backend)")
    ap.add_argument("--tnn-autotune", action="store_true",
                    help="measurement-driven tuning: CSSE stage-2 reranks "
                         "by measured step latency and the pallas executor "
                         "uses tuned tile configs (implies --tnn-backend "
                         "pallas unless overridden); measurements persist "
                         "in REPRO_AUTOTUNE_CACHE")
    ap.add_argument("--tnn-mesh", default=None, metavar="AXES",
                    help="comma-separated mesh axes (e.g. 'data' or "
                         "'data,model') to distribute tensorized "
                         "contractions over: FP/BP run batch-parallel, WG "
                         "splits the contracted batch with a deferred psum, "
                         "and CSSE stage-2 ranks sequences "
                         "communication-aware for that mesh (see "
                         "docs/SHARDING.md)")
    ap.add_argument("--tnn-precision", default=None, metavar="POLICY",
                    help="quantized contraction execution for tensorized "
                         "layers: bf16 (default) | fp8[_e4m3] | fp8_e5m2 | "
                         "int8. Layers carry delayed-scaling amax history "
                         "(per-tensor — the training path ignores a "
                         "':tile' suffix, which only engages on direct "
                         "just-in-time-scaled executor calls), CSSE "
                         "stage-2 prices every byte term at the policy "
                         "width, and both executors run quantized (see "
                         "docs/PRECISION.md)")
    ap.add_argument("--tnn-remat", default=None, metavar="POLICY",
                    help="activation stash policy of the tensorized "
                         "custom-vjp: store (default) | recompute (model-"
                         "level per-layer jax.checkpoint re-runs the FP "
                         "plans inside the backward) | quantized[:dtype] "
                         "(fp8/int8 stash; lossless under --tnn-precision, "
                         "~2x stash reduction vs bf16 store). See "
                         "docs/MEMORY.md")
    ap.add_argument("--tnn-memory-budget", default=None, metavar="BYTES",
                    help="peak activation-memory budget ('64MB', '1.5GB', "
                         "or raw bytes): CSSE stage-2 never picks a plan "
                         "whose modeled live-tensor peak exceeds it, and "
                         "the stash planner raises the microbatch count "
                         "(gradient accumulation) until the per-step "
                         "activation stash fits")
    ap.add_argument("--tnn-search", choices=["per-axis", "joint"],
                    default="per-axis",
                    help="plan-search mode: per-axis (default; each "
                         "--tnn-* flag fixes its axis independently) | "
                         "joint (repro.core.search re-searches the "
                         "contraction sequence under every fusion x "
                         "precision x stash combo and the winning combo "
                         "overrides those flags — docs/SEARCH.md)")
    ap.add_argument("--tnn-pipeline", type=int, default=None,
                    metavar="STAGES",
                    help="pipeline-parallel execution of the layer stack: "
                         "partition into STAGES contiguous stages and "
                         "stream microbatches through them under the 1F1B "
                         "schedule; raises --microbatches to at least "
                         "STAGES, and each step reports modeled-vs-"
                         "measured pipeline bubble through the telemetry "
                         "drift channel (docs/DISTRIBUTED.md)")
    ap.add_argument("--tnn-trace", default=None, metavar="PATH",
                    help="write a telemetry trace of the run: '*.jsonl' "
                         "streams events as recorded, any other suffix "
                         "writes Chrome trace-event JSON loadable in "
                         "Perfetto (spans for CSSE/autotune/plan "
                         "compile/kernel dispatch and per-train-step "
                         "phases, counters, model-vs-measured drift "
                         "records — docs/OBSERVABILITY.md)")
    ap.add_argument("--loss-scale", type=float, default=1.0,
                    help="static loss scaling for low-precision training: "
                         "the loss is multiplied by this before backward "
                         "and gradients divided back in AdamW")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()
    if args.tnn_backend is not None and not args.tnn:
        ap.error("--tnn-backend requires --tnn (no tensorized layers to "
                 "route without it)")
    if args.tnn_autotune and not args.tnn:
        ap.error("--tnn-autotune requires --tnn (no tensorized layers to "
                 "tune without it)")
    if args.tnn_mesh is not None and not args.tnn:
        ap.error("--tnn-mesh requires --tnn (no tensorized contractions to "
                 "shard without it)")
    if args.tnn_precision is not None and not args.tnn:
        ap.error("--tnn-precision requires --tnn (no tensorized "
                 "contractions to quantize without it)")
    if args.tnn_remat is not None and not args.tnn:
        ap.error("--tnn-remat requires --tnn (no tensorized stash to "
                 "manage without it)")
    if args.tnn_memory_budget is not None and not args.tnn:
        ap.error("--tnn-memory-budget requires --tnn (the budget "
                 "constrains tensorized plans and stashes)")
    if args.tnn_search != "per-axis" and not args.tnn:
        ap.error("--tnn-search requires --tnn (no tensorized plans to "
                 "search without it)")
    if args.tnn_pipeline is not None and not args.tnn:
        ap.error("--tnn-pipeline requires --tnn (the staged path "
                 "partitions the tensorized layer stack)")
    if args.tnn_pipeline is not None and args.tnn_pipeline < 1:
        ap.error("--tnn-pipeline must be >= 1")
    enable_compile_cache()

    def run(start_step: int) -> int:
        out = train(args.arch, smoke=args.smoke, tnn=args.tnn,
                    steps=args.steps, global_batch=args.batch,
                    seq_len=args.seq, lr=args.lr, ckpt_dir=args.ckpt_dir,
                    ckpt_every=args.ckpt_every,
                    microbatches=args.microbatches,
                    production_mesh=args.production_mesh,
                    tnn_backend=args.tnn_backend,
                    tnn_autotune=args.tnn_autotune,
                    tnn_mesh=args.tnn_mesh,
                    tnn_precision=args.tnn_precision,
                    tnn_remat=args.tnn_remat,
                    tnn_memory_budget=args.tnn_memory_budget,
                    tnn_search=args.tnn_search,
                    tnn_pipeline=args.tnn_pipeline,
                    loss_scale=args.loss_scale,
                    trace_path=args.tnn_trace)
        _log.info(f"done: final loss {out['final_loss']:.4f} "
                  f"in {out['wall_s']:.1f}s, stragglers={out['stragglers']}")
        return args.steps

    try:
        ft.run_with_restarts(
            run, max_restarts=2,
            on_failure=lambda e: _log.info(f"RESTART: {e}"))
    finally:
        # A run that died mid-trace still flushes what it recorded.
        tm.finalize()


if __name__ == "__main__":
    main()
