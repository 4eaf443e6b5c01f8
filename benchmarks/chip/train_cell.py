"""General training driver: the system's jitted train step in a timed loop.

The step is built exactly as ``launch/train.py:train()`` wires it:
``steps.build_model``, ``steps.make_train_step``, ``sharding.param_specs``
/ ``batch_spec`` shardings, ``AdamW`` and a donated jitted step.  The
benchmark only makes the weights (from the seed, on the device, in one
jitted call) and the batches (uniform token ids from the seed, every row
different), and runs its own time window, because ``train()`` takes a step
count and not a time.

Set-up drives that one compiled step with its state through its first
three steps, through the same call and feed as the window, and reads: the
three losses, each leaf's gradient norm as the optimizer got it (from its
first moment after step 1) and each leaf's change after step 3.  The
reference follows the same three steps in float32 after the window.

Mix keys: ``batch``, ``seq``, ``optimizer`` (the AdamW settings) and
``tnn_mesh`` (mesh axes the TT contractions shard over, on several chips).
"""

from __future__ import annotations

import contextlib
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

from . import devtrace, flops
from .harness import (build_model, free_device, make_params, memory_peak,
                      read_metrics)

CHECK_STEPS = 3
TRACE_STEPS = 3


def tokens(seed: int, step: int, batch: int, seq: int, vocab: int) -> dict:
    """Step ``step``'s batch: uniform ids from (seed, step)."""
    rng = np.random.default_rng([seed, step, 11])
    t = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
    return {"inputs": t[:, :-1], "targets": t[:, 1:]}


def leaf_norms(tree) -> list:
    import jax
    import jax.numpy as jnp
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


class Program:
    """The system's train step with its state, for one cell."""

    def __init__(self, cell, devs):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import sharding
        from repro.launch import steps
        from repro.optim.adamw import AdamW

        self.cell, mix = cell, cell.mix
        self.mesh = sharding.make_mesh((len(devs), 1), ("data", "model"),
                                       devices=devs)
        self.model, self.lm = build_model(cell, self.mesh)
        o = mix["optimizer"]
        self.opt = AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"],
                         clip_norm=o["clip_norm"],
                         warmup_steps=o["warmup_steps"],
                         total_steps=o["total_steps"],
                         min_lr_ratio=o["min_lr_ratio"])
        shard = sharding.make_sharder(self.mesh)
        pspecs = sharding.param_specs(
            jax.eval_shape(self.model.init, jax.random.key(0)), self.mesh)
        self.pshard = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                   pspecs, is_leaf=lambda x: isinstance(x, P))
        opt_cls = type(jax.eval_shape(self.opt.init, jax.eval_shape(
            self.model.init, jax.random.key(0))))
        self.state_shard = {"params": self.pshard,
                            "opt": opt_cls(m=self.pshard, v=self.pshard,
                                           step=NamedSharding(self.mesh, P()))}
        self.step_fn = jax.jit(
            steps.make_train_step(self.model, self.opt, shard, microbatches=1),
            in_shardings=(self.state_shard, None), donate_argnums=0)
        self.init_opt = jax.jit(self.opt.init,
                                out_shardings=self.state_shard["opt"])
        self.state = None

    def start(self, seed: int) -> None:
        self.state = None
        params = make_params(self.cell, seed, self.pshard)
        self.state = {"params": params, "opt": self.init_opt(params)}

    def step(self, batch: dict) -> float:
        import jax.numpy as jnp
        self.state, metrics = self.step_fn(
            self.state, {k: jnp.asarray(v) for k, v in batch.items()})
        return float(metrics["loss"])


def program_readings(prog: Program, seed: int, fault: str | None) -> dict:
    """The first ``CHECK_STEPS`` steps of a fresh state, with readings."""
    import jax
    mix, cfg = prog.cell.mix, prog.cell.config
    b1 = mix["optimizer"]["b1"]
    losses, grads = [], None
    for s in range(1, CHECK_STEPS + 1):
        batch = tokens(seed, s, mix["batch"], mix["seq"], cfg["vocab_size"])
        if fault == "half_batch":
            half = mix["batch"] // 2
            batch = {k: np.concatenate([v[:half], v[:half]])
                     for k, v in batch.items()}
        if fault == "frozen_state":
            import jax.numpy as jnp
            _, met = prog.step_fn(jax.tree.map(jnp.copy, prog.state),
                                  {k: jnp.asarray(v)
                                   for k, v in batch.items()})
            losses.append(float(met["loss"]))
        else:
            losses.append(prog.step(batch))
        if s == 1:
            grads = [float(x) / (1 - b1) for x in
                     jax.jit(leaf_norms)(prog.state["opt"].m)]
    return {"loss": losses, "grad": grads,
            "change": change_norms(prog.cell, prog.state["params"], seed)}


def change_norms(cell, params, seed: int) -> list[float]:
    """Each leaf's norm of ``params`` minus the seed's initial weights,
    which are made again inside the call rather than kept."""
    import jax
    ref = cell.reference()
    fn = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
        lambda a, b: a - b, p, ref.init_params(cell.config, k))))
    return [float(x) for x in fn(params, jax.random.key(seed))]


def reference_readings(cell, seed: int, prec: str = "f32",
                       half_batch: bool = False) -> dict:
    """The same three steps by the plain reference.  Each optimizer step
    donates the weights, gradients and moments it replaces, so the device
    holds four copies of the weights and one step's activations."""
    import jax
    import jax.numpy as jnp
    ref, mix, cfg = cell.reference(), cell.mix, cell.config
    params = make_params(cell, seed)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    vg = jax.jit(jax.value_and_grad(
        lambda p, x, y: ref.loss(cfg, p, x, y, prec)))
    upd = jax.jit(lambda p, g, m, v, s: ref.adamw_step(p, g, m, v, s,
                                                      mix["optimizer"]),
                  donate_argnums=(0, 1, 2, 3))
    losses, grads = [], None
    for s in range(1, CHECK_STEPS + 1):
        b = tokens(seed, s, mix["batch"], mix["seq"], cfg["vocab_size"])
        x, y = b["inputs"], b["targets"]
        if half_batch:
            x, y = x[:len(x) // 2], y[:len(y) // 2]
        loss, g = vg(params, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
        params, m, v, scale = upd(params, g, m, v, jnp.float32(s))
        if s == 1:
            # the clipped gradient, as the first moment holds it
            b1 = mix["optimizer"]["b1"]
            grads = [float(n) / (1 - b1) for n in jax.jit(leaf_norms)(m)]
        del g
    return {"loss": losses, "grad": grads,
            "change": change_norms(cell, params, seed)}


def compare(got: dict, ref: dict) -> dict:
    """The numbers compared: the worst step's relative loss gap, and by the
    worst leaf, the gap between the two gradient norms and between the two
    change norms, against the reference's norm of that leaf or of the
    median leaf, whichever is larger.  Leaves whose reference gradient is
    under a thousandth of the median leaf's are left out of both."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))
    med_g = statistics.median(ref["grad"])
    keep = [i for i, g in enumerate(ref["grad"]) if g >= 1e-3 * med_g]

    def worst(key):
        med = statistics.median(ref[key][i] for i in keep)
        return max(abs(got[key][i] - ref[key][i]) / max(ref[key][i], med)
                   for i in keep)

    return {"loss_rel": loss, "grad_gap": worst("grad"),
            "change_gap": worst("change")}


def run(cell, *, devs, kind, peak, seed, seconds, trace, t_start, fault=None):
    import jax
    from repro import telemetry as tm

    mix, cfg = cell.mix, cell.config
    if trace:
        tm.configure(jax_bridge=True)
    prog = Program(cell, devs)
    prog.start(seed)
    got = program_readings(prog, seed, fault)

    B, T, V = mix["batch"], mix["seq"], cfg["vocab_size"]
    step_no = CHECK_STEPS + 1
    n = 0
    step_s = []
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    traced = None
    t0 = time.monotonic()
    setup_s = t0 - t_start
    while True:
        if trace and n == 1:
            jax.profiler.start_trace(trace_dir)
            window = jax.profiler.TraceAnnotation("bench.window")
            window.__enter__()
        in_trace = trace and 1 <= n <= TRACE_STEPS
        t_step = time.monotonic()
        with (jax.profiler.TraceAnnotation("bench.step") if in_trace
              else contextlib.nullcontext()):
            prog.step(tokens(seed, step_no, B, T, V))
        step_s.append(time.monotonic() - t_step)
        n += 1
        step_no += 1
        if trace and n == 1 + TRACE_STEPS:
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced = TRACE_STEPS
        if time.monotonic() - t0 >= seconds and (not trace or traced):
            break
    elapsed = time.monotonic() - t0
    print(f"window: {n} steps in {elapsed:.3f} s, step median "
          f"{statistics.median(step_s):.4f} s, slowest {max(step_s):.4f} s",
          file=sys.stderr)
    mem = memory_peak(devs)
    prog.state = None
    del prog
    free_device()
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    print(f"device bytes in use after the window, freed: {in_use}",
          file=sys.stderr)

    ref = reference_readings(cell, seed)
    nums = compare(got, ref)
    limits = mix["limits"]
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": correct, "attempted": n, "failed": 0,
              "device": {"platform": devs[0].platform, "kind": kind,
                         "count": len(devs), "memory_peak_bytes": mem}}
    if not trace:
        result["metrics"] = {
            "train_tok_s": {"value": n * B * T / elapsed, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        tr = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = devtrace.window(tr, "bench.window")
        busy = devtrace.busy(tr, lo, hi)
        rundata = {
            "trace": tr, "window": (lo, hi), "devtrace": devtrace,
            "flops": flops, "peak": peak, "cell": cell, "config": cfg,
            "mix": mix, "chips": len(devs), "steps_traced": traced,
            "step_flops": flops.train_step_flops(cfg, B, T),
            "program_spans": [e for e in tm.snapshot()
                              if e.get("type") == "span"],
            "setup_s": setup_s,
        }
        result["metrics"] = read_metrics(cell, rundata)
        result["device"].update(busy_s=busy, window_s=hi - lo)
        result["breakdown"] = {
            "device_ops": [list(x) for x in devtrace.top_ops(tr, lo, hi)],
            "idle_gaps": [list(x) for x in devtrace.idle_gaps(tr, lo, hi)]}
        tm.finalize()
    result["checks"] = checks
    return result

