"""Drive TinyLlama-TT training and serving once on a TPU chip and check them.

    python chip_smoke.py             # one chip: train (einsum, pallas), serve
    python chip_smoke.py --chips 4   # four chips: sharded TT training vs one

The model is ``tinyllama_1_1b`` at its published widths and full depth
(22 layers, d 2048, GQA 32/4, d_ff 5632, vocab 32000) with the arch's
default TT-tensorized MLP, random weights from a fixed seed.

One chip.  ``train()`` takes a few steps at batch 8 x 2048 tokens with the
einsum and then the Pallas contraction executor; the losses must be finite
and the two first-step losses must agree within ``LOSS_RTOL``.  Then the
``serve()`` entry point answers a few requests, each of which must return
its full count of tokens.

Four chips.  The same steps on a (4, 1) data mesh with the tensorized
contractions sharded over it (``tnn_mesh="data"``), and on one chip of the
same process; each step's losses must agree within ``LOSS_RTOL``.

Everything runs in this one process.  It exits non-zero, and prints no
result, when JAX finds no TPU, when the chip is not the one the planning
model describes, or when any phase fails.  Its last line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "tinyllama_1_1b"
BATCH, SEQ, STEPS = 8, 2048, 5
# A rate at which five steps from random weights stay stable: at the
# trainer's 3e-3 the loss jumps by 4 within three steps, and two runs
# that differ only in rounding part ways.
LR = 1e-4
REQUESTS, PROMPT, NEW = 8, 128, 32
# Two runs of the same bf16 model agree to a few bf16 roundings
# (2**-8 relative each): four of them bound the loss difference.
LOSS_RTOL = 4 * 2.0 ** -8


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def tpu_devices():
    """The TPU devices, or SmokeFailure.  ``JAX_PLATFORMS=tpu`` makes a
    failed TPU start-up raise instead of carrying on with the CPU."""
    os.environ["JAX_PLATFORMS"] = "tpu"
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"no TPU: {e}") from None
    check(devs[0].platform == "tpu", f"no TPU: JAX runs on {devs[0].platform}")
    return devs


def steady(times: list[float]) -> float:
    return statistics.median(times[1:]) if len(times) > 1 else times[0]


def report_train(tag: str, out: dict) -> None:
    s = out["step_s"]
    print(f"[{tag}] losses {out['losses']}")
    print(f"[{tag}] first step {s[0]:.3f} s, of which compile about "
          f"{s[0] - steady(s):.3f} s; steady step {steady(s) * 1e3:.2f} ms "
          f"(chip time, median of {len(s) - 1}), "
          f"{BATCH * SEQ / steady(s):,.0f} tokens/s")


def run_train(backend: str, **kw) -> dict:
    from repro.launch.train import train
    out = train(ARCH, smoke=False, tnn=True, steps=STEPS, global_batch=BATCH,
                seq_len=SEQ, lr=LR, ckpt_dir=None, ckpt_every=0,
                microbatches=1, production_mesh=False, log_every=1,
                tnn_backend=backend, **kw)
    out.pop("state")           # free the chip for the next phase
    check(len(out["losses"]) == STEPS
          and all(math.isfinite(x) for x in out["losses"]),
          f"{backend}: non-finite or missing losses {out['losses']}")
    return out


def close(a: float, b: float) -> bool:
    return abs(a - b) <= LOSS_RTOL * max(abs(a), abs(b))


def phase_train_one_chip() -> None:
    runs = {b: run_train(b) for b in ("einsum", "pallas")}
    for b, out in runs.items():
        report_train(f"train {b}", out)
    l_e, l_p = runs["einsum"]["losses"][0], runs["pallas"]["losses"][0]
    print(f"[train] first-step loss einsum {l_e!r} pallas {l_p!r} "
          f"|diff| {abs(l_e - l_p):.3g} (limit {LOSS_RTOL:.3g} relative)")
    check(close(l_e, l_p), "pallas and einsum first-step losses disagree")


def phase_serve() -> None:
    from repro.launch.serve import serve
    out = serve(ARCH, smoke=False, tnn=True, requests=REQUESTS,
                batch=REQUESTS, prompt_len=PROMPT, max_new=NEW)
    done, vocab = out["done"], out["engine"].model.cfg.vocab
    ticks = out["tick_s"]
    print(f"[serve] {len(done)} requests, {len(ticks)} ticks, warmup "
          f"{out['warmup_s']:.3f} s (includes compile), steady tick "
          f"{steady(ticks) * 1e3:.2f} ms (chip time, median), "
          f"{sum(ticks):.3f} s for all ticks")
    check(len(done) == REQUESTS, f"{len(done)} of {REQUESTS} completed")
    for r in done:
        check(len(r.out_tokens) == NEW
              and all(0 <= t < vocab for t in r.out_tokens),
              f"request {r.rid}: {len(r.out_tokens)} tokens "
              f"{r.out_tokens[:8]}...")
    # Reference: a greedy request's first token is the argmax of the full
    # forward over its prompt.  The engine gets there through chunked
    # prefill and its own attention, so bf16 rounding may swap near-tied
    # logits: the token must be among the reference's top 5.
    import jax
    import jax.numpy as jnp
    engine = out["engine"]
    forward = jax.jit(lambda p, x: engine.model(p, x)[0][0, -1])
    for r in done:
        if r.temperature == 0.0:
            logits = forward(engine.params, jnp.asarray(r.prompt)[None])
            top = [int(t) for t in jnp.argsort(-logits)[:5]]
            print(f"[serve] request {r.rid}: first token {r.out_tokens[0]}, "
                  f"reference top 5 {top}")
            check(r.out_tokens[0] in top,
                  f"request {r.rid}: first token not in the reference top 5")


def phase_train_four_chips(devs) -> None:
    from repro.distributed.sharding import make_mesh
    axes = ("data", "model")
    four = run_train("pallas", tnn_mesh="data",
                     mesh=make_mesh((4, 1), axes, devices=devs[:4]))
    one = run_train("pallas", mesh=make_mesh((1, 1), axes, devices=devs[:1]))
    report_train("train 4 chips", four)
    report_train("train 1 chip", one)
    for i, (a, b) in enumerate(zip(four["losses"], one["losses"])):
        print(f"[train] step {i} loss 4 chips {a!r} 1 chip {b!r} "
              f"|diff| {abs(a - b):.3g}")
        check(close(a, b), f"step {i}: 4-chip and 1-chip losses disagree")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded training path and its "
                         "one-chip comparison")
    args = ap.parse_args()
    try:
        devs = tpu_devices()
        check(len(devs) >= args.chips,
              f"{args.chips} chips asked for, {len(devs)} present")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro import telemetry as tm
        from repro.core import perf_model, plan_compiler
        from repro.kernels import fused_contraction
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import blocks
    except (SmokeFailure, ImportError) as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1

    kind = devs[0].device_kind
    print(f"device_kind {kind!r}, {len(devs)} devices")
    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir}")
    # Plans and tile choices are searched afresh, from committed files.
    os.makedirs(os.path.join(ROOT, ".cache"), exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="chip_smoke-",
                               dir=os.path.join(ROOT, ".cache"))
    os.environ["REPRO_CSSE_CACHE"] = os.path.join(scratch, "csse")
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(scratch, "autotune")
    try:
        perf_model.hardware_for(kind)
        print(f"pallas interpret {fused_contraction.INTERPRET}, "
              f"pallas flash attention {blocks._USE_PALLAS_FLASH}")
        check(not fused_contraction.INTERPRET and blocks._USE_PALLAS_FLASH,
              "kernels would run in interpret mode or flash is off")
        tm.configure()         # in memory: the plan compiler's counters
        if args.chips == 4:
            phase_train_four_chips(devs)
        else:
            phase_train_one_chip()
            phase_serve()
        counters = tm.counters()
        print(f"plan_compiler DEGRADE_COUNTS {plan_compiler.DEGRADE_COUNTS}, "
              f"einsum fallbacks "
              f"{counters.get('plan_compiler.einsum_fallbacks', 0)}, chains "
              f"{counters.get('plan_compiler.chains', 0)}, plans compiled "
              f"{counters.get('plan_compiler.compiled', 0)}")
        check(not any(plan_compiler.DEGRADE_COUNTS.values()),
              "a chain degraded to unfused GEMMs")
        peak = devs[0].memory_stats().get("peak_bytes_in_use")
        print(f"peak_bytes_in_use {peak} (device 0)")
    except (SmokeFailure, ValueError) as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
