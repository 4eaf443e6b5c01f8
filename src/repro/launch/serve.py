"""Batched serving driver (continuous batching over the ServeEngine).

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_1_1b --smoke \
      --requests 8 --max-new 16 \
      --serve-kv-dtype fp8 --serve-memory-budget 64MB \
      --serve-prefill-chunk 16 --serve-max-prefill-tokens 64

Server start builds phase-specialized execution profiles (CSSE +
autotune warmed separately for the prefill and decode token batches —
see ``repro.serving.profiles``) when the model is tensorized, then runs
the slot-table engine.  ``--serve-memory-budget`` bounds admission by
the modeled per-slot KV bytes; ``--serve-kv-dtype fp8|int8`` stores the
KV cache quantized, halving that per-slot price.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import telemetry as tm
from repro.configs import base as cfgbase
from repro.distributed import sharding
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.memory.planner import format_bytes
from repro.serving import profiles as profiles_lib
from repro.serving.engine import Request, ServeEngine

_log = tm.get_logger("serve")


def serve(arch_id: str, *, smoke: bool, tnn: bool, requests: int = 8,
          batch: int = 4, prompt_len: int = 16, max_new: int = 16,
          kv_dtype: str = "bf16", memory_budget=None,
          prefill_chunk: int = 32,
          max_prefill_tokens: int | None = None) -> dict:
    """Serve ``requests`` random prompts through the continuous-batching
    engine; returns the completed requests, the engine, the warmup
    (compile) seconds and the seconds of every tick."""
    arch = cfgbase.get(arch_id)
    tnn_cfg = arch.tnn_default if tnn else None
    model, cfg = steps_lib.build_model(arch, tnn=tnn_cfg, smoke=smoke)
    mesh = make_host_mesh()
    shard = sharding.make_sharder(mesh)
    params = model.init(jax.random.key(0))

    # Phase-specialized planning at server start: prefill and decode get
    # their own CSSE/autotune cache entries (phase-tagged signatures).
    prof = profiles_lib.build_profiles(
        cfg, batch_size=batch, prefill_chunk=prefill_chunk)
    if prof:
        # raw print (no [serve] prefix historically): profile_summary is
        # its own multi-line block
        print(profiles_lib.profile_summary(prof))

    engine = ServeEngine(
        model, params, batch_size=batch,
        max_len=prompt_len + max_new + 8,
        shard=shard,
        prefill_chunk=prefill_chunk,
        max_prefill_tokens=max_prefill_tokens,
        kv_policy=kv_dtype,
        memory_budget=memory_budget)
    _log.info(f"slot KV: {format_bytes(engine.slot_cost['total'])} "
              f"({kv_dtype}), capacity {engine.capacity}/"
              f"{batch} slots")
    rng = np.random.default_rng(0)
    for rid in range(requests):
        engine.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab, size=prompt_len,
                                dtype=np.int32),
            max_new_tokens=max_new,
            temperature=0.0 if rid % 2 == 0 else 0.8))
    t0 = time.time()
    engine.warmup()
    warmup_s = time.time() - t0
    tick_s = []
    while engine.busy:       # engine.run(), one timed tick at a time
        t = time.time()
        engine.step()
        tick_s.append(time.time() - t)
    return {"done": engine.completed, "engine": engine,
            "warmup_s": warmup_s, "tick_s": tick_s}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tnn", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--serve-kv-dtype", default="bf16",
                    help="KV cache storage: bf16 | fp8 | fp8_e5m2 | int8")
    ap.add_argument("--serve-memory-budget", default=None,
                    help="KV admission budget, e.g. 64MB (modeled bytes)")
    ap.add_argument("--serve-prefill-chunk", type=int, default=32,
                    help="prompt tokens a slot ingests per tick")
    ap.add_argument("--serve-max-prefill-tokens", type=int, default=None,
                    help="global prefill token budget per tick")
    ap.add_argument("--serve-trace", default=None, metavar="PATH",
                    help="write a telemetry trace of the serving run: "
                         "'*.jsonl' streams events, any other suffix "
                         "writes Chrome trace-event JSON for Perfetto "
                         "(per-request queue-wait/prefill/decode lanes, "
                         "tick spans, occupancy samples — "
                         "docs/OBSERVABILITY.md)")
    args = ap.parse_args()
    enable_compile_cache()
    owns_trace = bool(args.serve_trace) and not tm.enabled()
    if owns_trace:
        tm.configure(args.serve_trace)

    out = serve(args.arch, smoke=args.smoke, tnn=args.tnn,
                requests=args.requests, batch=args.batch,
                prompt_len=args.prompt_len, max_new=args.max_new,
                kv_dtype=args.serve_kv_dtype,
                memory_budget=args.serve_memory_budget,
                prefill_chunk=args.serve_prefill_chunk,
                max_prefill_tokens=args.serve_max_prefill_tokens)
    done, engine = out["done"], out["engine"]
    dt = sum(out["tick_s"])
    total_new = sum(len(r.out_tokens) for r in done)
    _log.info(f"{len(done)} requests, {total_new} tokens "
              f"in {dt:.2f}s ({total_new/dt:.1f} tok/s), "
              f"{engine.tick} ticks, peak occupancy {engine.max_occupancy}")
    for r in done[:4]:
        print(f"  req {r.rid}: {r.out_tokens[:12]}...")
    if owns_trace:
        tm.finalize()


if __name__ == "__main__":
    main()
