"""General serving driver: ``ServeEngine.step()`` under generated traffic.

The engine is built as ``launch/serve.py:serve()`` builds it: the model from
``steps.build_model``, the phase profiles of ``serving.profiles`` at server
start, then ``ServeEngine`` and its ``warmup()``.  The benchmark makes the
weights (from the seed, on the device, in one jitted call), offers the
mix's traffic, and stamps every new output token on the host clock at the
end of the tick that produced it.

Mix keys: ``arrival`` (``poisson`` at ``rate_per_s``, an open loop whose
requests are timed from when they were due; or ``closed`` with ``clients``
that each send their next request when the last one finishes),
``prompt_len`` and ``output_len`` (see ``traffic.py``), ``greedy_every``
and ``temperature``, ``engine`` (slots, max_len, prefill_chunk, kv_dtype)
and ``check`` (how many greedy tokens the output check compares).

After the window the check takes a sample, drawn from the seed, of the
greedy requests that finished, the longest among them, and runs the plain
reference once over each prompt with its served tokens: the number
compared is the widest gap by which a served token's logit lies below the
reference's best at that position.
"""

from __future__ import annotations

import contextlib
import shutil
import tempfile
import time

import numpy as np

from . import devtrace, flops, traffic
from .harness import (build_model, free_device, make_params, memory_peak,
                      read_metrics)

TRACE_S = 3.0
WARM_PROMPT, WARM_NEW = 40, 3


class Record:
    """One request and the host stamps of its tokens."""
    __slots__ = ("req", "seen", "stamps", "due")

    def __init__(self, req, due: float):
        self.req, self.due, self.seen, self.stamps = req, due, 0, []


class Program:
    """The system's serving engine with its weights, for one cell."""

    def __init__(self, cell, devs, seed: int, fault: str | None = None):
        from repro.distributed import sharding
        from repro.serving import profiles as profiles_lib
        from repro.serving.engine import ServeEngine

        e = cell.mix["engine"]
        mesh = sharding.make_mesh((len(devs), 1), ("data", "model"),
                                  devices=devs)
        model, lm = build_model(cell)
        params = make_params(cell, seed)
        profiles_lib.build_profiles(lm, batch_size=e["slots"],
                                    prefill_chunk=e["prefill_chunk"])
        self.engine = ServeEngine(
            model, params, batch_size=e["slots"], max_len=e["max_len"],
            shard=sharding.make_sharder(mesh),
            prefill_chunk=e["prefill_chunk"], kv_policy=e["kv_dtype"],
            seed=seed % 2 ** 31)
        self.engine.warmup()
        if fault == "token_altered":
            sample, vocab = self.engine._sample, lm.vocab
            self.engine._sample = lambda lg, t: (sample(lg, t) + 1) % vocab

    def warm(self, vocab: int) -> None:
        """A greedy and a sampled request through prefill, decode and
        sampling with real data, before the window."""
        from repro.serving.engine import Request
        rng = np.random.default_rng(0)
        for rid, temp in ((-1, 0.0), (-2, 0.8)):
            self.engine.submit(Request(
                rid=rid, prompt=rng.integers(0, vocab, WARM_PROMPT,
                                             dtype=np.int32),
                max_new_tokens=WARM_NEW, temperature=temp))
        self.engine.run()
        self.engine.completed.clear()


def record_calls(engine, calls: list) -> None:
    """Record each prefill and decode call of the engine: its start on
    the host clock, the tokens each slot adds and the slot's cache depth
    before it."""
    ext, dec = engine._extend_fn, engine._decode_fn

    def extend(params, toks, state, lengths, valid, active):
        calls.append((time.monotonic(), np.asarray(valid),
                      np.asarray(lengths)))
        return ext(params, toks, state, lengths, valid, active)

    def decode(params, tok, state, lengths, active):
        calls.append((time.monotonic(), np.asarray(active).astype(np.int64),
                      np.asarray(lengths)))
        return dec(params, tok, state, lengths, active)

    engine._extend_fn, engine._decode_fn = extend, decode


def serve_window(prog: Program, specs, mix: dict, seconds: float,
                 trace_dir: str | None) -> dict:
    """Offer the traffic for ``seconds``; returns every record and tick."""
    import jax
    from repro.serving.engine import Request

    engine = prog.engine
    closed = mix["arrival"]["kind"] == "closed"
    records, live, ticks = [], [], []
    t0 = time.monotonic()
    end = t0 + seconds
    trace_at = t0 + seconds / 2 if trace_dir else None
    traced = trace_host = None
    window = None
    clients = mix["arrival"].get("clients", 0)
    nxt = 0

    def submit(spec, due):
        req = Request(rid=spec.idx, prompt=spec.prompt,
                      max_new_tokens=spec.max_new,
                      temperature=spec.temperature, t_submit=due)
        engine.submit(req)
        rec = Record(req, due)
        records.append(rec)
        live.append(rec)

    if closed:
        nxt = min(clients, len(specs))
        for spec in specs[:nxt]:
            submit(spec, t0)
    while True:
        now = time.monotonic()
        if trace_at is not None and traced is None and now >= trace_at:
            jax.profiler.start_trace(trace_dir)
            window = jax.profiler.TraceAnnotation("bench.window")
            window.__enter__()
            traced = now
        if traced is not None and window is not None \
                and now >= traced + TRACE_S:
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            window = None
            trace_host = (traced, time.monotonic())
        if now >= end and window is None:
            break
        if not closed:
            while nxt < len(specs) and t0 + specs[nxt].due_s <= now:
                submit(specs[nxt], t0 + specs[nxt].due_s)
                nxt += 1
        if not engine.busy:
            wake = end if closed or nxt >= len(specs) \
                else min(end, t0 + specs[nxt].due_s)
            with (jax.profiler.TraceAnnotation("bench.wait") if window
                  else contextlib.nullcontext()):
                time.sleep(max(0.0, min(wake - now, 0.05)))
            continue
        with (jax.profiler.TraceAnnotation("bench.tick") if window
              else contextlib.nullcontext()):
            engine.step()
        t = time.monotonic()
        ticks.append((now, t))
        finished = 0
        for rec in live:
            k = len(rec.req.out_tokens)
            rec.stamps += [t] * (k - rec.seen)
            rec.seen = k
            finished += rec.req.done
        live[:] = [r for r in live if not r.req.done]
        for _ in range(finished if closed else 0):
            if nxt < len(specs):
                submit(specs[nxt], t)
                nxt += 1
    return {"records": records, "ticks": ticks, "t0": t0,
            "t_end": time.monotonic(), "end": end, "trace_host": trace_host}


def finish_for_check(engine, records, tokens: int, wait_s: float,
                     end: float) -> None:
    """After the window: tick on, taking no new requests, until every
    request due in the window has its first token and finished greedy
    requests hold ``tokens`` served tokens, or ``wait_s`` passes.  A first
    token that comes now is stamped and counts, late, in the time to first
    token; no other metric counts these ticks."""
    deadline = time.monotonic() + wait_s

    def done():
        greedy = sum(len(r.req.out_tokens) for r in records
                     if r.req.done and r.req.temperature == 0.0)
        return greedy >= tokens and all(
            r.req.out_tokens for r in records if r.due <= end)

    while engine.busy and not done() and time.monotonic() < deadline:
        engine.step()
        t = time.monotonic()
        for r in records:
            if r.req.out_tokens and not r.stamps:
                r.stamps.append(t)


def pick_sample(records, seed: int, tokens: int, max_requests: int):
    """Finished greedy requests: the longest, then others in an order
    drawn from the seed, until ``tokens`` served tokens."""
    done = [r for r in records if r.req.done and r.req.temperature == 0.0]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.req.out_tokens), r.req.rid))
    rest = done[1:]
    order = np.random.default_rng([seed, 13]).permutation(len(rest))
    out = [done[0]]
    for i in order:
        if sum(len(r.req.out_tokens) for r in out) >= tokens \
                or len(out) >= max_requests:
            break
        out.append(rest[i])
    return out


def logit_gaps(cell, seed: int, sample, precs=("f32",)) -> dict:
    """For each precision, the gaps at every served position: the f32
    reference's best logit minus its logit of the token that was served
    ("f32"), or of the token a lower-precision reference puts first
    (any other precision)."""
    import jax
    import jax.numpy as jnp
    ref, cfg = cell.reference(), cell.config
    e = cell.mix["engine"]
    L, R = e["max_len"], cell.mix["output_len"]["max"]
    params = make_params(cell, seed)

    @jax.jit
    def rows(params, seq, idx):
        out = {}
        for p in precs:
            h = ref.hidden(cfg, params, seq, p)[idx]
            out[p] = ref.mm("td,dv->tv", h, params["lm_head"]["w"], p)
        return out

    gaps = {p: [] for p in precs}
    for rec in sample:
        prompt, served = list(rec.req.prompt), rec.req.out_tokens
        seq = np.zeros(L, np.int32)
        full = prompt + served[:-1]
        seq[:len(full)] = full
        n = len(served)
        idx = np.zeros(R, np.int32)
        idx[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        lg = rows(params, jnp.asarray(seq), jnp.asarray(idx))
        f32 = np.asarray(lg["f32"])[:n]
        best = f32.max(-1)
        for p in precs:
            tok = (np.asarray(served) if p == "f32"
                   else np.asarray(lg[p])[:n].argmax(-1))
            gaps[p] += list(best - f32[np.arange(n), tok])
    return gaps


def run(cell, *, devs, kind, peak, seed, seconds, trace, t_start, fault=None):
    import jax
    from repro import telemetry as tm

    mix, cfg = cell.mix, cell.config
    if trace:
        tm.configure(jax_bridge=True)
    prog = Program(cell, devs, seed, fault)
    prog.warm(cfg["vocab_size"])
    specs = traffic.generate(mix, seed, seconds, cfg["vocab_size"])
    calls: list = []
    if trace:
        record_calls(prog.engine, calls)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup_s = time.monotonic() - t_start
    out = serve_window(prog, specs, mix, seconds, trace_dir)
    chk = mix["check"]
    finish_for_check(prog.engine, out["records"], chk["tokens"],
                     chk["wait_s"], out["end"])
    mem = memory_peak(devs)
    prog.engine = None
    del prog
    free_device()

    t0, end, t_end = out["t0"], out["end"], out["t_end"]
    recs = out["records"]
    sample = pick_sample(recs, seed, chk["tokens"], chk["max_requests"])
    gaps = logit_gaps(cell, seed, sample)["f32"] if sample else []
    checks = {"logit_gap": {"value": float(max(gaps)) if gaps else None,
                            "limit": mix["limits"]["logit_gap"]},
              "tokens_compared": {"value": len(gaps),
                                  "limit": chk["min_tokens"]}}
    correct = (bool(gaps) and checks["logit_gap"]["value"]
               <= checks["logit_gap"]["limit"]
               and len(gaps) >= chk["min_tokens"])

    due = [r for r in recs if r.due <= end]
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": sum(1 for r in due if not r.stamps),
              "device": {"platform": devs[0].platform, "kind": kind,
                         "count": len(devs), "memory_peak_bytes": mem}}
    if not trace:
        m = {"setup_s": {"value": setup_s, "unit": "s"}}
        names = {x["name"] for x in cell.end_to_end()}
        if "ttft_p50_ms" in names:
            ttft = [(r.stamps[0] - r.due) * 1e3 for r in due if r.stamps]
            m["ttft_p50_ms"] = {"value": float(np.percentile(ttft, 50)),
                                "unit": "ms"}
        if "itl_p95_ms" in names:
            itl = [(b - a) * 1e3 for r in recs
                   for a, b in zip(r.stamps, r.stamps[1:]) if t0 <= a
                   and b <= end]
            m["itl_p95_ms"] = {"value": float(np.percentile(itl, 95)),
                               "unit": "ms"}
        if "serve_tok_s" in names:
            n_tok = sum(1 for r in recs for s in r.stamps if t0 <= s <= t_end)
            m["serve_tok_s"] = {"value": n_tok / (t_end - t0),
                                "unit": "tokens/s"}
        result["metrics"] = m
    else:
        tr = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = devtrace.window(tr, "bench.window")
        rundata = {
            "trace": tr, "window": (lo, hi), "devtrace": devtrace,
            "flops": flops, "peak": peak, "cell": cell, "config": cfg,
            "mix": mix, "chips": len(devs), "records": recs,
            "ticks": out["ticks"], "calls": calls, "t0": t0, "end": end,
            "trace_host": out["trace_host"],
            "program_spans": [e for e in tm.snapshot()
                              if e.get("type") == "span"],
            "setup_s": setup_s,
        }
        result["metrics"] = read_metrics(cell, rundata)
        result["device"].update(busy_s=devtrace.busy(tr, lo, hi),
                                window_s=hi - lo)
        result["breakdown"] = {
            "device_ops": [list(x) for x in devtrace.top_ops(tr, lo, hi)],
            "idle_gaps": [list(x) for x in devtrace.idle_gaps(tr, lo, hi)]}
        tm.finalize()
    result["checks"] = checks
    return result
