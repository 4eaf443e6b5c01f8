"""Device time by the program's named scopes, and the program's tracer
records on a profiler trace's clock.

The system names its layers in the jitted code with ``jax.named_scope``:
``tt_fp``, ``tt_bp`` and ``tt_wg`` (the TT contraction phases),
``attention``, ``lm_head`` and ``engine_select`` (the serving engine's
per-slot select).  XLA keeps the name in each instruction's ``op_name``
metadata, as a path segment (``.../rematted_computation/tt_fp/dot_general``)
or wrapped by a transform (``jvp(lm_head)/...``,
``transpose(jvp(lm_head))/...``); :func:`in_scope` matches both.

:func:`scope_seconds` reads ``trace["op_names"]``, which maps an op event's
name (as ``devtrace.load`` keeps it) to its ``op_name``; :func:`attach`
fills it in.  The system's tracer (``repro.telemetry``) ties its clock to
the capture with a ``tracer.sync`` annotation; :func:`place` puts any of
its records on the trace's clock with it.

Everything here reads what a run of the system left and returns nothing
where it finds nothing: a system without the scopes, the sync or the spans
gives no metric, and no error.
"""

from __future__ import annotations

import functools
import re
import sys
import traceback

from . import devtrace

SCOPES = ("tt_fp", "tt_bp", "tt_wg", "attention", "lm_head", "engine_select")

_META = re.compile(r'op_name="([^"]*)"')


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``op_name`` lies inside ``scope``: the scope is a segment of
    the path, bare or inside a transform's parentheses."""
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)", op_name) \
        is not None


@functools.lru_cache(maxsize=None)
def scopes_of(op_name: str) -> tuple[str, ...]:
    """The scopes ``op_name`` lies in (a trace holds few distinct names
    over many events, hence the memo)."""
    return tuple(sc for sc in SCOPES if in_scope(op_name, sc))


def scoped_intervals(trace: dict, scope: str, device: str):
    """Merged intervals of the ops of one device that lie in ``scope``."""
    names = trace.get("op_names") or {}
    return devtrace.union((s, e) for n, s, e in trace["ops"][device]
                          if scope in scopes_of(names.get(n, "")))


def scope_seconds(trace: dict, scope: str, lo: float, hi: float) -> float:
    """Device seconds in [lo, hi] of the ops that lie in ``scope``,
    averaged over the devices."""
    devs = trace["ops"]
    if not devs:
        return 0.0
    return sum(devtrace.covered(scoped_intervals(trace, scope, d), lo, hi)
               for d in devs) / len(devs)


def unscoped_share(trace: dict, scopes, lo: float, hi: float) -> float | None:
    """Share of the device's busy time in [lo, hi] that no scope claims."""
    busy = devtrace.busy(trace, lo, hi)
    if busy <= 0:
        return None
    claimed = sum(devtrace.covered(devtrace.union(
        iv for sc in scopes for iv in scoped_intervals(trace, sc, d)), lo, hi)
        for d in trace["ops"]) / len(trace["ops"])
    return 1.0 - claimed / busy


def op_key(text: str) -> str | None:
    """What an op event's HLO text and a compiled module's line for the
    same instruction share: its name and result type, layouts left out
    (two programs of one cell may both hold a ``%fusion.4``)."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    if not text.startswith("%") or " = " not in text:
        return None
    name, rest = text.split(" = ", 1)
    m = re.match(r"(.*?)\s[a-z][\w\-.]*\(", rest)
    typ = re.sub(r"\{[^{}]*\}", "", m.group(1)) if m else ""
    return f"{name} {typ.replace(' ', '')}"


def op_names_from_text(hlo_texts) -> dict[str, str]:
    """``op_name`` of every instruction of compiled modules' texts
    (``Compiled.as_text()``), by :func:`op_key` and by bare instruction
    name.  A key that two modules place in different scopes is left out:
    it cannot be told apart."""
    out: dict[str, str] = {}
    clash: set[str] = set()
    for text in hlo_texts:
        for line in text.splitlines():
            m = _META.search(line)
            key = op_key(line) if m else None
            if key is None:
                continue
            for k in (key, key.split(" ", 1)[0]):
                if k in out and scopes_of(out[k]) != scopes_of(m.group(1)):
                    clash.add(k)
                out[k] = m.group(1)
    return {k: v for k, v in out.items() if k not in clash}


def attach(trace: dict, compiled=None) -> dict:
    """Fill ``trace["op_names"]``, once: from the op events' own names
    where they carry the ``op_name`` metadata, else, where ``compiled``
    (a function returning the texts of the programs that ran) is given,
    from those texts by :func:`op_key`.  Returns the trace."""
    if "op_names" in trace:
        return trace
    events = {n for ops in trace["ops"].values() for n, _, _ in ops}
    names = {}
    for n in events:
        m = _META.search(n)
        if m:
            names[n] = m.group(1)
    if not names and compiled is not None:
        try:
            texts = compiled()
        except Exception:            # a metric then goes silent, no more
            traceback.print_exc()
            print("scopes: no compiled text, so no op names",
                  file=sys.stderr)
            texts = []
        by_key = op_names_from_text(texts)
        for n in events:
            key = op_key(n)
            if key is None:
                continue
            name = by_key.get(key) or by_key.get(key.split(" ", 1)[0])
            if name:
                names[n] = name
    trace["op_names"] = names
    return trace


# -- the tracer's clock -----------------------------------------------------------


def clock_offset(trace: dict, records) -> float | None:
    """Seconds that place a tracer time on the trace's clock: the start of
    the trace's first ``tracer.sync`` annotation less the last
    ``tracer.sync`` instant the tracer recorded (both in seconds)."""
    synced = [s for n, s, _ in trace["spans"] if n == "tracer.sync"]
    inst = [e["ts"] for e in records
            if e.get("type") == "instant" and e.get("name") == "tracer.sync"]
    if not synced or not inst:
        return None
    return synced[0] - inst[-1] * 1e-6


def place(trace: dict, record: dict, records) -> tuple[float, float] | None:
    """A tracer span record's (start, end) on the trace's clock."""
    off = clock_offset(trace, records)
    if off is None:
        return None
    s = record["ts"] * 1e-6 + off
    return s, s + record["dur"] * 1e-6


# -- what a traced run prints -------------------------------------------------------


def report(run: dict, compiled=None) -> None:
    """Attach the op names (:func:`attach`) and print to standard error,
    once per run: the scopes' device ms, the share of busy device time no
    scope claims, the compiles inside the traced window (and, where the
    run gives the measured window on the host clock, inside that), and
    where the sync places the tracer's ``serve.tick`` records against
    their bridged twins."""
    tr = run["trace"]
    if "op_names" in tr:
        return
    lo, hi = run["window"]
    attach(tr, compiled)
    parts = [f"{sc} {scope_seconds(tr, sc, lo, hi) * 1e3:.3f} ms"
             for sc in SCOPES]
    share = unscoped_share(tr, SCOPES, lo, hi)
    if share is not None:
        parts.append(f"unscoped {100 * share:.2f}% of busy")
    print("scopes in the traced window: " + ", ".join(parts), file=sys.stderr)
    names = tr["op_names"]
    unscoped = {"ops": {d: [o for o in ops if not scopes_of(names.get(o[0], ""))]
                        for d, ops in tr["ops"].items()}}
    for label, sec in devtrace.top_ops(unscoped, lo, hi, 8):
        name = next((v for k, v in names.items()
                     if devtrace.op_label(k) == label), "no op_name")
        print(f"  unscoped: {label} {sec * 1e3:.3f} ms ({name})",
              file=sys.stderr)
    compiles = sum(1 for n, s, _ in tr["spans"]
                   if n == "jax.compile" and lo <= s < hi)
    print(f"compiles inside the traced window: {compiles}", file=sys.stderr)
    records = program_records()
    mono_us = _mono_us()
    if "t0" in run and mono_us is not None:
        a, b = mono_us(run["t0"]), mono_us(run["end"])
        n = sum(1 for e in records if e.get("name") == "jax.compile"
                and e.get("type") == "span" and a <= e["ts"] + e["dur"] < b)
        print(f"compiles inside the measured window: {n}", file=sys.stderr)
    off = clock_offset(tr, records)
    twins = [(s, e) for n, s, e in tr["spans"] if n == "serve.tick"]
    ticks = [e for e in records
             if e.get("type") == "span" and e.get("name") == "serve.tick"]
    if off is not None and twins:
        placed = [e["ts"] * 1e-6 + off for e in ticks]
        worst = max(min(abs(p - s) for p in placed) for s, _ in twins)
        print(f"serve.tick placed by tracer.sync: {len(twins)} bridged, "
              f"worst start gap {worst * 1e3:.4f} ms", file=sys.stderr)


def program_records() -> list[dict]:
    """Every record of the system's tracer in this process, or none."""
    try:
        from repro import telemetry as tm
    except ImportError:
        return []
    return tm.snapshot()


def _mono_us():
    """The system's map from ``time.monotonic()`` to its tracer clock, or
    None where the system has none."""
    try:
        from repro import telemetry as tm
    except ImportError:
        return None
    return getattr(tm, "mono_us", None)


# -- the programs' compiled texts ---------------------------------------------------


def _compiled(fn, *args) -> list[str]:
    """``fn``'s compiled text for abstract ``args``, or none where its
    lowering names none of the scopes (a system without them)."""
    lowered = fn.lower(*args)
    text = lowered.as_text(debug_info=True)
    if not any(sc in text for sc in SCOPES):
        return []
    return [lowered.compile().as_text()]


def train_texts(run: dict) -> list[str]:
    """The cell's train step, built again as the training driver builds it
    and compiled from abstract arguments: the persistent compile cache
    gives back the run's own program."""
    import jax
    import jax.numpy as jnp

    from . import train_cell
    prog = train_cell.Program(run["cell"], jax.devices()[:run["chips"]])
    params = jax.eval_shape(prog.model.init, jax.random.key(0))
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        {"params": params, "opt": jax.eval_shape(prog.opt.init, params)},
        prog.state_shard)
    tok = jax.ShapeDtypeStruct((run["mix"]["batch"], run["mix"]["seq"]),
                               jnp.int32)
    return _compiled(prog.step_fn, state, {"inputs": tok, "targets": tok})


def serve_texts(run: dict) -> list[str]:
    """The engine's extend, decode and slot-zeroing programs, the engine
    built as the serving driver builds it, on abstract weights."""
    import jax
    import jax.numpy as jnp
    from repro.distributed import sharding
    from repro.serving import profiles as profiles_lib
    from repro.serving.engine import ServeEngine

    from .harness import build_model
    cell = run["cell"]
    e = cell.mix["engine"]
    devs = jax.devices()[:run["chips"]]
    mesh = sharding.make_mesh((len(devs), 1), ("data", "model"),
                              devices=devs)
    model, lm = build_model(cell)
    one = jax.sharding.SingleDeviceSharding(devs[0])
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: cell.reference().init_params(cell.config, k),
                       jax.random.key(0)))
    profiles_lib.build_profiles(lm, batch_size=e["slots"],
                                prefill_chunk=e["prefill_chunk"])
    engine = ServeEngine(
        model, params, batch_size=e["slots"], max_len=e["max_len"],
        shard=sharding.make_sharder(mesh), prefill_chunk=e["prefill_chunk"],
        kv_policy=e["kv_dtype"])
    B, C = e["slots"], e["prefill_chunk"]
    vec = jax.ShapeDtypeStruct((B,), jnp.int32)
    mask = jax.ShapeDtypeStruct((B,), jnp.bool_)
    state = engine._state()
    texts = (_compiled(engine._extend_fn, params,
                       jax.ShapeDtypeStruct((B, C), jnp.int32), state, vec,
                       vec, mask)
             + _compiled(engine._decode_fn, params, vec, state, vec, mask))
    if engine._zero_fn is not None:
        texts += _compiled(engine._zero_fn, state, mask)
    return texts


# -- the readers' shared parts ------------------------------------------------------


def train_ms(run: dict, scope: str) -> float | None:
    """Device ms per traced training step in ``scope``; None where no op
    of the trace lies in it."""
    report(run, lambda: train_texts(run))
    tr, (lo, hi) = run["trace"], run["window"]
    if not run.get("steps_traced") or not _present(tr, scope):
        return None
    return scope_seconds(tr, scope, lo, hi) * 1e3 / run["steps_traced"]


def tick_ms(run: dict, scope: str) -> float | None:
    """Device ms in ``scope`` per engine tick of the traced window, the
    ticks counted by the bridged ``serve.tick`` spans that start in it."""
    tr, (lo, hi) = run["trace"], run["window"]
    ticks = sum(1 for n, s, _ in tr["spans"]
                if n == "serve.tick" and lo <= s < hi)
    if not ticks:
        return None
    report(run, lambda: serve_texts(run))
    if not _present(tr, scope):
        return None
    return scope_seconds(tr, scope, lo, hi) * 1e3 / ticks


def _present(trace: dict, scope: str) -> bool:
    return any(in_scope(n, scope) for n in trace["op_names"].values())
