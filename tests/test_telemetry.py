"""Telemetry subsystem tests: tracer contract, exporters, and the exact
counter guarantees the instrumented layers make.

* disabled tracer is a strict no-op (shared noop span, no events, no
  counters, no clock reads via ``now_us``);
* spans parent correctly, including across the autotuner's measurement
  worker thread (the explicit ``current_context``/``attach`` handoff);
* JSONL stream -> Chrome trace-event JSON round-trips losslessly and
  passes the structural Perfetto schema check;
* CSSE winner-cache counters land exact values for hit / miss /
  MODEL_VERSION-invalidation;
* a chain kernel that refuses to lower degrades the compiled plan with
  an exact, queryable degrade count (and still computes the right
  answer);
* the leveled logger keeps the historical ``[component] msg`` bytes and
  switches to JSON under ``REPRO_LOG=json``.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry as tm
from repro.core import autotune, csse, factorizations as F, plan_compiler
from repro.core.plan_compiler import ChainLoweringError
from repro.telemetry import export


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with a disabled, empty tracer and
    zeroed module-level counters (they are process-global on purpose)."""
    tm.reset()
    plan_compiler.reset_degrade_counts()
    csse.reset_cache_stats()
    csse.clear_memo()
    yield
    tm.reset()
    plan_compiler.reset_degrade_counts()
    csse.reset_cache_stats()
    csse.clear_memo()


# ---------------------------------------------------------------------------
# Tracer contract
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_noop():
    assert not tm.enabled()
    s1 = tm.span("a", x=1)
    s2 = tm.span("b")
    assert s1 is s2, "disabled span must be the shared no-op singleton"
    with s1:
        pass
    tm.inc("some.counter", 5)
    tm.sample("gauge", 1.0)
    tm.event("evt", k=2)
    tm.drift("d", predicted_s=1.0, measured_s=2.0)
    tm.complete_span("c", 0.0, 1.0)
    assert tm.counters() == {}
    assert tm.snapshot() == []
    assert tm.drift_records() == []
    assert tm.now_us() == 0.0
    assert tm.current_context() is None


def test_span_nesting_and_counters():
    tm.configure()
    with tm.span("outer"):
        with tm.span("inner", tag="x"):
            tm.inc("n")
        tm.inc("n")
    evs = [e for e in tm.snapshot() if e["type"] == "span"]
    # Spans record on exit: inner first, then outer.
    inner, outer = evs
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["parent"] == outer["id"]
    assert outer["parent"] is None
    assert inner["args"] == {"tag": "x"}
    assert tm.counters() == {"n": 2}
    assert tm.current_context() is None, "context must unwind"


def test_span_context_restored_after_exception():
    tm.configure()
    with tm.span("outer"):
        with pytest.raises(ValueError):
            with tm.span("inner"):
                raise ValueError("boom")
        assert tm.current_context().name == "outer"


def test_suspended_preserves_state():
    tm.configure()
    tm.inc("kept")
    with tm.suspended():
        assert not tm.enabled()
        tm.inc("dropped")
    assert tm.enabled()
    assert tm.counters() == {"kept": 1}


def test_autotune_worker_thread_span_parenting(tmp_path):
    """The sweep span recorded on the tuner's worker thread must parent
    under the caller's span — the current_context/attach handoff."""
    tm.configure()
    tuner = autotune.Tuner(cache_dir=str(tmp_path))
    with tm.span("caller") as caller:
        tuner.record(autotune.StepShape("gemm", (8, 16, 4)))
        caller_id = caller.span_id
    spans = {e["name"]: e for e in tm.snapshot() if e["type"] == "span"}
    sweep = spans["autotune.sweep"]
    assert sweep["parent"] == caller_id
    assert sweep["tid"] != spans["caller"]["tid"], (
        "sweep runs on the worker thread, so it must land on its own lane"
    )
    assert tm.counters()["autotune.measured"] == 1
    assert tm.drift_records(), "a measured sweep must emit a drift record"


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------


def _emit_one_of_each():
    with tm.span("parent"):
        with tm.span("child", k=1):
            pass
    tm.inc("hits", 3)
    tm.sample("occupancy", 2.0)
    tm.event("mark", rid=7)
    tm.drift("model", predicted_s=0.5, measured_s=1.5, kind="gemm")
    tm.complete_span("lifecycle", 10.0, 20.0, lane="slot0", rid=7)


def test_jsonl_to_chrome_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tm.configure(path)
    _emit_one_of_each()
    tm.finalize()

    events = export.load_trace(path)
    kinds = [e["type"] for e in events]
    assert kinds.count("span") == 3
    assert "counters" in kinds and "drift" in kinds and "instant" in kinds

    chrome = export.to_chrome(events, thread_names={0: "main"})
    assert export.validate_chrome(chrome) == []
    phases = [e["ph"] for e in chrome["traceEvents"]]
    assert phases.count("X") == 3
    assert "C" in phases and "M" in phases

    back = export.from_chrome(chrome)
    spans = {e["name"]: e for e in back if e["type"] == "span"}
    assert spans["child"]["parent"] == spans["parent"]["id"]
    assert spans["child"]["args"] == {"k": 1}
    assert spans["lifecycle"]["args"]["rid"] == 7
    (drift,) = [e for e in back if e["type"] == "drift"]
    assert drift["predicted_s"] == 0.5 and drift["measured_s"] == 1.5
    assert drift["args"] == {"kind": "gemm"}
    # The finalize counter snapshot survives as per-name counter samples.
    assert {e["name"]: e["value"] for e in back if e["type"] == "counter"}["hits"] == 3


def test_chrome_file_output_validates(tmp_path):
    path = str(tmp_path / "trace.json")
    tm.configure(path)
    _emit_one_of_each()
    tm.finalize()
    with open(path) as f:
        obj = json.load(f)
    assert export.validate_chrome(obj) == []
    names = {e["args"]["name"] for e in obj["traceEvents"] if e["ph"] == "M"}
    assert "slot0" in names, "virtual lanes must be named for Perfetto"
    assert export.load_trace(path), "Chrome files load back as events"


def test_validate_chrome_catches_violations():
    bad = {
        "traceEvents": [
            {"ph": "Z", "name": "x", "ts": 0, "pid": 1, "tid": 0},
            {"ph": "X", "name": "y", "ts": -1, "pid": 1, "tid": 0},
            {"ph": "X", "name": "", "ts": 0, "pid": 1, "tid": 0, "dur": 1},
        ],
    }
    errors = export.validate_chrome(bad)
    assert len(errors) >= 3


def test_trace_report_renders(tmp_path):
    from repro.analysis import trace_report

    path = str(tmp_path / "trace.json")
    tm.configure(path)
    _emit_one_of_each()
    tm.finalize()
    events = export.load_trace(path)
    rows = trace_report.phase_table(events)
    assert {r["name"] for r in rows} == {"parent", "child", "lifecycle"}
    assert trace_report.counter_values(events)["hits"] == 3
    (drift,) = trace_report.drift_summary(events)
    assert drift["name"] == "model" and drift["count"] == 1
    assert drift["geomean_ratio"] == pytest.approx(3.0)
    lines = []
    trace_report.render(events, print_fn=lines.append)
    assert any("lifecycle" in line for line in lines)
    assert any("model" in line for line in lines)


# ---------------------------------------------------------------------------
# CSSE winner-cache counters
# ---------------------------------------------------------------------------


def _net():
    fact = F.tt((4, 4), (4, 4), 4)
    return fact.forward_network(batch_axes=(("b", 8),))


OPTS = csse.SearchOptions(objective="edp")


def test_cache_counters_exact(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CSSE_CACHE", str(tmp_path))
    tm.configure()

    first = csse.search(_net(), OPTS)
    assert first.stats["cache_stats"] == {
        "memo_hits": 0,
        "disk_hits": 0,
        "misses": 1,
        "invalidations": 0,
    }

    second = csse.search(_net(), OPTS)
    assert second.stats["cache_stats"]["memo_hits"] == 1

    csse.clear_memo()
    third = csse.search(_net(), OPTS)
    assert third.stats["cache_stats"]["disk_hits"] == 1

    assert csse.CACHE_STATS == {
        "memo_hits": 1,
        "disk_hits": 1,
        "misses": 1,
        "invalidations": 0,
    }
    counters = tm.counters()
    assert counters["csse.cache.misses"] == 1
    assert counters["csse.cache.memo_hits"] == 1
    assert counters["csse.cache.disk_hits"] == 1
    assert "csse.cache.invalidations" not in counters


def test_model_version_invalidates_memo_and_disk(tmp_path, monkeypatch):
    from repro.core import perf_model

    monkeypatch.setenv("REPRO_CSSE_CACHE", str(tmp_path))
    tm.configure()

    csse.search(_net(), OPTS)
    assert csse.CACHE_STATS["misses"] == 1

    # A model-semantics bump invalidates BOTH stale entries on the next
    # search: the in-process memo one, then the disk file it falls
    # through to (each ranked under the old version).
    monkeypatch.setattr(perf_model, "MODEL_VERSION", perf_model.MODEL_VERSION + 1)
    res = csse.search(_net(), OPTS)
    assert csse.CACHE_STATS["invalidations"] == 2
    assert csse.CACHE_STATS["misses"] == 2
    assert res.stats["cache_stats"]["invalidations"] == 2

    # The fresh search rewrote the disk entry under the new version:
    # another bump plus a cleared memo exercises the disk-only path.
    monkeypatch.setattr(perf_model, "MODEL_VERSION", perf_model.MODEL_VERSION + 1)
    csse.clear_memo()
    csse.search(_net(), OPTS)
    assert csse.CACHE_STATS["invalidations"] == 3
    assert tm.counters()["csse.cache.invalidations"] == 3


# ---------------------------------------------------------------------------
# Chain-degrade accounting
# ---------------------------------------------------------------------------


def _chain_plan():
    fact = F.tt((16,), (16,), 8)
    net = fact.forward_network(batch_axes=(("b", 64),))
    plan = csse.search(net, csse.SearchOptions(fused_chain=True)).plan
    arrays = [
        jax.random.normal(jax.random.key(i), net.node_shape(i), jnp.float32)
        for i in range(net.num_nodes)
    ]
    return plan, arrays


def _refuse(*args, **kwargs):
    raise ChainLoweringError("test kernel refuses every chain")


def test_runtime_chain_degrade_exact_count(monkeypatch):
    plan, arrays = _chain_plan()
    compiled = plan_compiler.compile_plan(plan)
    num_chain = compiled.report()["num_chain"]
    assert num_chain >= 1
    want = plan_compiler.run(compiled, arrays)

    tm.configure()
    monkeypatch.setattr(plan_compiler, "chain_n_pallas", _refuse)
    got = plan_compiler.run(compiled, arrays)

    assert plan_compiler.DEGRADE_COUNTS["runtime"] == num_chain
    assert plan_compiler.DEGRADE_COUNTS["compile"] == 0
    assert tm.counters()["plan_compiler.chain_degrade.runtime"] == num_chain
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )

    # Every occurrence is counted: a second run doubles the figure.
    plan_compiler.run(compiled, arrays)
    assert plan_compiler.DEGRADE_COUNTS["runtime"] == 2 * num_chain
    assert tm.counters()["plan_compiler.chain_degrade.runtime"] == 2 * num_chain


def test_compile_chain_degrade_exact_count(monkeypatch):
    plan, arrays = _chain_plan()
    num_chain = plan_compiler.compile_plan(plan).report()["num_chain"]
    assert num_chain >= 1

    tm.configure()
    monkeypatch.setattr(plan_compiler, "_build_chain", _refuse)
    compiled = plan_compiler.compile_plan(plan)

    assert compiled.report()["num_chain"] == 0
    assert plan_compiler.DEGRADE_COUNTS["compile"] == num_chain
    assert tm.counters()["plan_compiler.chain_degrade.compile"] == num_chain
    want = plan_compiler.run(plan_compiler.compile_plan(plan, fuse=False), arrays)
    got = plan_compiler.run(compiled, arrays)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )


def test_degrade_counts_without_tracer(monkeypatch):
    """DEGRADE_COUNTS must count even with telemetry disabled — silent
    degrades are the failure mode this PR exists to kill."""
    plan, arrays = _chain_plan()
    compiled = plan_compiler.compile_plan(plan)
    num_chain = compiled.report()["num_chain"]
    monkeypatch.setattr(plan_compiler, "chain_n_pallas", _refuse)
    assert not tm.enabled()
    plan_compiler.run(compiled, arrays)
    assert plan_compiler.DEGRADE_COUNTS["runtime"] == num_chain
    assert tm.counters() == {}


# ---------------------------------------------------------------------------
# Leveled logger
# ---------------------------------------------------------------------------


def test_logger_default_format_is_byte_identical(capsys, monkeypatch):
    monkeypatch.delenv("REPRO_LOG", raising=False)
    tm.get_logger("train").info("step 3 loss 1.25")
    assert capsys.readouterr().out == "[train] step 3 loss 1.25\n"


def test_logger_json_mode(capsys, monkeypatch):
    monkeypatch.setenv("REPRO_LOG", "json")
    tm.get_logger("serve").info("request done")
    rec = json.loads(capsys.readouterr().out)
    assert rec["component"] == "serve"
    assert rec["level"] == "info"
    assert rec["msg"] == "request done"


def test_logger_level_threshold(capsys, monkeypatch):
    monkeypatch.setenv("REPRO_LOG", "warn")
    log = tm.get_logger("train")
    log.info("hidden")
    log.warn("shown")
    out = capsys.readouterr().out
    assert "hidden" not in out
    assert out == "[train] WARN: shown\n"


def test_warn_once_mirrors_into_trace(capsys, monkeypatch):
    monkeypatch.delenv("REPRO_LOG", raising=False)
    tm.configure()
    log = tm.get_logger("plan_compiler")
    log.warn_once("key", "degraded")
    log.warn_once("key", "degraded")
    out = capsys.readouterr().out
    assert out.count("WARN") == 1
    events = [e for e in tm.snapshot() if e["type"] == "instant"]
    assert len(events) == 1 and events[0]["name"] == "log.warn"


# ---------------------------------------------------------------------------
# The profiler's clock and the compile counter
# ---------------------------------------------------------------------------


def test_sync_profiler_places_records_on_the_profiler_clock(tmp_path):
    """In a real CPU capture, the ``tracer.sync`` pair places a bridged
    span and a ``complete_span`` of the same stretch within 1 ms of the
    profiler's own event for the bridged span."""
    from benchmarks.chip import devtrace, scopes

    tm.configure(jax_bridge=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tm.sync_profiler() is not None
        time.sleep(0.02)
        t0 = tm.now_us()
        with tm.span("probe.bridged"):
            time.sleep(0.01)
        tm.complete_span("probe.complete", t0, tm.now_us())
    finally:
        jax.profiler.stop_trace()
    trace = devtrace.load(str(tmp_path))
    (twin,) = [(s, e) for n, s, e in trace["spans"] if n == "probe.bridged"]
    records = {e["name"]: e for e in tm.snapshot() if e["type"] == "span"}
    for name in ("probe.bridged", "probe.complete"):
        s, e = scopes.place(trace, records[name], tm.snapshot())
        assert abs(s - twin[0]) < 1e-3 and abs(e - twin[1]) < 1e-3, name


def test_bridge_syncs_at_the_first_span_of_a_capture(tmp_path):
    tm.configure(jax_bridge=True)
    with tm.span("before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with tm.span("inside"):
                pass
    finally:
        jax.profiler.stop_trace()
    syncs = [e for e in tm.snapshot() if e["name"] == "tracer.sync"]
    assert len(syncs) == 1


def test_compile_counter_counts_recompiles_only():
    tm.configure()
    f = jax.jit(lambda x: jnp.sin(x) * 3.0)
    f(np.ones(7, np.float32)).block_until_ready()
    n = tm.counters().get("jax.compiles", 0)
    assert n >= 1
    f(np.ones(7, np.float32)).block_until_ready()
    assert tm.counters()["jax.compiles"] == n, "same shapes: no compile"
    f(np.ones(9, np.float32)).block_until_ready()
    assert tm.counters()["jax.compiles"] == n + 1, "new shape: one compile"
    spans = [e for e in tm.snapshot() if e["type"] == "span"
             and e["name"] == "jax.compile"]
    assert len(spans) == n + 1 and all(e["dur"] >= 0 for e in spans)


# ---------------------------------------------------------------------------
# Named scopes in the compiled programs
# ---------------------------------------------------------------------------


def _op_names(compiled) -> list[str]:
    import re
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


def _has_scope(names, scope) -> bool:
    import re
    pat = re.compile(rf"(^|[/(]){scope}([/)]|$)")
    return any(pat.search(n) for n in names)


def test_train_step_hlo_names_its_layers():
    """The TT phases, attention and the LM head stay named in the compiled
    smoke-size train step, whatever scan, remat or grad do to the path."""
    import dataclasses

    from repro.configs import base as cfgbase
    from repro.launch import steps
    from repro.optim.adamw import AdamW

    arch = cfgbase.get("internlm2_1_8b")
    tnn = dataclasses.replace(arch.tnn_default, enabled=True, rank=8,
                              num_factors=2)
    model, _ = steps.build_model(arch, tnn=tnn, smoke=True)
    opt = AdamW(lr=1e-3)
    step = jax.jit(steps.make_train_step(model, opt, lambda x, a: x))
    params = jax.eval_shape(model.init, jax.random.key(0))
    state = {"params": params, "opt": jax.eval_shape(opt.init, params)}
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    names = _op_names(step.lower(state, {"inputs": tok, "targets": tok})
                      .compile())
    for scope in ("tt_fp", "tt_bp", "tt_wg", "attention", "lm_head"):
        assert _has_scope(names, scope), scope


def _engine_lowered(arch: str):
    """A smoke-size engine of ``arch`` and its lowered extend and decode
    programs."""
    from repro.configs import base as cfgbase
    from repro.launch import steps
    from repro.serving.engine import ServeEngine

    model, _ = steps.build_model(cfgbase.get(arch), smoke=True)
    eng = ServeEngine(model, model.init(jax.random.key(0)), batch_size=2,
                      max_len=32, prefill_chunk=4)
    vec = jnp.zeros(2, jnp.int32)
    off = jnp.zeros(2, bool)
    ext = eng._extend_fn.lower(eng.params, jnp.zeros((2, 4), jnp.int32),
                               eng._state(), vec, vec, off)
    dec = eng._decode_fn.lower(eng.params, vec, eng._state(), vec, off)
    return eng, (ext, dec)


def _engine_programs(arch: str):
    """A smoke-size engine of ``arch`` and its compiled extend and decode
    programs."""
    eng, lowered = _engine_lowered(arch)
    return eng, tuple(low.compile() for low in lowered)


def test_engine_programs_update_the_cache_in_place():
    """The native bf16 tick programs write the cache in place: (a) every
    cache leaf they take is donated and aliased to an output, and (b) no
    dynamic_update_slice writes a block as large as one layer's
    ``[B, cache_len, KV, hd]`` cache, which is what stacking each layer's
    whole K and V back as a scan output does."""
    import math
    import re
    eng, lowered = _engine_lowered("internlm2_1_8b")
    layer = eng._state().layers.k[0].size
    for low in lowered:
        text = low.compile().as_text()
        head = text.splitlines()[0]
        aliased = {int(n) for n in re.findall(r"\(\s*(\d+),\s*\{\}", head)}
        cache = {int(m.group(1)) for m in re.finditer(
            r'parameter\((\d+)\).*op_name="cache\.', text)}
        assert len(cache) >= 2
        assert cache <= aliased, (cache, aliased)

        updates = [m.group(1) for m in re.finditer(
            r"stablehlo\.dynamic_update_slice .*?: \(tensor<[^>]*>, "
            r"tensor<([^>]*)>", low.as_text())]
        assert updates
        for dims in updates:
            assert math.prod(int(d) for d in dims.split("x")[:-1]) < layer


def test_engine_programs_name_the_select():
    _, programs = _engine_programs("internlm2_1_8b")
    for compiled in programs:
        names = _op_names(compiled)
        assert _has_scope(names, "engine_select")
        assert _has_scope(names, "attention")


def _largest_in_scope(compiled, scope) -> int:
    """Elements of the largest output of an instruction in ``scope``."""
    import math
    import re
    most = 0
    for line in compiled.as_text().splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if not m or not _has_scope([m.group(1)], scope) or " = " not in line:
            continue
        rest = line.split(" = ", 1)[1]
        typ = re.match(r"(.*?)\s[a-z][\w\-.]*\(", rest).group(1)
        for dims in re.findall(r"\[([\d,]*)\]", typ):
            most = max(most, math.prod(int(d) for d in dims.split(",") if d))
    return most


@pytest.mark.parametrize("arch,whole", [("internlm2_1_8b", False),
                                        ("rwkv6_7b", True)])
def test_engine_select_spans_written_rows_or_whole_state(arch, whole):
    """The tick programs' ``engine_select`` scope holds only what one
    layer freezes.  On an attention stack that is the chunk-sized rows
    written, smaller than one layer's ``[B, cache_len, KV, hd]`` cache; on
    a state stack (RWKV-6) it is one layer's ``[B, ...]`` state, never the
    whole stacked state.  Only the slot-zeroing at admission selects over
    the whole state."""
    eng, programs = _engine_programs(arch)
    state = eng._state()
    if whole:
        layer = max(a[0].size for a in jax.tree.leaves(state.layers))
    else:
        layer = state.layers.k[0].size
    for compiled in programs:
        largest = _largest_in_scope(compiled, "engine_select")
        assert largest > 0
        assert (largest == layer) if whole else (largest < layer)
    zero = eng._zero_fn.lower(state, jnp.zeros(eng.batch, bool)).compile()
    assert (_largest_in_scope(zero, "engine_select")
            == max(a.size for a in jax.tree.leaves(state)))
