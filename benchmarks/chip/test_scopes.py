"""CPU tests of device time by named scope (``scopes.py``) and of the
readers of the scoped and engine-phase metrics, on synthetic traces and
run data, and of the programs' compiled texts at smoke size."""

from __future__ import annotations

import importlib.util
import os

import pytest

from . import cellrun, devtrace, harness, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"

# Two programs of one cell that both hold a %fusion.4, as the serving
# engine's extend and decode programs do, with different result types.
EXTEND = """HloModule jit_extend_fn
ENTRY %main {
  %fusion.4 = bf16[2,4,8]{2,1,0} fusion(%p0, %p1), kind=kLoop, calls=%f4, metadata={op_name="jit(extend_fn)/engine_select/select_n"}
  ROOT %fusion.7 = (bf16[2,8]{1,0}, f32[2]{0}) fusion(%p2), kind=kOutput, calls=%f7, metadata={op_name="jit(extend_fn)/while/body/closed_call/attention/dot_general"}
}
"""
DECODE = """HloModule jit_decode_fn
ENTRY %main {
  %fusion.4 = bf16[2,1,8]{2,1,0} fusion(%p0), kind=kLoop, calls=%f4, metadata={op_name="jit(decode_fn)/while/body/closed_call/attention/add"}
  %dot.3 = f32[2,8]{1,0} dot(%a, %b), metadata={op_name="jit(decode_fn)/transpose(jvp(lm_head))/dot_general"}
  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f9, metadata={op_name="jit(decode_fn)/transpose(jvp())/while/body/closed_call/tt_bp/mul"}
}
"""


def ev(text: str) -> str:
    """An op event's name as the profiler gives it: the HLO text, with
    operand types, without the metadata."""
    return text.split(", metadata=")[0].replace("%p0", "bf16[2] %p0")


def synthetic():
    sel, att, att2, head, bp = (
        ev("%fusion.4 = bf16[2,4,8]{2,1,0} fusion(%p0, %p1), kind=kLoop"),
        ev("%fusion.7 = (bf16[2,8]{1,0}, f32[2]{0}) fusion(%p2), kind=kOutput"),
        ev("%fusion.4 = bf16[2,1,8]{2,1,0} fusion(%p0), kind=kLoop"),
        ev("%dot.3 = f32[2,8]{1,0} dot(%a, %b)"),
        ev("%fusion.9 = f32[8]{0} fusion(%a), kind=kLoop"))
    return {"ops": {DEV: [(sel, 0.0, 1.0), (att, 1.0, 1.5), (att2, 2.0, 2.25),
                          (head, 3.0, 3.5), (bp, 4.0, 4.1),
                          ("%copy.1 = f32[8]{0} copy(%a)", 5.0, 6.0),
                          (sel, 6.0, 7.0)]},
            "spans": [("bench.window", 0.0, 10.0)]}


def test_in_scope_matches_segments_and_transforms():
    assert scopes.in_scope("jit(f)/jvp(lm_head)/dot_general", "lm_head")
    assert scopes.in_scope("jit(f)/transpose(jvp(lm_head))/mul", "lm_head")
    assert scopes.in_scope("a/rematted_computation/tt_fp/dot_general", "tt_fp")
    assert scopes.in_scope("jit(f)/attention", "attention")
    assert not scopes.in_scope("jit(f)/lm_head_norm/add", "lm_head")
    assert not scopes.in_scope("jit(f)/my_attention/add", "attention")


def test_scope_seconds_tells_two_programs_apart():
    tr = scopes.attach(synthetic(), lambda: [EXTEND, DECODE])
    assert scopes.scope_seconds(tr, "engine_select", 0.0, 10.0) == 2.0
    # the extend program's fusion.7 and the decode program's fusion.4
    assert scopes.scope_seconds(tr, "attention", 0.0, 10.0) == 0.75
    assert scopes.scope_seconds(tr, "lm_head", 0.0, 10.0) == 0.5
    assert scopes.scope_seconds(tr, "tt_bp", 0.0, 10.0) == pytest.approx(0.1)
    assert scopes.scope_seconds(tr, "tt_fp", 0.0, 10.0) == 0.0
    # clipped to the window
    assert scopes.scope_seconds(tr, "engine_select", 0.5, 6.5) == 1.0
    # busy 4.35 s, of which the copy's 1 s lies in no scope
    share = scopes.unscoped_share(tr, scopes.SCOPES, 0.0, 10.0)
    assert share == pytest.approx(1.0 / 4.35)


def test_op_names_from_the_events_own_metadata():
    tr = {"ops": {DEV: [('%fusion.2 = f32[4]{0} fusion(%a), metadata={'
                         'op_name="jit(s)/jvp(tt_fp)/mul"}', 0.0, 2.0)]},
          "spans": []}
    scopes.attach(tr, lambda: pytest.fail("the events carry their names"))
    assert scopes.scope_seconds(tr, "tt_fp", 0.0, 3.0) == 2.0


def test_a_key_two_programs_name_differently_is_left_out():
    # the same scope under another program's name is no clash
    same = EXTEND.replace("jit(extend_fn)", "jit(other_fn)")
    assert len(scopes.op_names_from_text([EXTEND, same])) == 4
    other = EXTEND.replace("engine_select/select_n", "lm_head/select_n")
    names = scopes.op_names_from_text([EXTEND, other])
    assert scopes.op_key("%fusion.4 = bf16[2,4,8]{2,1,0} fusion(%p0)") \
        not in names
    assert "%fusion.4" not in names and "%fusion.7" in names
    # an event whose text gives no result type falls back to its bare
    # name, where that is unambiguous
    tr = {"ops": {DEV: [("%fusion.7 = fusion(%p2)", 0.0, 1.0),
                        ("%fusion.4 = fusion(%p0)", 1.0, 2.0)]}, "spans": []}
    scopes.attach(tr, lambda: [EXTEND, other])
    assert scopes.scope_seconds(tr, "attention", 0.0, 2.0) == 1.0
    assert list(tr["op_names"]) == ["%fusion.7 = fusion(%p2)"]


def test_op_key_leaves_layouts_and_operands_out():
    a = scopes.op_key("ROOT %f.1 = (bf16[2,8]{1,0:T(8,128)}, f32[2]{0}) "
                      "fusion(bf16[2] %p), kind=kLoop")
    b = scopes.op_key("%f.1 = (bf16[2,8]{1,0}, f32[2]{0}) fusion(%p)")
    assert a == b == "%f.1 (bf16[2,8],f32[2])"
    assert scopes.op_key("not an instruction") is None


def test_place_puts_tracer_records_on_the_trace_clock():
    tr = {"ops": {}, "spans": [("tracer.sync", 5.0, 5.00001),
                               ("serve.tick", 5.2, 5.3)]}
    records = [{"type": "instant", "name": "tracer.sync", "ts": 1e6},
               {"type": "span", "name": "serve.tick", "ts": 1.2e6,
                "dur": 1e5}]
    assert scopes.place(tr, records[1], records) == pytest.approx((5.2, 5.3))
    assert scopes.place({"ops": {}, "spans": []}, records[1], records) is None


# -- the readers ------------------------------------------------------------------


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "test_metric_" + name.replace(".", "_"),
        os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


TRAIN_TEXT = """HloModule jit_train_step
  %fusion.1 = bf16[4]{0} fusion(%a), metadata={op_name="s/jvp()/while/body/rematted_computation/tt_fp/dot_general"}
  %fusion.2 = bf16[4]{0} fusion(%a), metadata={op_name="s/transpose(jvp())/while/body/tt_bp/dot_general"}
  %fusion.3 = bf16[4]{0} fusion(%a), metadata={op_name="s/transpose(jvp())/while/body/tt_wg/dot_general"}
  %fusion.4 = bf16[4]{0} fusion(%a), metadata={op_name="s/jvp()/while/body/attention/dot_general"}
  %fusion.5 = bf16[4]{0} fusion(%a), metadata={op_name="s/transpose(jvp(lm_head))/dot_general"}
"""


def train_run(monkeypatch, text=TRAIN_TEXT):
    monkeypatch.setattr(scopes, "train_texts", lambda run: [text])
    ops = [(f"%fusion.{i} = bf16[4]{{0}} fusion(bf16[4] %a)", s, s + 0.01 * i)
           for s in (0.0, 1.0, 2.0) for i in range(1, 6)]
    return {"trace": {"ops": {DEV: ops}, "spans": []}, "window": (0.0, 3.0),
            "devtrace": devtrace, "steps_traced": 3}


@pytest.mark.parametrize("name,ms", [
    ("tt_fp_ms.train", 10.0), ("tt_bp_ms.train", 20.0),
    ("tt_wg_ms.train", 30.0), ("attn_ms.train", 40.0),
    ("lmhead_ms.train", 50.0)])
def test_train_readers(monkeypatch, name, ms):
    assert reader(name)(train_run(monkeypatch)) == pytest.approx(ms)


def test_train_readers_are_silent_without_scopes(monkeypatch):
    run = train_run(monkeypatch, text="")
    assert all(reader(n)(run) is None for n in (
        "tt_fp_ms.train", "tt_bp_ms.train", "tt_wg_ms.train",
        "attn_ms.train", "lmhead_ms.train"))


def test_select_reader(monkeypatch):
    monkeypatch.setattr(scopes, "serve_texts", lambda run: [EXTEND, DECODE])
    tr = synthetic()
    tr["spans"] += [("serve.tick", t, t + 0.9) for t in range(8)]
    run = {"trace": tr, "window": (0.0, 10.0), "devtrace": devtrace}
    assert reader("select_ms.chat")(run) == pytest.approx(2000.0 / 8)
    tr = synthetic()        # no bridged ticks: a system without them
    assert reader("select_ms.chat")({"trace": tr, "window": (0.0, 10.0),
                                     "devtrace": devtrace}) is None


def test_host_reader():
    from repro import telemetry as tm
    t0 = 100.0
    at = tm.mono_us

    def span(name, start_s, dur_s):
        return {"type": "span", "name": name, "ts": at(t0 + start_s),
                "dur": dur_s * 1e6}

    spans = [span("serve.tick", 0.0, 0.100), span("serve.fetch", 0.01, 0.08),
             span("serve.tick", 0.2, 0.100), span("serve.fetch", 0.21, 0.05),
             span("serve.fetch", 0.27, 0.02),
             span("serve.tick", 0.4, 0.100),
             span("serve.tick", 9.0, 0.5)]            # after the window
    run = {"program_spans": spans, "t0": t0, "end": t0 + 1.0}
    # host times 20, 30 and 100 ms
    assert reader("host_ms.chat")(run) == pytest.approx(30.0)
    assert reader("host_ms.chat")({"program_spans": [], "t0": t0,
                                   "end": t0 + 1.0}) is None


# -- the programs' compiled texts ------------------------------------------------------


@pytest.mark.parametrize("mix,scoped", [
    ("tiny-train", ("tt_fp", "tt_bp", "tt_wg", "attention", "lm_head")),
    ("tiny-serve", ("engine_select", "attention", "tt_fp"))])
def test_compiled_texts_name_the_scopes(tmp_path, mix, scoped):
    name = cellrun.make_checkout(str(tmp_path), mix)
    cell = harness.load_cell(name, str(tmp_path), str(tmp_path / "bench"))
    harness.prepare_program(str(tmp_path))
    run = {"cell": cell, "mix": cell.mix, "chips": 1}
    texts = (scopes.train_texts if mix == "tiny-train"
             else scopes.serve_texts)(run)
    names = scopes.op_names_from_text(texts).values()
    for sc in scoped:
        assert any(scopes.in_scope(n, sc) for n in names), sc
