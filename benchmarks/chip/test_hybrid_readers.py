"""CPU tests of the hybrid cell's readers (``mamba_ms.hybrid``,
``ssd_ms.hybrid``, ``ssd_roofline.hybrid``, ``step_mfu.hybrid``) on
synthetic traces, of ``flops_hybrid``'s counts, and of the scopes in the
tiny hybrid train step's compiled text."""

from __future__ import annotations

import json
import os

import pytest

from . import devtrace, flops_hybrid, harness, scopes, test_cell_hybrid
from .test_scopes import DEV, reader

TEXT = """HloModule jit_train_step
  %fusion.1 = bf16[4]{0} fusion(%a), metadata={op_name="s/jvp()/while/body/closed_call/mamba/dot_general"}
  %linear_scan.2 = (bf16[4]{0}, f32[2]{0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="s/jvp()/while/body/closed_call/mamba/ssd/jit(linear_scan)/pallas_call"}
  %fusion.3 = bf16[4]{0} fusion(%a), metadata={op_name="s/transpose(jvp())/while/body/closed_call/checkpoint/mamba/ssd/jit(linear_scan)/jvp()/transpose"}
  %fusion.4 = bf16[4]{0} fusion(%a), metadata={op_name="s/jvp()/while/body/closed_call/attention/dot_general"}
  %custom-call.5 = bf16[4]{0} custom-call(), custom_call_target="AllocateBuffer", metadata={op_name="s/transpose(jvp())/while/body/closed_call/checkpoint/mamba/ssd/jit(linear_scan)"}
"""
EVENTS = {1: "%fusion.1 = bf16[4]{0} fusion(bf16[4] %a)",
          2: "%linear_scan.2 = (bf16[4]{0}, f32[2]{0}) custom-call(bf16[4] %a)",
          3: "%fusion.3 = bf16[4]{0} fusion(bf16[4] %a)",
          4: "%fusion.4 = bf16[4]{0} fusion(bf16[4] %a)",
          5: "%custom-call.5 = bf16[4]{0} custom-call()"}
CONFIG = os.path.join(os.path.dirname(__file__), "configs",
                      "granite-4.0-h-micro-tt.json")


def hybrid_run(monkeypatch, text=TEXT):
    """Three traced steps; in each, op i starts at i tenths of a second
    and lasts i centiseconds."""
    monkeypatch.setattr(scopes, "train_texts", lambda run: [text])
    with open(CONFIG) as f:
        cfg = json.load(f)
    ops = [(EVENTS[i], s + 0.1 * i, s + 0.11 * i) for s in (0.0, 1.0, 2.0)
           for i in EVENTS]
    return {"trace": {"ops": {DEV: ops}, "spans": []}, "window": (0.0, 3.0),
            "devtrace": devtrace, "steps_traced": 3, "config": cfg,
            "mix": {"batch": 2, "seq": 4096}, "chips": 1,
            "peak": {"flops_s": 197e12, "hbm_bytes_s": 819e9}}


@pytest.mark.parametrize("name,ms", [("mamba_ms.hybrid", 110.0),
                                     ("ssd_ms.hybrid", 100.0)])
def test_scope_readers(monkeypatch, name, ms):
    assert reader(name)(hybrid_run(monkeypatch)) == pytest.approx(ms)


@pytest.mark.parametrize("name", ["mamba_ms.hybrid", "ssd_ms.hybrid",
                                  "ssd_roofline.hybrid"])
def test_readers_are_silent_without_scopes(monkeypatch, name):
    assert reader(name)(hybrid_run(monkeypatch, text="")) is None


def test_ssd_roofline_reads_the_kernel_calls(monkeypatch):
    run = hybrid_run(monkeypatch)
    fl, nb = flops_hybrid.ssd_call(2, 64, 1, 4096, 128, 64, 256)
    least = max(fl / 197e12, nb / 819e9)
    # three kernel calls of 20 ms each; the buffer allocations in the same
    # scope are no kernel calls
    assert reader("ssd_roofline.hybrid")(run) == pytest.approx(
        100.0 * least / 0.02)


def test_step_mfu_reader(monkeypatch):
    run = hybrid_run(monkeypatch)
    work = 3 * flops_hybrid.train_step_flops(run["config"], 2, 4096)
    assert reader("step_mfu.hybrid")(run) == pytest.approx(
        100.0 * work / (3.0 * 197e12))


def test_ssd_flops_counts():
    # one chunk over the whole sequence: every (query, key) pair once
    T, N, P = 8, 4, 2
    pairs = T * (T + 1) // 2
    assert flops_hybrid.ssd_flops(1, 1, T, N, P, T) == (
        2 * pairs * N + 2 * pairs * P + 4 * T * N * P)
    # C B^T is shared by the heads of a group
    shared = flops_hybrid.ssd_flops(1, 3, T, N, P, 4)
    apart = flops_hybrid.ssd_flops(3, 1, T, N, P, 4)
    assert apart - shared == 2 * 2 * (2 * 10) * N
    # a call's floor: X in and Y out, dt and the final state per head;
    # B and C once per group and row
    fl, nb = flops_hybrid.ssd_call(2, 3, 1, T, N, P, 4)
    assert fl == flops_hybrid.ssd_flops(2, 3, T, N, P, 4)
    assert nb == 2 * 3 * (T * (2 * 2 * P + 4) + 4 * N * P) + 2 * T * 2 * 2 * N


def test_hybrid_train_step_names_the_scopes(tmp_path):
    name = test_cell_hybrid.make_checkout(str(tmp_path))
    cell = harness.load_cell(name, str(tmp_path), str(tmp_path / "bench"))
    harness.prepare_program(str(tmp_path))
    run = {"cell": cell, "mix": cell.mix, "chips": 1}
    names = scopes.op_names_from_text(scopes.train_texts(run)).values()
    for sc in ("mamba", "ssd", "attention", "lm_head", "tt_fp"):
        assert any(scopes.in_scope(n, sc) for n in names), sc
