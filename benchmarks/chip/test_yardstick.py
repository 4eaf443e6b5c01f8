"""CPU tests of the benchmark's yardstick: FLOP counts, trace reduction,
traffic, the manifest, and the plain reference against the system."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from . import devtrace, flops, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- FLOPs ----------------------------------------------------------------------


def test_tt_least_flops_by_hand():
    # W[4, 6] as two TT cores G0[m0=4, r=2] G1[r=2, n0=6], one token.
    # (X G1) G0 = 2*6*2 + 2*2*4 = 40; X (G1 G0) = 2*4*2*6 + 2*6*4 = 144;
    # (X G0) G1 = 1*6*4*2 (no sum) + 2*4*6*2 = 144.  Least: 40.
    assert flops.tt_forward_flops(4, 6, 2, 1, 1) == 40
    # Nine tokens: (X G1) G0 = 9 * 40, X W = 96 + 9 * 48 = 528 > 360.
    assert flops.tt_forward_flops(4, 6, 2, 1, 9) == 360


def test_tt_train_flops_by_hand():
    # W[4, 6], r = 2, one token.  Forward 40 (above).  Input gradient
    # dX[n] from dY[m]: (dY G0) G1 = 2*4*2 + 2*2*6 = 40.  Core gradients
    # one by one: dG0[m, r] = dY[b, m] (X G1)[b, r] = 24 + 2*4*2 = 40, and
    # dG1[r, n] = (dY G0)[b, r] X[b, n] = 16 + 2*2*6 = 40.  Through
    # dW = dY X (2*4*6 = 48): dG0 = dW G1 and dG1 = G0 dW at 96 each, 240.
    assert flops.tt_train_flops(4, 6, 2, 1, 1) == 40 + 40 + 80


def test_attention_flops_by_hand():
    # One head of 2, four tokens, causal: 1+2+3+4 = 10 pairs, each
    # 2*2 for q.k and 2*2 for p.v.
    fl, nb = flops.flash_call(1, 4, 1, 1, 2)
    assert fl == 10 * 8
    # q, k, v and out in bf16 (4 * 4 * 2 * 2 bytes) and lse in f32.
    assert nb == 4 * 4 * 2 * 2 + 4 * 4
    assert flops.attention_pairs(3, 2) == 2 * 3 + 3


def test_train_step_flops_counts_each_part():
    cfg = json.load(open(os.path.join(HERE, "testdata", "smoke-tt.json")))
    B, T = 2, 8
    f = flops.forward_flops(cfg, B * T, B * flops.attention_pairs(0, T))
    d, hd, H, KV, V, L = 64, 16, 4, 2, 256, 2
    assert f["proj"] == L * 2 * B * T * (d * H * hd * 2 + d * KV * hd * 2)
    assert f["head"] == 2 * B * T * d * V
    assert f["attn"] == L * 4 * hd * H * B * 36
    total = flops.train_step_flops(cfg, B, T)
    assert total > 3 * (f["proj"] + f["attn"] + f["head"]) + f["mlp"]


# -- trace reduction ---------------------------------------------------------------


def synthetic_trace():
    ar = ("%all-reduce.1 = f32[8] all-reduce(f32[8] %x)", 0.0, 0.0)
    return {
        "ops": {
            "/device:TPU:0": [
                ("%fusion.1 = bf16[2] fusion(bf16[2] %a)", 0.0, 1.0),
                ("%k.2 = (bf16[8,64,128], f32[8,1,64]) custom-call(bf16[8])",
                 1.5, 2.5),
                (ar[0], 2.0, 3.0),
                ("%fusion.1 = bf16[2] fusion(bf16[2] %a)", 4.0, 5.0)],
            "/device:TPU:1": [
                ("%fusion.1 = bf16[2] fusion(bf16[2] %a)", 0.0, 2.0),
                (ar[0], 2.0, 4.0)],
        },
        "spans": [("bench.window", 0.0, 6.0), ("bench.step", 0.0, 3.5),
                  ("sample", 3.0, 3.8), ("bench.step", 3.5, 6.0)],
    }


def test_trace_busy_idle_and_window():
    tr = synthetic_trace()
    assert devtrace.window(tr, "bench.window") == (0.0, 6.0)
    # device 0: [0,1] [1.5,3] [4,5] = 3.5 s; device 1: [0,4] = 4 s.
    assert devtrace.busy(tr, 0.0, 6.0) == pytest.approx(3.75)


def test_trace_kernel_and_collective_time():
    tr = synthetic_trace()
    ev = devtrace.kernel_events(tr, "f32[8,1,64]", 0.0, 6.0)
    assert [(s, e) for _, s, e in ev] == [(1.5, 2.5)]
    # device 0: all-reduce [2,3] overlaps the custom call until 2.5;
    # device 1: [2,4] with nothing else after 2.  (0.5 + 2) / 2.
    assert devtrace.exposed_collective_s(tr, 0.0, 6.0) == pytest.approx(1.25)


def test_trace_breakdown():
    tr = synthetic_trace()
    top = dict(devtrace.top_ops(tr, 0.0, 6.0))
    assert top["%fusion.1 fusion"] == pytest.approx((2.0 + 2.0) / 2)
    gaps = devtrace.idle_gaps(tr, 0.0, 6.0)
    # device 0 idles [1,1.5], [3,4] (host sampling) and [5,6].
    assert gaps[0] == ("sample", pytest.approx(1.0))
    assert sorted(g for _, g in gaps) == pytest.approx([0.5, 1.0, 1.0])


# -- traffic ---------------------------------------------------------------------


def mix(kind="poisson"):
    m = json.load(open(os.path.join(HERE, "mixes", "serve-chat.json")))
    if kind == "closed":
        m["arrival"] = {"kind": "closed", "clients": 6,
                        "requests_per_client": 4}
    return m


@pytest.mark.parametrize("kind", ["poisson", "closed"])
def test_traffic_deterministic_by_seed(kind):
    a = traffic.generate(mix(kind), 2**31 + 5, 30, 1000)
    b = traffic.generate(mix(kind), 2**31 + 5, 30, 1000)
    c = traffic.generate(mix(kind), 2**31 + 6, 30, 1000)
    key = lambda specs: [(s.due_s, s.max_new, s.temperature,  # noqa: E731
                          s.prompt.tobytes()) for s in specs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # the same work, in another order
    assert sorted(len(s.prompt) for s in a) == sorted(len(s.prompt) for s in c)
    assert sorted(s.max_new for s in a) == sorted(s.max_new for s in c)


def test_traffic_fits_the_engine():
    m = mix()
    specs = traffic.generate(m, 1, 30, 92544)
    assert len(specs) == traffic.count(m, 30)
    assert all(len(s.prompt) + s.max_new <= m["engine"]["max_len"]
               for s in specs)
    dues = [s.due_s for s in specs]
    assert dues == sorted(dues) and dues[0] == 0.0
    assert {s.temperature for s in specs[::2]} == {0.0}


# -- the manifest --------------------------------------------------------------------


def manifest():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_manifest_names_units_and_files():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    metrics = m["end_to_end"] + m["per_layer"]
    for x in m["configs"] + m["workloads"] + metrics:
        assert NAME.match(x["name"]), x["name"]
    for x in metrics:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
    names = [x["name"] for x in metrics]
    assert len(set(names)) == len(names)
    for c in m["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"]
    for w in m["workloads"]:
        assert os.path.exists(os.path.join(HERE, "mixes",
                                           w["traffic"] + ".json"))
    for x in m["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "metrics",
                                           x["name"] + ".py"))


def test_manifest_every_cell_reports_what_its_metrics_move():
    m = manifest()
    cells = {w["name"] for w in m["workloads"]}
    e2e = {x["name"]: set(x.get("workloads", cells)) for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    for x in m["per_layer"]:
        assert set(x["workloads"]) <= e2e[x["moves"]], x["name"]
    for c in cells:
        assert sum(c in v for v in e2e.values()) >= 2
        assert any(c in x["workloads"] for x in m["per_layer"])
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(cells) // 2)


def test_harness_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    wl = manifest()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", wl,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


# -- the plain reference against the system ------------------------------------------


def test_reference_matches_the_system_at_smoke_size():
    import dataclasses

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import base as cfgbase
    from repro.launch import steps
    from repro.models.lm import LM

    from .configs import dense_decoder as ref
    cfg = json.load(open(os.path.join(HERE, "testdata", "smoke-tt.json")))
    arch = cfgbase.get(cfg["registry"])
    tnn = dataclasses.replace(arch.tnn_default, rank=cfg["tnn"]["rank"])
    _, lm = steps.build_model(arch, tnn=tnn)
    lm = dataclasses.replace(
        lm, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        head_dim=16, d_ff=128, vocab=256, qkv_bias=True, rope_theta=1e6,
        norm_eps=1e-5, compute_dtype=jnp.float32)
    model = LM(lm)
    params = ref.init_params(cfg, jax.random.key(3))
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        jnp.shape, jax.eval_shape(model.init, jax.random.key(0)))
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, 256, (2, 32), dtype=np.int32))
    tgt = jnp.asarray(rng.integers(0, 256, (2, 32), dtype=np.int32))
    got = model(params, tok)[0]
    want = jnp.stack([ref.logits(cfg, params, t) for t in tok])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def sys_loss(p):
        return model.loss(p, {"inputs": tok, "targets": tgt})[0]

    l_sys, g_sys = jax.value_and_grad(sys_loss)(params)
    l_ref, g_ref = jax.value_and_grad(
        lambda p: ref.loss(cfg, p, tok, tgt, row_block=16))(params)
    assert float(l_sys) == pytest.approx(float(l_ref), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_sys), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


def test_fp8_control_moves_the_logits():
    import jax
    import jax.numpy as jnp

    from .configs import dense_decoder as ref
    cfg = json.load(open(os.path.join(HERE, "testdata", "smoke-tt.json")))
    params = ref.init_params(cfg, jax.random.key(4))
    tok = jnp.arange(32) % 256
    f32 = ref.logits(cfg, params, tok, "f32")
    fp8 = ref.logits(cfg, params, tok, "fp8")
    assert float(jnp.max(jnp.abs(f32 - fp8))) > 1e-2
