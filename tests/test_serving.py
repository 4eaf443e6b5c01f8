"""Serving engine tests: scheduler invariants, model parity, quantized KV.

Two model tiers:

* ``FakeLM`` — a deterministic token automaton (``next = (7*tok + 3) %
  vocab`` via one-hot logits) with the real engine model protocol
  (``init_cache`` / ``extend`` / ``decode_step``).  Scheduler tests run
  on it in microseconds, and because its output depends only on the
  request's own tokens, any cross-slot contamination in the engine
  shows up as a wrong token immediately.
* the tiny real LM (2 layers, d_model 64) — parity, invariance, and
  quantized-KV bound tests.

The continuous-batching regression test pins the PR's scheduler fix:
the seed engine drained each admission wave to its longest request
before admitting from the queue; the slot-table engine must admit a
queued request into a freed slot while another slot is still decoding.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tensorized import TNNConfig
from repro.models.lm import LM, LMConfig
from repro.precision import QuantPolicy
from repro.serving import kv_cache as kvq
from repro.serving import profiles as profiles_lib
from repro.serving.engine import DECODE, FREE, Request, ServeEngine

VOCAB = 97


def fake_next(tok: int) -> int:
    return (7 * tok + 3) % VOCAB


def fake_sequence(start: int, n: int) -> list[int]:
    out, t = [], start
    for _ in range(n):
        t = fake_next(t)
        out.append(t)
    return out


class _FakeCache(NamedTuple):
    toks: jax.Array          # [B, T] fed-token history
    length: jax.Array        # [] or [B]


def _frozen(buf, new, start, valid):
    """``new`` where ``valid > 0``; elsewhere the rows of ``buf`` that
    ``new`` would overwrite at ``start`` — the models' freeze contract."""
    old = jax.vmap(lambda b, s: jax.lax.dynamic_slice_in_dim(
        b, s, new.shape[1], axis=0))(buf, start)
    return jnp.where((valid > 0)[:, None], new, old)


class FakeLM:
    """Deterministic LM: logits are one-hot at ``(7*tok + 3) % vocab``.
    A slot with ``valid == 0`` keeps its history, as in the real models."""

    vocab = VOCAB

    def init_cache(self, batch: int, max_len: int) -> _FakeCache:
        return _FakeCache(jnp.zeros((batch, max_len), jnp.int32),
                          jnp.zeros((), jnp.int32))

    def _logits(self, toks):
        nxt = (7 * toks + 3) % self.vocab
        return jax.nn.one_hot(nxt, self.vocab, dtype=jnp.float32)

    def extend(self, params, toks, cache, shard=None, valid=None):
        B, C = toks.shape
        length = cache.length
        if jnp.ndim(length) == 0:
            length = jnp.full((B,), length, jnp.int32)
        upd = jax.vmap(lambda buf, new, start:
                       jax.lax.dynamic_update_slice_in_dim(buf, new, start,
                                                           axis=0))
        rows = toks if valid is None else _frozen(cache.toks, toks, length,
                                                  valid)
        newtoks = upd(cache.toks, rows, length)
        adv = C if valid is None else valid
        return self._logits(toks), _FakeCache(newtoks, cache.length + adv)

    def decode_step(self, params, tok, cache, shard=None, valid=None):
        B = tok.shape[0]
        length = cache.length
        if jnp.ndim(length) == 0:
            length = jnp.full((B,), length, jnp.int32)
        upd = jax.vmap(lambda buf, new, start:
                       jax.lax.dynamic_update_slice_in_dim(buf, new, start,
                                                           axis=0))
        rows = tok[:, None]
        if valid is not None:
            rows = _frozen(cache.toks, rows, length, valid)
        newtoks = upd(cache.toks, rows, length)
        adv = 1 if valid is None else valid
        return self._logits(tok), _FakeCache(newtoks, cache.length + adv)


def fake_engine(**kw) -> ServeEngine:
    kw.setdefault("batch_size", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_chunk", 4)
    return ServeEngine(FakeLM(), {}, **kw)


def mk_req(rid, prompt, max_new=4, temp=0.0):
    return Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=max_new, temperature=temp)


# ---------------------------------------------------------------------------
# scheduler invariants (FakeLM)
# ---------------------------------------------------------------------------


def test_all_requests_complete():
    eng = fake_engine(batch_size=2)
    for rid in range(5):
        eng.submit(mk_req(rid, [rid + 1, rid + 2], max_new=3))
    done = eng.run()
    assert sorted(r.rid for r in done) == list(range(5))
    assert all(len(r.out_tokens) == 3 for r in done)


def test_outputs_are_the_deterministic_sequence():
    eng = fake_engine()
    eng.submit(mk_req(0, [5, 9], max_new=4))
    done = eng.run()
    assert done[0].out_tokens == fake_sequence(9, 4)


def test_continuous_batching_regression():
    """A queued request must land in a freed slot while another slot is
    still mid-decode — the seed engine drained the whole wave first."""
    eng = fake_engine(batch_size=2)
    eng.submit(mk_req(0, [1], max_new=16))     # long: holds its slot
    eng.submit(mk_req(1, [2], max_new=2))      # short: frees slot early
    eng.submit(mk_req(2, [3], max_new=2))      # queued behind the wave
    done = eng.run()
    assert sorted(r.rid for r in done) == [0, 1, 2]
    ticks = {(kind, rid): tick for tick, kind, rid in eng.events}
    assert ticks[("admit", 2)] < ticks[("finish", 0)], (
        "request 2 waited for the longest request of the prior wave")


def test_continuous_batching_preserves_outputs():
    """The refilled request's tokens are correct despite the mid-decode
    admission (no state bleed from the freed slot's history)."""
    eng = fake_engine(batch_size=2)
    eng.submit(mk_req(0, [1], max_new=16))
    eng.submit(mk_req(1, [2], max_new=2))
    eng.submit(mk_req(2, [3], max_new=5))
    done = {r.rid: r.out_tokens for r in eng.run()}
    assert done[0] == fake_sequence(1, 16)
    assert done[1] == fake_sequence(2, 2)
    assert done[2] == fake_sequence(3, 5)


def test_max_new_tokens_respected():
    eng = fake_engine()
    for rid, mn in enumerate([1, 3, 7]):
        eng.submit(mk_req(rid, [rid + 1], max_new=mn))
    done = {r.rid: r.out_tokens for r in eng.run()}
    assert [len(done[r]) for r in range(3)] == [1, 3, 7]


def test_eos_early_stop():
    start = 5
    seq = fake_sequence(start, 8)
    eng = fake_engine(eos_id=seq[2])
    eng.submit(mk_req(0, [start], max_new=8))
    done = eng.run()
    assert done[0].out_tokens == seq[:3]       # stopped at EOS, early


def test_eos_on_first_token():
    start = 5
    eng = fake_engine(eos_id=fake_next(start))
    eng.submit(mk_req(0, [start], max_new=8))
    done = eng.run()
    assert done[0].out_tokens == [fake_next(start)]


def test_eos_never_appearing_hits_budget():
    eng = fake_engine(eos_id=VOCAB + 5)        # not producible
    eng.submit(mk_req(0, [1], max_new=6))
    assert len(eng.run()[0].out_tokens) == 6


def test_admission_budget_limits_occupancy():
    per = kvq.model_slot_bytes(FakeLM(), 32)
    eng = fake_engine(batch_size=4, memory_budget=int(2.5 * per))
    assert eng.capacity == 2
    for rid in range(6):
        eng.submit(mk_req(rid, [rid + 1], max_new=4))
    done = eng.run()
    assert sorted(r.rid for r in done) == list(range(6))
    assert eng.max_occupancy <= 2


def test_budget_below_one_slot_raises():
    per = kvq.model_slot_bytes(FakeLM(), 32)
    with pytest.raises(ValueError, match="memory budget"):
        fake_engine(memory_budget=per // 2)


def test_budget_string_parsing():
    eng = fake_engine(memory_budget="1MB")
    assert eng.capacity == eng.batch           # 1MB >> the fake cache


def test_oversized_prompt_rejected():
    eng = fake_engine(max_len=8)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(mk_req(0, list(range(1, 8)), max_new=4))


def test_empty_prompt_rejected():
    eng = fake_engine()
    with pytest.raises(ValueError, match="empty"):
        eng.submit(Request(rid=0, prompt=np.zeros(0, np.int32)))


def test_prefill_token_budget_serializes_prompt_ingestion():
    """With a per-tick prefill token budget of one chunk, two admitted
    prompts ingest in admission order rather than in parallel."""
    eng = fake_engine(batch_size=2, prefill_chunk=4, max_prefill_tokens=4)
    eng.submit(mk_req(0, list(range(1, 9)), max_new=2))   # 8 prompt tokens
    eng.submit(mk_req(1, list(range(11, 19)), max_new=2))
    done = {r.rid: r.out_tokens for r in eng.run()}
    assert done[0] == fake_sequence(8, 2)
    assert done[1] == fake_sequence(18, 2)
    firsts = {rid: t for t, kind, rid in eng.events if kind == "finish"}
    assert firsts[0] < firsts[1]               # oldest prompt finished first
    # the budget halves per-tick prefill throughput, so the run needs more
    # ticks than the same workload without a budget
    free = fake_engine(batch_size=2, prefill_chunk=4)
    free.submit(mk_req(0, list(range(1, 9)), max_new=2))
    free.submit(mk_req(1, list(range(11, 19)), max_new=2))
    free.run()
    assert eng.tick > free.tick


def test_chunked_prefill_output_independent_of_chunking():
    outs = {}
    for chunk in (2, 3, 8):
        eng = fake_engine(batch_size=2, prefill_chunk=chunk, max_len=32)
        eng.submit(mk_req(0, list(range(1, 8)), max_new=5))
        outs[chunk] = eng.run()[0].out_tokens
    assert outs[2] == outs[3] == outs[8] == fake_sequence(7, 5)


def test_events_well_formed():
    eng = fake_engine(batch_size=2)
    for rid in range(5):
        eng.submit(mk_req(rid, [rid + 1], max_new=3))
    eng.run()
    admits = [rid for _, kind, rid in eng.events if kind == "admit"]
    finishes = [rid for _, kind, rid in eng.events if kind == "finish"]
    assert sorted(admits) == list(range(5)) == sorted(finishes)
    at = {rid: t for t, kind, rid in eng.events if kind == "admit"}
    ft = {rid: t for t, kind, rid in eng.events if kind == "finish"}
    assert all(at[r] <= ft[r] for r in range(5))
    assert all(s is None for s in eng.slot_req)
    assert np.all(eng.phase == FREE)


def test_warmup_does_not_change_outputs():
    def run_once(warm):
        eng = fake_engine(seed=7)
        if warm:
            eng.warmup()
        eng.submit(mk_req(0, [3, 4], max_new=5, temp=0.9))
        return eng.run()[0].out_tokens
    assert run_once(True) == run_once(False)


def test_step_returns_newly_completed():
    eng = fake_engine()
    eng.submit(mk_req(0, [1], max_new=1))
    got = []
    while eng.busy:
        got += eng.step()
    assert [r.rid for r in got] == [0]


# ---------------------------------------------------------------------------
# real-model parity and invariance
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = LMConfig(name="serve-test", num_layers=2, d_model=64, num_heads=4,
                   num_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
                   remat=False)
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    return model, params, cfg


def _prompts(rng, n, lo=3, hi=10):
    return [rng.integers(0, 256, size=int(rng.integers(lo, hi)),
                         dtype=np.int32) for _ in range(n)]


def test_engine_matches_hand_rolled(tiny_lm):
    model, params, _ = tiny_lm
    prompt = np.arange(1, 7, dtype=np.int32)
    eng = ServeEngine(model, params, batch_size=1, max_len=24,
                      prefill_chunk=8)
    eng.submit(mk_req(0, prompt, max_new=5))
    got = eng.run()[0].out_tokens

    cache = model.init_cache(1, 24 + 8)
    cache = cache._replace(length=jnp.zeros(1, jnp.int32))
    logits, cache = model.extend(params, jnp.asarray(prompt)[None], cache)
    want = [int(jnp.argmax(logits[0, -1].astype(jnp.float32)))]
    for _ in range(4):
        logits, cache = model.decode_step(
            params, jnp.asarray([want[-1]], jnp.int32), cache)
        want.append(int(jnp.argmax(logits[0].astype(jnp.float32))))
    assert got == want


def test_solo_vs_batched_invariance(tiny_lm):
    """Greedy outputs are independent of batch composition."""
    model, params, _ = tiny_lm
    rng = np.random.default_rng(3)
    prompts = _prompts(rng, 4)

    batched = ServeEngine(model, params, batch_size=2, max_len=24,
                          prefill_chunk=8)
    for rid, p in enumerate(prompts):
        batched.submit(mk_req(rid, p, max_new=4))
    got = {r.rid: r.out_tokens for r in batched.run()}

    for rid, p in enumerate(prompts):
        solo = ServeEngine(model, params, batch_size=1, max_len=24,
                           prefill_chunk=8)
        solo.submit(mk_req(rid, p, max_new=4))
        assert solo.run()[0].out_tokens == got[rid], f"request {rid}"


def test_prompt_length_invariance(tiny_lm):
    """A short prompt sharing a batch with a much longer one gets the
    same tokens as alone — right-aligned slots never attend padding."""
    model, params, _ = tiny_lm
    short = np.array([9, 4, 2], np.int32)
    long = np.arange(1, 17, dtype=np.int32)

    mixed = ServeEngine(model, params, batch_size=2, max_len=32,
                        prefill_chunk=8)
    mixed.submit(mk_req(0, short, max_new=4))
    mixed.submit(mk_req(1, long, max_new=4))
    got = {r.rid: r.out_tokens for r in mixed.run()}

    solo = ServeEngine(model, params, batch_size=1, max_len=32,
                       prefill_chunk=8)
    solo.submit(mk_req(0, short, max_new=4))
    assert solo.run()[0].out_tokens == got[0]


def test_extend_matches_prefill_logits(tiny_lm):
    model, params, _ = tiny_lm
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, 256)
    want, _ = model.prefill(params, toks, 24)
    cache = model.init_cache(2, 24)
    _, cache = model.extend(params, toks[:, :4], cache)
    got, _ = model.extend(params, toks[:, 4:], cache)
    np.testing.assert_allclose(
        np.asarray(got[:, -1], np.float32), np.asarray(want, np.float32),
        atol=0.08, rtol=0)


def test_per_slot_decode_matches_scalar(tiny_lm):
    model, params, _ = tiny_lm
    toks = jax.random.randint(jax.random.key(2), (1, 6), 0, 256)
    _, scalar_cache = model.prefill(params, toks, 24)
    vec = model.init_cache(1, 24)
    vec = vec._replace(length=jnp.zeros(1, jnp.int32))
    _, vec = model.extend(params, toks, vec)
    nxt = jnp.array([7], jnp.int32)
    want, _ = model.decode_step(params, nxt, scalar_cache)
    got, _ = model.decode_step(params, nxt, vec)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.08, rtol=0)


def test_native_path_freezes_inactive_slots_in_the_write(tiny_lm):
    """On the native bf16 path the model's KV write freezes inactive
    slots: their K/V come back bit-identical in every layer, and the live
    slots' K/V and logits equal the whole-cache select's (the same call
    with ``valid=None``, then ``jnp.where`` over the cache).  The tick
    programs donate the cache, so each call gets its own copy."""
    model, params, _ = tiny_lm
    B, C = 3, 8
    eng = ServeEngine(model, params, batch_size=B, max_len=24,
                      prefill_chunk=C)
    kk, kv = jax.random.split(jax.random.key(7))
    shape = eng.cache.layers.k.shape
    layers = eng.cache.layers._replace(
        k=jax.random.normal(kk, shape).astype(jnp.bfloat16),
        v=jax.random.normal(kv, shape).astype(jnp.bfloat16))
    cache = eng.cache._replace(layers=layers)
    assert not bool(jnp.any(layers.k == 0))
    lengths = jnp.array([5, 9, 3], jnp.int32)

    def whole_select(active, new):
        m = active[None, :, None, None, None]
        return (jnp.where(m, new.layers.k, layers.k),
                jnp.where(m, new.layers.v, layers.v))

    def check(active, logits, new, ref_logits, ref):
        for got, old, want in zip((new.layers.k, new.layers.v),
                                  (layers.k, layers.v),
                                  whole_select(active, ref)):
            np.testing.assert_array_equal(np.asarray(got[:, ~active]),
                                          np.asarray(old[:, ~active]))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(logits[active]),
                                      np.asarray(ref_logits[active]))

    toks = jax.random.randint(jax.random.key(8), (B, C), 0, 256)
    valid = jnp.array([C, 0, C], jnp.int32)
    active = np.asarray(valid) > 0
    logits, new = eng._extend_fn(params, toks, jax.tree.map(jnp.copy, cache),
                                 lengths, valid, jnp.asarray(active))
    ref_logits, ref = model.extend(params, toks,
                                   cache._replace(length=lengths))
    check(active, logits, new, ref_logits, ref)
    np.testing.assert_array_equal(np.asarray(new.length),
                                  np.asarray(lengths + valid))

    tok = jnp.array([11, 22, 33], jnp.int32)
    active = np.array([True, True, False])
    logits, new = eng._decode_fn(params, tok, jax.tree.map(jnp.copy, cache),
                                 lengths, jnp.asarray(active))
    ref_logits, ref = model.decode_step(params, tok,
                                        cache._replace(length=lengths))
    check(active, logits, new, ref_logits, ref)
    np.testing.assert_array_equal(np.asarray(new.length),
                                  np.asarray(lengths + active))


def _slot(cache, b):
    """Slot ``b`` of a decode cache: stacked per-layer leaves keep their
    layer axis and take batch row ``b`` on axis 1; the per-slot length
    takes row ``b``; per-layer length bookkeeping is dropped."""
    def rows(a):
        return a[:, b] if a.ndim >= 3 else None
    return (jax.tree.map(rows, (cache.layers, cache.shared)),
            cache.length[b])


@pytest.mark.parametrize("arch", ["rwkv6_7b", "zamba2_7b",
                                  "granite_4_0_h_micro"])
def test_state_stack_extend_is_successive_decode_steps(arch):
    """On a stack with state layers (RWKV-6, Zamba2's Mamba-2 groups with
    the shared attention block, Granite's Mamba-2 and attention runs),
    ``LM.extend`` over a chunk with per-slot ``valid`` equals, for each
    slot ``b``, ``valid[b]`` successive ``decode_step`` calls: the same
    logits rows and the same state; a slot with ``valid == 0`` comes back
    bit-identical.  And ``decode_step(valid=)`` leaves a frozen slot's
    whole state bit-identical while the live slots step as with
    ``valid=None``."""
    from repro.configs import base as cfgbase
    from repro.launch import steps
    model, cfg = steps.build_model(cfgbase.get(arch), smoke=True)
    params = model.init(jax.random.key(0))
    B, C = 3, 4
    k1, k2 = jax.random.split(jax.random.key(9))
    ext, dec = jax.jit(model.extend), jax.jit(model.decode_step)
    cache = model.init_cache(B, 16)
    cache = cache._replace(length=jnp.zeros(B, jnp.int32))
    # a live state at a different depth in each slot
    _, cache = ext(params, jax.random.randint(k1, (B, 3), 0, cfg.vocab),
                   cache, valid=jnp.array([3, 2, 1], jnp.int32))
    toks = jax.random.randint(k2, (B, C), 0, cfg.vocab)
    valid = np.array([C, 0, 2], np.int32)
    logits, got = ext(params, toks, cache, valid=jnp.asarray(valid))
    assert logits.shape == (B, C, cfg.vocab)

    ref, rows = cache, []
    for i in range(C):
        lg, ref = dec(params, toks[:, i], ref)
        rows.append(lg)
        for b in np.nonzero(valid == i + 1)[0]:
            jax.tree.map(np.testing.assert_array_equal, _slot(got, b),
                         _slot(ref, b))
            np.testing.assert_array_equal(
                np.asarray(logits[b, :i + 1]),
                np.asarray(jnp.stack(rows, 1)[b]))
    jax.tree.map(np.testing.assert_array_equal, _slot(got, 1),
                 _slot(cache, 1))

    live = jnp.array([1, 0, 1], jnp.int32)
    lg, frozen = dec(params, toks[:, 0], cache, valid=live)
    want_lg, want = dec(params, toks[:, 0], cache)
    jax.tree.map(np.testing.assert_array_equal, _slot(frozen, 1),
                 _slot(cache, 1))
    for b in (0, 2):
        jax.tree.map(np.testing.assert_array_equal, _slot(frozen, b),
                     _slot(want, b))
        np.testing.assert_array_equal(np.asarray(lg[b]),
                                      np.asarray(want_lg[b]))


def test_donated_engine_serves_soundly(tiny_lm):
    """The tick programs donate the state they are given.  After
    ``warmup()`` an engine admits, prefills, decodes and re-admits into a
    freed slot with the greedy outputs of a second engine run on the same
    requests; ``serve.kv_inplace`` counts each native extend and decode
    call, and ``serve.kv_whole`` only the admitting ticks' slot-zeroing."""
    from repro import telemetry as tm
    model, params, _ = tiny_lm
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, 3, lo=5, hi=14)
    budgets = (3, 7, 4)

    def serve(warm):
        eng = ServeEngine(model, params, batch_size=2, max_len=32,
                          prefill_chunk=4)
        if warm:
            eng.warmup()
        calls = []
        ext, dec = eng._extend_fn, eng._decode_fn
        eng._extend_fn = lambda *a: calls.append("extend") or ext(*a)
        eng._decode_fn = lambda *a: calls.append("decode") or dec(*a)
        for rid, (p, n) in enumerate(zip(prompts, budgets)):
            eng.submit(mk_req(rid, p, max_new=n))
        tm.reset()
        tm.configure()
        try:
            done = {r.rid: r.out_tokens for r in eng.run()}
            counters = tm.counters()
        finally:
            tm.reset()
        return eng, done, calls, counters

    eng, got, calls, counters = serve(warm=True)
    _, want, _, _ = serve(warm=False)
    assert got == want
    assert [len(got[r]) for r in range(3)] == list(budgets)
    admits = [t for t, kind, _ in eng.events if kind == "admit"]
    assert len(set(admits)) >= 2        # the third request re-admits
    assert {"extend", "decode"} <= set(calls)
    assert counters["serve.kv_inplace"] == len(calls)
    assert counters["serve.kv_whole"] == len(set(admits))


def test_engine_vs_tensorized_model(tiny_lm):
    """The engine drives a TNN model identically to the dense protocol."""
    _, _, base = tiny_lm
    import dataclasses
    cfg = dataclasses.replace(
        base, name="serve-tnn",
        tnn=TNNConfig(enabled=True, method="tt", rank=8, num_factors=2,
                      targets=("mlp",), backend="einsum"))
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, batch_size=2, max_len=24,
                      prefill_chunk=8)
    eng.submit(mk_req(0, np.array([3, 1, 4], np.int32), max_new=3))
    done = eng.run()
    assert len(done[0].out_tokens) == 3


# ---------------------------------------------------------------------------
# quantized KV cache
# ---------------------------------------------------------------------------


def test_slot_bytes_fp8_halves_payload(tiny_lm):
    _, _, cfg = tiny_lm
    bf16 = kvq.slot_bytes(cfg, 64)
    fp8 = kvq.slot_bytes(cfg, 64, QuantPolicy.parse("fp8"))
    int8 = kvq.slot_bytes(cfg, 64, QuantPolicy.parse("int8"))
    assert bf16["payload"] / fp8["payload"] >= 2.0
    assert bf16["payload"] / int8["payload"] >= 2.0
    assert fp8["meta"] == 2 * cfg.num_layers * 4
    assert bf16["meta"] == 0
    assert fp8["total"] == fp8["payload"] + fp8["meta"]


def test_quantized_kv_roundtrip_bounds():
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((2, 3, 8, 2, 4)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 3, 8, 2, 4)), jnp.float32)
    for name, rel in (("fp8_e4m3", 0.07), ("int8", 0.01)):
        pol = QuantPolicy.parse(name)
        q = kvq.quantize_kv(k, v, pol)
        dk, dv = kvq.dequantize_kv(q, pol, jnp.float32)
        amax = float(jnp.max(jnp.abs(k)))
        assert float(jnp.max(jnp.abs(dk - k))) <= rel * amax
        assert float(jnp.max(jnp.abs(dv - v))) <= rel * amax


def test_quantized_requant_is_bit_stable():
    """dequantize -> requantize with unchanged amax is the identity —
    the property that lets the engine requantize every tick."""
    rng = np.random.default_rng(1)
    k = jnp.asarray(rng.standard_normal((2, 2, 4, 2, 4)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 4, 2, 4)), jnp.float32)
    pol = QuantPolicy.parse("fp8")
    q1 = kvq.quantize_kv(k, v, pol)
    dk, dv = kvq.dequantize_kv(q1, pol, jnp.float32)
    q2 = kvq.quantize_kv(dk, dv, pol, prev=q1)
    np.testing.assert_array_equal(np.asarray(q1.qk, np.uint8),
                                  np.asarray(q2.qk, np.uint8))
    np.testing.assert_array_equal(np.asarray(q1.qv, np.uint8),
                                  np.asarray(q2.qv, np.uint8))


def test_quantized_amax_monotone():
    pol = QuantPolicy.parse("fp8")
    rng = np.random.default_rng(2)
    q = None
    prev = np.zeros(2)
    for step in range(4):
        k = jnp.asarray(rng.standard_normal((2, 2, 4, 2, 4)) * (step + 1),
                        jnp.float32)
        q = kvq.quantize_kv(k, k, pol, prev=q)
        cur = np.asarray(q.k_amax)
        assert np.all(cur >= prev)
        prev = cur


def test_quantized_engine_first_token_parity(tiny_lm):
    """Single-chunk prompts: the first sampled token sees only the
    current tick's full-precision KV, so fp8 must match bf16 exactly."""
    model, params, _ = tiny_lm
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, 3, lo=3, hi=8)
    outs = {}
    for kv in (None, "fp8"):
        eng = ServeEngine(model, params, batch_size=2, max_len=24,
                          prefill_chunk=8, kv_policy=kv)
        for rid, p in enumerate(prompts):
            eng.submit(mk_req(rid, p, max_new=1))
        outs[kv] = {r.rid: r.out_tokens for r in eng.run()}
    assert outs[None] == outs["fp8"]


def test_quantized_engine_kv_error_bounded(tiny_lm):
    """After identical prompts, the fp8 engine's dequantized KV matches
    the bf16 engine's cache within the fp8 relative-error bound."""
    model, params, _ = tiny_lm
    prompt = np.arange(1, 9, dtype=np.int32)

    ref = ServeEngine(model, params, batch_size=1, max_len=24,
                      prefill_chunk=8)
    ref.submit(mk_req(0, prompt, max_new=1))
    ref.run()
    kb = np.asarray(ref.cache.layers.k[:, 0, :8], np.float32)

    quant = ServeEngine(model, params, batch_size=1, max_len=24,
                        prefill_chunk=8, kv_policy="fp8")
    quant.submit(mk_req(0, prompt, max_new=1))
    quant.run()
    dk, _ = kvq.dequantize_kv(quant.qkv, quant.kv_policy, jnp.float32)
    kq = np.asarray(dk[:, 0, :8], np.float32)

    amax = np.abs(kb).max()
    assert np.abs(kq - kb).max() <= 0.08 * amax


def test_quantized_engine_full_run_completes(tiny_lm):
    model, params, _ = tiny_lm
    for kv in ("fp8", "int8", "fp8_e5m2"):
        eng = ServeEngine(model, params, batch_size=2, max_len=24,
                          prefill_chunk=8, kv_policy=kv)
        for rid in range(4):
            eng.submit(mk_req(rid, np.array([rid + 1, 2, 3], np.int32),
                              max_new=4))
        done = eng.run()
        assert sorted(r.rid for r in done) == list(range(4))
        assert all(len(r.out_tokens) == 4 for r in done)


def test_quantized_kv_requires_attention():
    with pytest.raises(ValueError, match="bf16|attention"):
        # FakeLM has no cfg; pretend-SSM via a cfg stub
        class Cfg:
            block = "mamba2"
            hybrid = None

        class SSMish(FakeLM):
            cfg = Cfg()

        ServeEngine(SSMish(), {}, batch_size=1, max_len=8,
                    kv_policy="fp8")


# ---------------------------------------------------------------------------
# phase-specialized profiles
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tnn_cfg():
    return LMConfig(name="serve-prof", num_layers=2, d_model=64,
                    num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                    vocab=256, remat=False,
                    tnn=TNNConfig(enabled=True, method="tt", rank=8,
                                  num_factors=2, targets=("mlp",),
                                  backend="einsum"))


def test_phase_signatures_distinct(tnn_cfg):
    """Prefill and decode resolve to different CSSE cache entries for
    every projection — the tentpole's phase-tagged key guarantee."""
    ps = profiles_lib.build_profiles(tnn_cfg, batch_size=4,
                                     prefill_chunk=16)
    assert set(ps) == {"prefill", "decode"}
    pre = dict(ps["prefill"].signatures)
    dec = dict(ps["decode"].signatures)
    assert pre.keys() == dec.keys() and len(pre) > 0
    for name in pre:
        assert pre[name] != dec[name], name


def test_phase_signature_stable_across_builds(tnn_cfg):
    a = profiles_lib.build_profile(tnn_cfg, "decode", 4)
    b = profiles_lib.build_profile(tnn_cfg, "decode", 4)
    assert a.signatures == b.signatures


def test_phase_enters_search_options(tnn_cfg):
    tnn = profiles_lib.phase_tnn(tnn_cfg.tnn, "decode")
    assert tnn.phase == "decode"
    assert tnn.search_options().phase == "decode"
    assert tnn_cfg.tnn.search_options().phase == ""


def test_phase_enters_autotune_signature():
    from repro.core import autotune
    tuner = autotune.Tuner(cache_dir=None)
    a = autotune.StepShape("gemm", (64, 64, 64), False, "bfloat16",
                           phase="prefill")
    b = autotune.StepShape("gemm", (64, 64, 64), False, "bfloat16",
                           phase="decode")
    assert tuner.signature(a) != tuner.signature(b)


def test_profiles_empty_without_tnn(tiny_lm):
    _, _, cfg = tiny_lm
    assert profiles_lib.build_profiles(cfg, batch_size=2,
                                       prefill_chunk=8) == {}


def test_profile_token_shapes(tnn_cfg):
    ps = profiles_lib.build_profiles(tnn_cfg, batch_size=4,
                                     prefill_chunk=16)
    assert ps["prefill"].tokens == 64
    assert ps["decode"].tokens == 4
    assert ps["prefill"].opts.phase == "prefill"
    assert ps["decode"].opts.phase == "decode"


# ---------------------------------------------------------------------------
# engine internals
# ---------------------------------------------------------------------------


def test_occupancy_and_capacity_properties():
    eng = fake_engine(batch_size=3)
    assert eng.occupancy == 0 and not eng.busy
    eng.submit(mk_req(0, [1], max_new=5))
    assert eng.busy
    eng.step()        # prefill + first decode land in the same tick
    assert eng.occupancy == 1
    assert np.sum(eng.phase == DECODE) == 1
    eng.run()
    assert eng.occupancy == 0 and not eng.busy


def test_temperature_sampling_stays_in_vocab(tiny_lm):
    model, params, _ = tiny_lm
    eng = ServeEngine(model, params, batch_size=2, max_len=24,
                      prefill_chunk=8, seed=11)
    for rid in range(3):
        eng.submit(mk_req(rid, np.array([rid + 1, 5], np.int32),
                          max_new=4, temp=1.0))
    done = eng.run()
    assert all(0 <= t < 256 for r in done for t in r.out_tokens)
