"""Property-based tests (hypothesis) on the system's invariants."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import contraction, csse, factorizations as F, perf_model
from repro.core.policy import ExecutionPolicy
from repro.core.tnetwork import plan_from_tree
from repro.memory.stash import StashPolicy
from repro.optim import compression
from repro.precision import (
    DTYPES,
    QuantPolicy,
    compute_scale,
    dequantize,
    quantize,
    scale_from_history,
    update_history,
)

_dims = st.lists(st.integers(2, 5), min_size=2, max_size=3)
_methods = st.sampled_from(["tt", "ttm", "tr", "ht", "bt"])


def _make(method, out_dims, in_dims, rank):
    if method in ("ttm", "ht", "bt"):
        n = min(len(out_dims), len(in_dims))
        out_dims, in_dims = out_dims[:n], in_dims[:n]
    return F.make(method, tuple(out_dims), tuple(in_dims), rank)


@settings(max_examples=25, deadline=None)
@given(_methods, _dims, _dims, st.integers(2, 4), st.integers(1, 6))
def test_any_search_tree_is_correct(method, out_dims, in_dims, rank, batch):
    """Whatever tree CSSE returns, executing it equals the direct einsum."""
    fact = _make(method, out_dims, in_dims, rank)
    net = fact.forward_network(batch_axes=(("b", batch),))
    res = csse.search(net, csse.SearchOptions(objective="flops", num_candidates=2))
    arrays = [
        jnp.asarray(
            np.random.default_rng(i).standard_normal(net.node_shape(i)), jnp.float32
        )
        for i in range(net.num_nodes)
    ]
    got = contraction.execute(res.plan, arrays)
    import string

    sym = {a: string.ascii_letters[i] for i, a in enumerate(sorted(net.sizes))}
    spec = ",".join("".join(sym[a] for a in node) for node in net.nodes)
    spec += "->" + "".join(sym[a] for a in net.output)
    want = jnp.einsum(spec, *arrays)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-3)


@settings(max_examples=25, deadline=None)
@given(_methods, _dims, _dims, st.integers(2, 4))
def test_compression_accounting(method, out_dims, in_dims, rank):
    """num_params equals the sum of core sizes; dense_params = M*N."""
    fact = _make(method, out_dims, in_dims, rank)
    assert fact.num_params == sum(
        math.prod(fact.core_shape(i)) for i in range(fact.num_cores)
    )
    assert fact.dense_params == fact.M * fact.N
    assert fact.M == math.prod(fact.out_dims)
    assert fact.N == math.prod(fact.in_dims)


@settings(max_examples=20, deadline=None)
@given(_methods, _dims, _dims, st.integers(2, 3), st.integers(1, 4))
def test_search_optimum_no_worse_than_fixed(method, out_dims, in_dims, rank, batch):
    """Stage-1 FLOPs optimum <= the fixed sequence's FLOPs, always."""
    fact = _make(method, out_dims, in_dims, rank)
    net = fact.forward_network(batch_axes=(("b", batch),))
    res = csse.search(net, csse.SearchOptions(objective="flops"))
    fixed = plan_from_tree(net, fact.fixed_tree(net))
    assert res.plan.total_flops <= fixed.total_flops


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 2048), st.integers(1, 2048), st.integers(1, 2048))
def test_mxu_utilisation_bounds(m, n, k):
    u = perf_model.TPU_V5E.mxu_utilisation(m, n, k)
    assert 0.0 < u <= 1.0
    # aligned dims achieve exactly 1
    assert (
        perf_model.TPU_V5E.mxu_utilisation(
            ((m + 127) // 128) * 128, ((n + 127) // 128) * 128, ((k + 7) // 8) * 8
        )
        == 1.0
    )


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 64), st.integers(2, 16))
def test_int8_quantisation_error_bound(rows, cols):
    x = jnp.asarray(
        np.random.default_rng(rows * cols).standard_normal((rows, cols)), jnp.float32
    )
    q, scale = compression.quantize_int8(x)
    deq = compression.dequantize_int8(q, scale)
    # symmetric per-tensor int8: error bounded by half a quantisation step
    assert float(jnp.max(jnp.abs(deq - x))) <= float(scale) * 0.5 + 1e-7


_quant_dtypes = st.sampled_from(["fp8_e4m3", "fp8_e5m2", "int8"])


@settings(max_examples=30, deadline=None)
@given(_quant_dtypes, st.floats(0.0, 1e6, allow_nan=False), st.floats(1.0, 4.0))
def test_compute_scale_positive_and_monotone(dtype, amax, margin):
    """Scales are strictly positive (eps floor) and monotone in amax."""
    qmax = DTYPES[dtype][2]
    s = float(compute_scale(amax, qmax, margin))
    assert s > 0 and math.isfinite(s)
    assert float(compute_scale(amax * 2 + 1e-6, qmax, margin)) > s
    if amax > 1e-9:
        # definition: amax maps to qmax/margin
        assert s == pytest.approx(amax * margin / qmax, rel=1e-6)


@settings(max_examples=25, deadline=None)
@given(_quant_dtypes, st.integers(1, 40), st.integers(1, 16), st.floats(0.01, 100.0))
def test_quantize_respects_range(dtype, rows, cols, spread):
    """Quantized values never exceed the dtype's representable range, and
    the round-trip error is bounded by one quantization step."""
    pol = QuantPolicy.parse(dtype)
    x = jnp.asarray(
        np.random.default_rng(rows * cols).standard_normal((rows, cols)) * spread,
        jnp.float32,
    )
    t = quantize(x, pol)
    q32 = np.asarray(t.q, np.float32)
    assert np.all(np.abs(q32) <= pol.qmax)
    step = float(t.scale) * (1.0 if dtype == "int8" else pol.qmax * 2.0**-3)
    assert float(jnp.max(jnp.abs(dequantize(t) - x))) <= step + 1e-6


@settings(max_examples=25, deadline=None)
@given(
    st.lists(
        st.floats(0.0, 1e4, width=32, allow_subnormal=False), min_size=1, max_size=8
    ),
    st.floats(1e-6, 1e4),
)
def test_scale_from_history_uses_window_max(amaxes, current):
    """The delayed scale always reflects the window max — and bootstraps
    from the current amax only while the history is all-zero.  The
    history is f32, so the amaxes are f32 values (a subnormal one would
    be flushed to zero on the way in)."""
    hist = jnp.zeros((len(amaxes),))
    for a in amaxes:
        hist = update_history(hist, a)
    s = float(scale_from_history(hist, current, qmax=127.0))
    hmax = max(amaxes)
    expect = hmax if hmax > 0 else current
    assert s == pytest.approx(float(compute_scale(expect, 127.0)), rel=1e-6)


@settings(max_examples=12, deadline=None)
@given(st.integers(2, 6), st.integers(2, 6), st.integers(1, 5))
def test_factorize_dim_products(a, b, n):
    x = a * b * 7
    factors = F.factorize_dim(x, n)
    assert len(factors) == n and math.prod(factors) == x


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(1, 3))
def test_plan_peak_memory_nonnegative_monotone(rank, batch):
    fact = F.tt((4, 4), (4, 4), rank)
    net = fact.forward_network(batch_axes=(("b", batch),))
    plan = csse.search(net, csse.SearchOptions(objective="flops")).plan
    assert plan.peak_intermediate_elems >= 0
    assert plan.total_read_elems > 0 and plan.total_write_elems > 0


# ---------------------------------------------------------------------------
# ExecutionPolicy round-trips (the unified planning object, PR 7)
# ---------------------------------------------------------------------------

import json  # noqa: E402

_tiles = st.sampled_from((32, 64, 128, 256, 512))
_quants = st.sampled_from(("bf16", "fp8_e4m3", "fp8_e5m2", "int8")).map(
    QuantPolicy.parse
)
_stashes = st.sampled_from(
    ("store", "recompute", "quantized:fp8_e4m3", "quantized:int8")
).map(StashPolicy.parse)

_policies = st.builds(
    ExecutionPolicy,
    objective=st.sampled_from(("latency", "energy", "edp", "flops", "measured")),
    num_candidates=st.integers(1, 16),
    engine=st.sampled_from(("auto", "dfs", "dp")),
    dfs_max_nodes=st.integers(1, 9),
    allow_outer=st.booleans(),
    anchor_input=st.booleans(),
    fused_chain=st.booleans(),
    tile_sweep=st.lists(_tiles, min_size=1, max_size=3, unique=True).map(tuple),
    sweep_strategy=st.sampled_from(("full", "halving")),
    measure_dtype=st.sampled_from(("float32", "bfloat16")),
    precision=_quants,
    stash=_stashes,
    memory_budget=st.one_of(st.none(), st.integers(1, 1 << 40)),
    phase=st.sampled_from(("", "prefill", "decode")),
)


@settings(max_examples=50, deadline=None)
@given(_policies)
def test_execution_policy_json_round_trip(xp):
    """serialize -> (wire) -> deserialize is the identity, and the cache
    signature survives the trip (a reloaded policy may never re-plan)."""
    again = ExecutionPolicy.from_json(json.loads(json.dumps(xp.to_json())))
    assert again == xp
    assert again.signature() == xp.signature()
    assert again.signature_payload() == xp.signature_payload()


@settings(max_examples=50, deadline=None)
@given(_policies, _policies)
def test_execution_policy_signature_separates_policies(a, b):
    """Equal policies hash equal; distinct signature payloads mean
    distinct signatures (no cache collisions across policies)."""
    if a == b:
        assert a.signature() == b.signature()
    elif a.signature_payload() != b.signature_payload():
        assert a.signature() != b.signature()


@settings(max_examples=50, deadline=None)
@given(_policies)
def test_search_options_shim_round_trip(xp):
    """The legacy SearchOptions view lifts back to the same policy (the
    axes SearchOptions never carried are restored as overrides)."""
    opts = xp.search_options()
    back = opts.to_policy(
        tile_sweep=xp.tile_sweep, sweep_strategy=xp.sweep_strategy, stash=xp.stash
    )
    assert back == xp
    # and the csse search layer hashes both spellings identically
    assert csse.SearchOptions.from_policy(xp) == opts


@settings(max_examples=50, deadline=None)
@given(_policies)
def test_execution_policy_old_kwarg_shim_equivalence(xp):
    """from_kwargs with the pre-unification spellings (policy= for
    precision, remat= tag for stash) builds the identical policy."""
    built = ExecutionPolicy.from_kwargs(
        objective=xp.objective,
        num_candidates=xp.num_candidates,
        engine=xp.engine,
        dfs_max_nodes=xp.dfs_max_nodes,
        allow_outer=xp.allow_outer,
        anchor_input=xp.anchor_input,
        fused_chain=xp.fused_chain,
        tile_sweep=xp.tile_sweep,
        sweep_strategy=xp.sweep_strategy,
        measure_dtype=xp.measure_dtype,
        mesh=xp.mesh,
        policy=xp.quant_policy,
        remat=xp.stash.tag(),
        memory_budget=xp.memory_budget,
        phase=xp.phase,
    )
    assert built == xp
    assert built.signature() == xp.signature()


# ---------------------------------------------------------------------------
# serving scheduler invariants (FakeLM from tests/test_serving.py — a
# deterministic token automaton, so the properties run in milliseconds)
# ---------------------------------------------------------------------------

from repro.serving import kv_cache as _kvq  # noqa: E402
from repro.serving.engine import Request, ServeEngine  # noqa: E402
from test_serving import VOCAB, FakeLM, fake_sequence  # noqa: E402

_prompt = st.lists(st.integers(0, VOCAB - 1), min_size=1, max_size=6)
_requests = st.lists(
    st.tuples(_prompt, st.integers(1, 5)), min_size=1, max_size=6)


def _serve(requests, batch, chunk, max_prefill=None, budget=None,
           eos=None):
    eng = ServeEngine(FakeLM(), {}, batch_size=batch, max_len=16,
                      prefill_chunk=chunk, max_prefill_tokens=max_prefill,
                      memory_budget=budget, eos_id=eos)
    for rid, (prompt, max_new) in enumerate(requests):
        eng.submit(Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                           max_new_tokens=max_new))
    return eng, eng.run(max_ticks=10_000)


@settings(max_examples=15, deadline=None)
@given(_requests, st.integers(1, 3), st.integers(1, 4))
def test_scheduler_no_request_lost_or_duplicated(requests, batch, chunk):
    """Every submitted request completes exactly once, with at least one
    and at most max_new_tokens output tokens."""
    eng, done = _serve(requests, batch, chunk)
    assert sorted(r.rid for r in done) == list(range(len(requests)))
    for r in done:
        assert 1 <= len(r.out_tokens) <= r.max_new_tokens
    admits = [rid for _, kind, rid in eng.events if kind == "admit"]
    assert sorted(admits) == list(range(len(requests)))


@settings(max_examples=15, deadline=None)
@given(_requests, st.integers(1, 3), st.integers(1, 4))
def test_scheduler_outputs_deterministic_per_request(requests, batch,
                                                     chunk):
    """Outputs depend only on the request's own prompt — any batch mix,
    chunking, or admission order yields the automaton's sequence."""
    _, done = _serve(requests, batch, chunk)
    for r in done:
        want = fake_sequence(requests[r.rid][0][-1], r.max_new_tokens)
        assert r.out_tokens == want


@settings(max_examples=15, deadline=None)
@given(_requests, st.integers(1, 4), st.integers(1, 3), st.integers(1, 4))
def test_scheduler_occupancy_bounded_by_budget(requests, batch, slots,
                                               chunk):
    """Occupancy never exceeds the memory-budget capacity."""
    per = _kvq.model_slot_bytes(FakeLM(), 16)
    eng, done = _serve(requests, batch, chunk, budget=per * slots)
    assert eng.capacity == min(batch, slots)
    assert eng.max_occupancy <= eng.capacity
    assert sorted(r.rid for r in done) == list(range(len(requests)))


@settings(max_examples=10, deadline=None)
@given(_requests, st.integers(1, 3), st.integers(1, 4), st.integers(1, 6))
def test_scheduler_prefill_budget_preserves_outputs(requests, batch, chunk,
                                                    max_prefill):
    """The per-tick prefill token budget changes scheduling, never
    tokens."""
    _, done = _serve(requests, batch, chunk, max_prefill=max_prefill)
    for r in done:
        want = fake_sequence(requests[r.rid][0][-1], r.max_new_tokens)
        assert r.out_tokens == want


@settings(max_examples=10, deadline=None)
@given(_requests, st.integers(1, 3), st.integers(0, VOCAB - 1))
def test_scheduler_eos_truncates_never_extends(requests, batch, eos):
    """With an EOS id, outputs are the untruncated sequence cut at (and
    including) the first EOS, still within max_new_tokens."""
    _, done = _serve(requests, batch, 2, eos=eos)
    for r in done:
        full = fake_sequence(requests[r.rid][0][-1], r.max_new_tokens)
        want = full[:full.index(eos) + 1] if eos in full else full
        assert r.out_tokens == want
