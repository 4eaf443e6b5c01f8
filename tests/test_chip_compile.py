"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed alongside jax, compiles
each kernel at real widths for one chip of a ``v5e:2x2`` topology that is
described, not attached.  It refuses what interpret mode accepts: block
shapes off the (8, 128) tiling, reshapes the layout pass cannot lower, and
more VMEM than the kernel may use.  Every kernel is built with
``interpret=False`` passed explicitly, since interpret mode is the default
off the chip, and must reach the compiled program as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import plan_compiler, tensorized
from repro.core.tensorized import TNNConfig
from repro.kernels import fused_contraction as fc
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.quantized import dequantize_pallas, quantize_pallas
from repro.kernels.ssm_scan import linear_scan_pallas
from repro.precision import QuantPolicy

BF16, F32 = jnp.bfloat16, jnp.float32
TOKENS = 8 * 2048  # one TinyLlama training batch
D_MODEL, D_FF = 2048, 5632  # TinyLlama widths


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    cache entry written for a chip that is not attached cannot be read
    back, and the next compile would warn."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_matmul_pallas_tinyllama_widths(one_chip, transpose_rhs):
    w = (D_FF, D_MODEL) if transpose_rhs else (D_MODEL, D_FF)

    def fn(x, w):
        return fc.matmul_pallas(x, w, transpose_rhs=transpose_rhs, interpret=False)

    _compile(fn, one_chip, ((TOKENS, D_MODEL), BF16), (w, BF16))


@pytest.mark.parametrize("tag", ["fp8_e4m3", "int8"])
def test_scaled_matmul_pallas(one_chip, tag):
    dt = QuantPolicy.parse(tag).operand_dtype
    m = 4096

    def fn(x, w, sl, sr):
        return fc.matmul_pallas(x, w, scales=(sl, sr), interpret=False)

    _compile(
        fn,
        one_chip,
        ((m, D_MODEL), dt),
        ((D_MODEL, D_FF), dt),
        ((m, 1), F32),
        ((1, D_FF), F32),
    )


@pytest.mark.parametrize("tag", ["fp8_e4m3", "int8"])
def test_quantize_dequantize_pallas(one_chip, tag):
    pol = QuantPolicy.parse(tag)
    scale = ((TOKENS, 1), F32)
    _compile(
        lambda x, s: quantize_pallas(x, s, pol, interpret=False),
        one_chip,
        ((TOKENS, D_MODEL), BF16),
        scale,
    )
    _compile(
        lambda q, s: dequantize_pallas(q, s, interpret=False),
        one_chip,
        ((TOKENS, D_MODEL), pol.operand_dtype),
        scale,
    )


def _atis_chains(phase):
    """The chains ``compile_plan`` emits for the paper's ATIS-TT layer
    (d 768, TT rank 8, 128 tokens) in one training phase."""
    from benchmarks.workloads import paper_workloads

    wl = next(w for w in paper_workloads() if w.name == "ATIS-TT")
    opts = TNNConfig(enabled=True, method="tt", rank=8, num_factors=3).search_options()
    fp, bp, (_, dw, wg) = tensorized._plans(wl.fact, wl.tokens, opts)
    results = {"fp": [fp], "bp": [bp], "wg": list(wg) + [dw] * bool(dw)}
    return [
        op
        for r in results[phase]
        for op in plan_compiler.compile_plan(r.plan).ops
        if isinstance(op, plan_compiler.ChainOp)
    ]


@pytest.mark.parametrize("phase", ["fp", "bp", "wg"])
def test_atis_tt_chains_compile(one_chip, phase):
    """The TT sweep's regrouped chains (``k_{i+1} = g * n_i``) lower."""
    chains = _atis_chains(phase)
    assert chains and any(op.m != op.m0 for op in chains)
    for op in chains:
        _compile(
            lambda x, *w: fc.chain_n_pallas(x, w, interpret=False),
            one_chip,
            ((op.m0, op.k), BF16),
            *((s, BF16) for s in op.link_shapes),
        )


def test_flash_attention_tinyllama(one_chip):
    q, kv = (8, 2048, 32, 64), (8, 2048, 4, 64)
    _compile(
        lambda q, k, v: flash_attention_fwd(q, k, v, interpret=False),
        one_chip,
        (q, BF16),
        (kv, BF16),
        (kv, BF16),
    )


def test_flash_attention_granite_head_64(one_chip):
    """Granite-4.0-H's attention: 32 heads of 64 over 8 KV heads, batch 2 x
    4096, its configured softmax scale."""
    q, kv = (2, 4096, 32, 64), (2, 4096, 8, 64)
    _compile(
        lambda q, k, v: flash_attention_fwd(
            q, k, v, softmax_scale=0.015625, interpret=False),
        one_chip,
        (q, BF16),
        (kv, BF16),
        (kv, BF16),
    )


def test_ssd_scan_granite_shapes(one_chip):
    """Granite-4.0-H's Mamba-2 scan: 2 x 64 streams of 4096 tokens, state
    128 x 64, the published chunk of 256."""
    bh, t, dk, dv = 2 * 64, 4096, 128, 64

    def fn(q, k, v, ld):
        return linear_scan_pallas(q, k, v, ld, mode="ssd", chunk=256, interpret=False)

    _compile(
        fn,
        one_chip,
        ((bh, t, dk), BF16),
        ((bh, t, dk), BF16),
        ((bh, t, dv), BF16),
        ((bh, t), F32),
    )


@pytest.mark.parametrize("mode", ["ssd", "rwkv6"])
def test_linear_scan_real_width(one_chip, mode):
    bh, t, d = 8 * 64, 2048, 64  # rwkv6-7b: 64 heads of 64

    def fn(q, k, v, ld, u):
        return linear_scan_pallas(q, k, v, ld, u, mode=mode, interpret=False)

    seq = ((bh, t, d), BF16)
    decay = ((bh, t), F32) if mode == "ssd" else ((bh, t, d), F32)
    _compile(fn, one_chip, seq, seq, seq, decay, ((bh, d), F32))


@pytest.mark.parametrize(
    "m0,shapes",
    [
        (4096, ((2048, 4096), (4096, 1024))),  # 39 MiB of f32 elements: guard rejects
        (4096, ((2048, 2048), (2048, 1024))),  # admitted near the limit
        (65536, ((96, 64), (512, 64), (512, 128))),  # deep regrouped chain
    ],
)
def test_chain_vmem_guard_matches_compiler(one_chip, m0, shapes):
    """A chain the VMEM guard admits compiles under the kernel's VMEM
    limit; one it rejects never reaches the compiler."""
    fits = fc.chain_vmem_bytes(m0, shapes) <= fc.CHAIN_VMEM_BUDGET_BYTES
    args = [((m0, shapes[0][0]), F32)] + [(s, F32) for s in shapes]

    def fn(x, *w):
        return fc.chain_n_pallas(x, w, interpret=False)

    if fits:
        _compile(fn, one_chip, *args)
    else:
        with pytest.raises(fc.ChainLoweringError, match="VMEM budget"):
            _compile(fn, one_chip, *args)


@pytest.mark.parametrize("phase", ["extend", "decode"])
def test_engine_tick_writes_the_cache_in_place(one_chip, phase):
    """The serving engine's tick programs at InternLM2's attention widths
    and the chat cell's cache (16 slots of 2080 rows; two layers): the
    donated cache is aliased to the output, and no instruction copies a
    whole stacked K or V, as one that lays the loop's stack out anew for
    extend's score product would."""
    import re

    from repro.models.lm import LM, LMConfig
    from repro.serving.engine import ServeEngine

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    class AbstractEngine(ServeEngine):
        def _init_device_cache(self):
            cache = jax.eval_shape(lambda: self.model.init_cache(
                self.batch, self.cache_len))
            self.cache = jax.tree.map(spec, cache._replace(
                length=jax.ShapeDtypeStruct((self.batch,), jnp.int32)))
            self.qkv = None

    cfg = LMConfig(name="chat-attn", num_layers=2, d_model=2048,
                   num_heads=16, num_kv_heads=8, head_dim=128, d_ff=256,
                   vocab=256, remat=False)
    model = LM(cfg)
    params = jax.tree.map(spec, jax.eval_shape(model.init, jax.random.key(0)))
    B, C = 16, 32
    eng = AbstractEngine(model, params, batch_size=B, max_len=2048,
                         prefill_chunk=C)
    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
    if phase == "extend":
        toks = jax.ShapeDtypeStruct((B, C), jnp.int32, sharding=one_chip)
        lowered = eng._extend_fn.lower(params, toks, eng.cache, vec, vec, mask)
    else:
        lowered = eng._decode_fn.lower(params, vec, eng.cache, vec, mask)
    compiled = lowered.compile()
    stack = eng.cache.layers.k
    stack_bytes = stack.size * stack.dtype.itemsize
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * stack_bytes
    dims = ",".join(map(str, stack.shape))
    copies = re.findall(rf"= bf16\[{dims}\]\S* copy\(", compiled.as_text())
    assert not copies
