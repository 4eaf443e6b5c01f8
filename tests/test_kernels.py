"""Per-kernel shape/dtype sweeps against the ref.py oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

F32, BF16 = jnp.float32, jnp.bfloat16


def _assert_close(got, want, dtype):
    tol = 1e-4 if dtype == F32 else 2.5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize(
    "m,k,n", [(128, 128, 128), (200, 96, 72), (64, 256, 128), (13, 7, 5), (1, 384, 256)]
)
@pytest.mark.parametrize("transpose_rhs", [False, True])
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_fused_matmul(m, k, n, transpose_rhs, dtype):
    x = jax.random.normal(jax.random.key(0), (m, k), dtype)
    wshape = (n, k) if transpose_rhs else (k, n)
    w = jax.random.normal(jax.random.key(1), wshape, dtype)
    got = ops.fused_matmul(x, w, transpose_rhs=transpose_rhs)
    want = ref.matmul(x, w, transpose_rhs=transpose_rhs)
    assert got.shape == (m, n) and got.dtype == dtype
    _assert_close(got, want, dtype)


@pytest.mark.parametrize(
    "m,k,h,n", [(128, 64, 32, 128), (200, 96, 48, 130), (64, 144, 96, 72)]
)
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_fused_chain(m, k, h, n, dtype):
    x = jax.random.normal(jax.random.key(0), (m, k), dtype)
    a = jax.random.normal(jax.random.key(1), (k, h), dtype)
    b = jax.random.normal(jax.random.key(2), (h, n), dtype)
    got = ops.fused_chain(x, a, b)
    want = ref.chain(x, a, b)
    assert got.shape == (m, n)
    _assert_close(got, want, dtype)


@pytest.mark.parametrize("mode", ["ssd", "rwkv6"])
@pytest.mark.parametrize("t,chunk", [(256, 64), (128, 128), (384, 96)])
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_linear_scan(mode, t, chunk, dtype):
    bh, dk, dv = 3, 32, 64
    key = jax.random.key(0)
    q = (jax.random.normal(key, (bh, t, dk), F32) * 0.5).astype(dtype)
    k = (jax.random.normal(jax.random.key(1), (bh, t, dk), F32) * 0.5).astype(dtype)
    v = (jax.random.normal(jax.random.key(2), (bh, t, dv), F32) * 0.5).astype(dtype)
    # ssd (Mamba-2): one decay per stream and token; rwkv6: one per channel
    decay_shape = (bh, t) if mode == "ssd" else (bh, t, dk)
    ld = -jnp.exp(jax.random.normal(jax.random.key(3), decay_shape, F32)) * 0.1
    u = jax.random.normal(jax.random.key(4), (bh, dk), F32) * 0.5
    got, got_state = ops.linear_scan(
        q, k, v, ld, u, mode=mode, chunk=chunk, use_pallas=True
    )
    want, want_state = ref.linear_scan_batched(q, k, v, ld, u, mode=mode)
    assert got.shape == (bh, t, dv)
    tol = 5e-3 if dtype == F32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )
    # the final-state output (what prefill hands to decode) must also match
    np.testing.assert_allclose(
        np.asarray(got_state),
        np.asarray(want_state),
        rtol=max(tol, 1e-2),
        atol=max(tol, 1e-2),
    )


@pytest.mark.parametrize("use_pallas", [True, False])
def test_linear_scan_ssd_strong_decay(use_pallas):
    """Mamba-2 decays that sum to about -250 over a chunk, where
    exp(-cumsum) overflows float32: the chunked scan stays finite and is
    the recurrence."""
    bh, t, dk, dv = 2, 256, 16, 16
    q = jax.random.normal(jax.random.key(0), (bh, t, dk)) * 0.5
    k = jax.random.normal(jax.random.key(1), (bh, t, dk)) * 0.5
    v = jax.random.normal(jax.random.key(2), (bh, t, dv)) * 0.5
    ld = -4.0 * jax.random.uniform(jax.random.key(3), (bh, t))
    got, got_state = ops.linear_scan(
        q, k, v, ld, mode="ssd", chunk=128, use_pallas=use_pallas
    )
    want, want_state = ref.linear_scan_batched(q, k, v, ld, mode="ssd")
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(got_state), np.asarray(want_state), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("mode", ["ssd", "rwkv6"])
def test_linear_scan_rejects_the_other_modes_decay(mode):
    """ssd mode takes one decay per stream and token ([BH, T]), rwkv6 one
    per channel ([BH, T, dk]); each refuses the other's shape."""
    bh, t, dk = 2, 64, 16
    x = jnp.zeros((bh, t, dk))
    wrong = jnp.zeros((bh, t, dk) if mode == "ssd" else (bh, t))
    with pytest.raises(ValueError, match="log-decay"):
        ops.linear_scan(x, x, x, wrong, mode=mode, chunk=64, use_pallas=False)


def test_linear_scan_state_continuity():
    """Chunk boundaries must be invisible: chunk=64 == chunk=128 results."""
    bh, t, dk, dv = 2, 256, 16, 16
    q = jax.random.normal(jax.random.key(0), (bh, t, dk)) * 0.5
    k = jax.random.normal(jax.random.key(1), (bh, t, dk)) * 0.5
    v = jax.random.normal(jax.random.key(2), (bh, t, dv)) * 0.5
    ld = -jnp.ones((bh, t)) * 0.05
    a, sa = ops.linear_scan(q, k, v, ld, mode="ssd", chunk=64, use_pallas=True)
    b, sb = ops.linear_scan(q, k, v, ld, mode="ssd", chunk=128, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sa), np.asarray(sb), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("qc,kc", [(32, 32), (64, 32), (128, 64)])
def test_flash_attention_kernel(causal, qc, kc):
    """Pallas flash forward == the jnp blockwise twin (GQA, incl. lse)."""
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models.blocks import _blockwise_attention_fwd_only

    B, Tq, Tk, KV, G, D = 2, 128, 128, 2, 3, 32
    q = jax.random.normal(jax.random.key(0), (B, Tq, KV * G, D)) * 0.5
    k = jax.random.normal(jax.random.key(1), (B, Tk, KV, D)) * 0.5
    v = jax.random.normal(jax.random.key(2), (B, Tk, KV, D)) * 0.5
    got, got_lse = flash_attention_fwd(q, k, v, causal=causal, q_chunk=qc, kv_chunk=kc)
    want, want_lse = _blockwise_attention_fwd_only(
        q, k, v, causal=causal, q_chunk=qc, kv_chunk=kc
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(got_lse), np.asarray(want_lse), rtol=2e-4, atol=2e-4
    )
