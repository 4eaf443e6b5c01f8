"""Pure-jnp oracles for the Pallas kernels (the correctness contract).

Every kernel in this package is validated against these references in
``tests/test_kernels.py`` across shape/dtype sweeps (interpret mode on CPU,
compiled on real TPUs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul(x: jax.Array, w: jax.Array, *, transpose_rhs: bool = False,
           out_dtype=None) -> jax.Array:
    """C = X @ W (or X @ W.T) with f32 accumulation."""
    if transpose_rhs:
        w = w.T
    out = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def chain(x: jax.Array, a: jax.Array, b: jax.Array, *,
          out_dtype=None) -> jax.Array:
    """Y = (X @ A) @ B — two chained contraction steps, f32 accumulation.

    The Pallas version keeps the [bm, H] intermediate VMEM-resident
    (FETTA's no-external-memory chaining / ETTE look-ahead).
    """
    t = jnp.dot(x, a, preferred_element_type=jnp.float32)
    t = t.astype(x.dtype)
    out = jnp.dot(t, b, preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def linear_scan(q: jax.Array, k: jax.Array, v: jax.Array,
                log_decay: jax.Array, u: jax.Array | None = None, *,
                mode: str = "ssd", out_dtype=None) -> jax.Array:
    """Sequential-oracle linear recurrence (single stream):

        S_t = diag(d_t) S_{t-1} + k_t^T v_t
        o_t = q_t (diag(a_t) S_{t-1} + diag(g_t) k_t^T v_t)

    mode="ssd":   a = d, g = 1   (Mamba-2:  o_t = q_t S_t)
    mode="rwkv6": a = 1, g = u   (bonus on the current token)

    Shapes: q, k: [T, dk]; v: [T, dv]; u: [dk]; log_decay: [T] in ``ssd``
    mode (one decay per token), [T, dk] in ``rwkv6`` mode.
    """
    assert mode in ("ssd", "rwkv6")
    dk, dv = k.shape[-1], v.shape[-1]
    if mode == "ssd":
        log_decay = log_decay[:, None]             # [T, 1]: every row alike
    d = jnp.exp(log_decay.astype(jnp.float32))
    if u is None:
        u = jnp.zeros((dk,), jnp.float32)

    def step(state, inp):
        qt, kt, vt, dt = inp
        kv = jnp.outer(kt, vt)
        if mode == "ssd":
            seen = state * dt[:, None] + kv
        else:
            seen = state + u[:, None] * kv
        out = qt @ seen
        state = state * dt[:, None] + kv
        return state, out

    init = jnp.zeros((dk, dv), jnp.float32)
    state, out = jax.lax.scan(step, init, (q.astype(jnp.float32),
                                           k.astype(jnp.float32),
                                           v.astype(jnp.float32), d))
    return out.astype(out_dtype or v.dtype), state


def linear_scan_batched(q, k, v, log_decay, u=None, *, mode="ssd",
                        out_dtype=None):
    """vmap of :func:`linear_scan` over a leading [BH] axis.

    Returns (o: [BH, T, dv], final_state: [BH, dk, dv] f32)."""
    fn = lambda q_, k_, v_, ld_, u_: linear_scan(  # noqa: E731
        q_, k_, v_, ld_, u_, mode=mode, out_dtype=out_dtype)
    if u is None:
        u = jnp.zeros((q.shape[0], q.shape[-1]), jnp.float32)
    return jax.vmap(fn)(q, k, v, log_decay, u)


def chunked_linear_scan(q, k, v, log_decay, u=None, *, mode="ssd",
                        chunk=128, out_dtype=None):
    """Pure-jnp twin of the Pallas chunked kernel (same blocked math).

    Differentiable — it is the body autodiff traverses for the kernel's
    custom VJP — and MXU-friendly (two GEMMs per chunk, not T rank-1
    updates).  Shapes as :func:`linear_scan_batched`.
    """
    assert mode in ("ssd", "rwkv6")
    bh, t, dk = q.shape
    dv = v.shape[-1]
    assert t % chunk == 0
    nc, c = t // chunk, chunk
    f32 = jnp.float32
    if u is None:
        u = jnp.zeros((bh, dk), f32)

    def blocks(z, d):
        return jnp.moveaxis(z.astype(f32).reshape(bh, nc, c, d), 1, 0)

    if mode == "ssd":
        log_decay = log_decay[..., None]           # [BH, T, 1]
    qb, kb, vb, ldb = (blocks(q, dk), blocks(k, dk), blocks(v, dv),
                       blocks(log_decay, log_decay.shape[-1]))
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    tri = (row >= col) if mode == "ssd" else (row > col)

    def step(state, blk):
        qc, kc, vc, ldc = blk                      # [BH, C, d*]
        lc = jnp.cumsum(ldc, axis=1)
        ex = lc if mode == "ssd" else lc - ldc
        qt = qc * jnp.exp(ex)
        if mode == "ssd":
            # lc: [BH, C, 1]; the ratio exp(lc_i - lc_j) as such
            seg = lc[:, :, None, 0] - lc[:, None, :, 0]            # [BH,C,C]
            ratio = jnp.exp(jnp.where(tri[None], seg, -jnp.inf))
            att = jnp.einsum("bik,bjk->bij", qc, kc) * ratio
        else:
            att = jnp.einsum("bik,bjk->bij", qt, kc * jnp.exp(-lc))
            att = jnp.where(tri[None], att, 0.0)
            diag = jnp.sum(qc * u[:, None, :] * kc, axis=-1)   # [BH, C]
            att = att + jax.vmap(jnp.diag)(diag)
        o = jnp.einsum("bij,bjv->biv", att, vc) + jnp.einsum(
            "bik,bkv->biv", qt, state)
        k_s = kc * jnp.exp(lc[:, -1:, :] - lc)
        state = (state * jnp.exp(lc[:, -1])[..., None]
                 + jnp.einsum("bck,bcv->bkv", k_s, vc))
        return state, o

    init = jnp.zeros((bh, dk, dv), f32)
    state, ob = jax.lax.scan(step, init, (qb, kb, vb, ldb))
    o = jnp.moveaxis(ob, 0, 1).reshape(bh, t, dv)
    return o.astype(out_dtype or v.dtype), state
