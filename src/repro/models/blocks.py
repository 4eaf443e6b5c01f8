"""Transformer building blocks — pure-JAX functional modules.

Conventions used across the model zoo:

* A module is a pair of functions ``<name>_init(key, cfg...) -> params`` and
  ``<name>_apply(params, x, ...) -> y``; params are pytrees of arrays only
  (static structure lives in configs / closures), so everything composes
  with jit / scan / grad untouched.
* Layer stacks are scanned: params are stacked along a leading layer axis
  by ``jax.vmap``-ed inits, keeping compiled HLO O(1 layer).
* ``dense`` transparently swaps to a :class:`TensorizedLinear` when a
  :class:`~repro.core.tensorized.TNNConfig` is attached — this is how the
  paper's technique enters every architecture.
* Sharding is injected via ``shard(x, logical_axes)`` callbacks
  (``repro.distributed.sharding``); modules never name mesh axes directly.
"""

from __future__ import annotations

import dataclasses
import math
import os as _os
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core.tensorized import TNNConfig, TensorizedLinear, make_tensorized_linear

Shard = Callable[[jax.Array, tuple[Optional[str], ...]], jax.Array]

# The CPU backend's DotThunk cannot execute batched bf16 x bf16 -> f32 dots;
# on CPU we upcast operands instead (identical math, MXU-equivalent on TPU).
# The dry-run sets REPRO_ASSUME_TPU_DOTS=1: it only lowers+compiles (never
# executes), and the upcast copies would otherwise inflate the roofline
# memory term with traffic that does not exist on the MXU.
_CPU = (jax.default_backend() == "cpu"
        and not _os.environ.get("REPRO_ASSUME_TPU_DOTS"))


def einsum_f32(spec: str, *ops: jax.Array) -> jax.Array:
    """einsum with f32 accumulation that also runs on the CPU backend."""
    if _CPU and any(o.dtype == jnp.bfloat16 for o in ops):
        ops = tuple(o.astype(jnp.float32) for o in ops)
    return jnp.einsum(spec, *ops, preferred_element_type=jnp.float32)


def no_shard(x, axes):
    return x


# ---------------------------------------------------------------------------
# Dense / tensorized projection
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Dense:
    """A projection that is either a dense matrix or a TNN factor network."""

    d_in: int
    d_out: int
    use_bias: bool = False
    tnn: TNNConfig | None = None        # None or disabled -> dense
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16

    def _tnn_layer(self) -> TensorizedLinear | None:
        if self.tnn is not None and self.tnn.enabled:
            return make_tensorized_linear(
                self.d_out, self.d_in, self.tnn, use_bias=self.use_bias,
                param_dtype=self.param_dtype, compute_dtype=self.compute_dtype)
        return None

    def init(self, key: jax.Array) -> dict:
        layer = self._tnn_layer()
        if layer is not None:
            return layer.init(key)
        std = 1.0 / math.sqrt(self.d_in)
        p = {"w": (jax.random.normal(key, (self.d_in, self.d_out), jnp.float32)
                   * std).astype(self.param_dtype)}
        if self.use_bias:
            p["b"] = jnp.zeros((self.d_out,), self.param_dtype)
        return p

    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        layer = self._tnn_layer()
        if layer is not None:
            return layer(params, x)
        y = jnp.dot(x.astype(self.compute_dtype),
                    params["w"].astype(self.compute_dtype),
                    preferred_element_type=jnp.float32)
        if self.use_bias:
            y = y + params["b"].astype(jnp.float32)
        return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * params["scale"]).astype(dt)


def groupnorm_heads(x: jax.Array, scale: jax.Array, eps: float = 1e-5
                    ) -> jax.Array:
    """Per-head normalisation used by RWKV-6 output (x: [..., H, D])."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * scale).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0
         ) -> jax.Array:
    """Rotary embedding.  x: [B, T, H, D], positions: [B, T]."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs   # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA) — full, blockwise (flash-style) and decode paths
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jax.Array          # [B, max_len, KV, D]
    v: jax.Array          # [B, max_len, KV, D]
    length: jax.Array     # [] int32 — tokens currently valid; the serving
                          # engine's slot table passes a per-slot [B] vector
                          # instead (co-batched requests at different depths)


def _keep_frozen(buf: jax.Array, new: jax.Array, start: jax.Array,
                 valid: jax.Array, layer=None) -> jax.Array:
    """The rows to write into ``buf`` ([B, T, ...], or with ``layer`` the
    stacked [L, B, T, ...]) at each slot's ``start`` ([B]): ``new``
    ([B, C, ...]) where ``valid > 0``, and where ``valid == 0`` the C rows
    already there, so that the write leaves a frozen slot exactly as it
    was.  Reads only the rows to be written, never the whole buffer."""
    with jax.named_scope("engine_select"):
        # One dynamic_slice per slot: a vmapped one lowers to a gather,
        # for which the TPU compiler re-lays out the whole buffer.
        lead = () if layer is None else (layer,)
        rows = (1, new.shape[1]) + buf.shape[len(lead) + 2:]
        size = (1,) * len(lead) + rows
        zeros = (0,) * (len(rows) - 2)
        old = jnp.concatenate([jax.lax.dynamic_slice(
            buf, lead + (b, start[b]) + zeros, size).reshape(rows)
            for b in range(new.shape[0])])
        keep = (valid > 0).reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(keep, new.astype(buf.dtype), old)


def _write_rows(buf: jax.Array, new: jax.Array, start: jax.Array,
                layer) -> tuple[jax.Array, jax.Array]:
    """Write ``new`` ([B, C, ...]) into the stacked cache ``buf`` ([L, B,
    T, ...]) at (``layer``, slot, ``start``), in place: one
    dynamic_update_slice per slot for a per-slot ``start`` ([B]; a vmapped
    one lowers to a scatter), one for all slots for a scalar ``start``.
    Returns the stack and its layer ``layer`` as attention reads it."""
    new = new.astype(buf.dtype)
    zeros = (0,) * (buf.ndim - 3)
    if jnp.ndim(start) == 0:
        buf = jax.lax.dynamic_update_slice(buf, new[None],
                                           (layer, 0, start) + zeros)
    else:
        for b in range(new.shape[0]):
            buf = jax.lax.dynamic_update_slice(
                buf, new[b][None, None], (layer, b, start[b]) + zeros)
    # Hold the stack in the layout it is passed in.  Left free, the TPU
    # compiler lays the loop's stack out for extend's score product, and
    # so copies the whole cache into and out of that layout on each call.
    buf = with_layout_constraint(buf, Layout(tuple(range(buf.ndim))))
    return buf, jax.lax.dynamic_index_in_dim(buf, layer, keepdims=False)


@dataclasses.dataclass(frozen=True)
class Attention:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True            # False: no position embedding (NoPE)
    softmax_scale: float | None = None   # None: 1 / sqrt(head_dim)
    causal: bool = True
    q_chunk: int = 512               # blockwise attention tile sizes
    kv_chunk: int = 1024
    tnn: TNNConfig | None = None     # tensorize q/o projections if targeted
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16

    def _proj(self, d_in, d_out, bias, target: str) -> Dense:
        tnn = self.tnn if (self.tnn and target in self.tnn.targets) else None
        return Dense(d_in, d_out, use_bias=bias, tnn=tnn,
                     param_dtype=self.param_dtype,
                     compute_dtype=self.compute_dtype)

    @property
    def _shapes(self):
        H, KV, D = self.num_heads, self.num_kv_heads, self.head_dim
        return H, KV, D

    def init(self, key: jax.Array) -> dict:
        H, KV, D = self._shapes
        kq, kk, kv, ko = jax.random.split(key, 4)
        return {
            "q": self._proj(self.d_model, H * D, self.qkv_bias, "qkv").init(kq),
            "k": self._proj(self.d_model, KV * D, self.qkv_bias, "qkv").init(kk),
            "v": self._proj(self.d_model, KV * D, self.qkv_bias, "qkv").init(kv),
            "o": self._proj(H * D, self.d_model, False, "out").init(ko),
        }

    # -- projections --------------------------------------------------------

    def _qkv(self, params, x, positions):
        B, T, _ = x.shape
        H, KV, D = self._shapes
        q = self._proj(self.d_model, H * D, self.qkv_bias, "qkv")(
            params["q"], x).reshape(B, T, H, D)
        k = self._proj(self.d_model, KV * D, self.qkv_bias, "qkv")(
            params["k"], x).reshape(B, T, KV, D)
        v = self._proj(self.d_model, KV * D, self.qkv_bias, "qkv")(
            params["v"], x).reshape(B, T, KV, D)
        if self.use_rope:
            q = rope(q, positions, self.rope_theta)
            k = rope(k, positions, self.rope_theta)
        return q, k, v

    def _scaled(self, scores):
        if self.softmax_scale is None:
            return scores / math.sqrt(self.head_dim)
        return scores * self.softmax_scale

    def _out(self, params, ctx):
        B, T = ctx.shape[:2]
        H, _, D = self._shapes
        return self._proj(H * D, self.d_model, False, "out")(
            params["o"], ctx.reshape(B, T, H * D))

    # -- full-sequence (training / prefill) ---------------------------------

    def __call__(self, params: dict, x: jax.Array, positions: jax.Array,
                 shard: Shard = no_shard) -> jax.Array:
        with jax.named_scope("attention"):
            q, k, v = self._qkv(params, x, positions)
            q = shard(q, ("batch", "seq", "heads", None))
            k = shard(k, ("batch", "seq", "kv_heads", None))
            ctx = blockwise_attention(q, k, v, causal=self.causal,
                                      q_chunk=self.q_chunk,
                                      kv_chunk=self.kv_chunk,
                                      softmax_scale=self.softmax_scale,
                                      mesh=getattr(shard, "mesh", None))
            return self._out(params, ctx)

    def prefill(self, params, x, positions, max_len: int, shard: Shard = no_shard):
        """Run full attention and return the populated KV cache."""
        q, k, v = self._qkv(params, x, positions)
        ctx = blockwise_attention(q, k, v, causal=self.causal,
                                  q_chunk=self.q_chunk, kv_chunk=self.kv_chunk,
                                  softmax_scale=self.softmax_scale,
                                  mesh=getattr(shard, "mesh", None))
        B, T, KV, D = k.shape
        pad = max_len - T
        cache = KVCache(
            k=jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
            v=jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
            length=jnp.array(T, jnp.int32),
        )
        return self._out(params, ctx), cache

    def decode_step(self, params, x, cache: KVCache, shard: Shard = no_shard,
                    valid: jax.Array | None = None, layer=None):
        """One-token decode.  x: [B, 1, d_model].

        ``cache.length`` is a scalar (every slot at the same depth — the
        historical path, bit-identical) or a per-slot ``[B]`` vector: each
        slot then writes its k/v at its own offset and masks to its own
        depth, which is what lets the serving engine mix requests of
        different lengths in one decode tick.

        ``valid`` ([B] int32 of 0 or 1, None = every slot) freezes the
        slots with 0: their write at ``length`` puts back the row already
        there, and their length does not advance.  Only that one row per
        slot is read and selected, never the whole buffer; a frozen slot's
        logits are don't-care.

        With ``layer`` (an index into the stack), ``cache.k``/``cache.v``
        are the whole stacked ``[L, B, T, KV, hd]`` buffers: only the new
        rows are written into them, attention reads layer ``layer``, and
        the returned cache holds the stacks."""
        with jax.named_scope("attention"):
            B = x.shape[0]
            H, KV, D = self._shapes
            length = cache.length
            per_slot = jnp.ndim(length) == 1
            if per_slot:
                positions = length[:, None]
            else:
                positions = jnp.broadcast_to(length, (B, 1))
            q, k, v = self._qkv(params, x, positions)
            if valid is not None:
                k = _keep_frozen(cache.k, k, positions[:, 0], valid, layer)
                v = _keep_frozen(cache.v, v, positions[:, 0], valid, layer)
            if layer is not None:
                ks, kc = _write_rows(cache.k, k, length, layer)
                vs, vc = _write_rows(cache.v, v, length, layer)
            elif per_slot:
                upd = jax.vmap(
                    lambda buf, new, start: jax.lax.dynamic_update_slice_in_dim(
                        buf, new, start, axis=0))
                kc = ks = upd(cache.k, k, length)
                vc = vs = upd(cache.v, v, length)
            else:
                kc = ks = jax.lax.dynamic_update_slice_in_dim(
                    cache.k, k, length, axis=1)
                vc = vs = jax.lax.dynamic_update_slice_in_dim(
                    cache.v, v, length, axis=1)
            new_cache = KVCache(ks, vs, length + (1 if valid is None
                                                  else valid))

            groups = H // KV
            qg = q.reshape(B, 1, KV, groups, D)
            scores = self._scaled(jnp.einsum(
                "bqkgd,btkd->bkgqt", qg.astype(jnp.float32),
                kc.astype(jnp.float32)))
            t_idx = jnp.arange(kc.shape[1])
            if per_slot:
                mask = (t_idx[None, None, None, None, :]
                        <= length[:, None, None, None, None])
            else:
                mask = t_idx[None, None, None, None, :] <= length
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bkgqt,btkd->bqkgd", probs,
                             vc.astype(jnp.float32)).astype(x.dtype)
            ctx = ctx.reshape(B, 1, H, D)
            return self._out(params, ctx), new_cache

    def extend(self, params, x, cache: KVCache, shard: Shard = no_shard,
               valid: jax.Array | None = None, layer=None):
        """Chunked prefill: append a C-token chunk per slot at each slot's
        current cache depth.  x: [B, C, d_model]; ``cache.length`` scalar
        or per-slot [B].

        ``valid`` ([B] int32, None = whole chunk) marks how many of the C
        tokens are real per slot.  In a slot with ``valid > 0``, k/v
        beyond its valid count are written as zeros: they sit past the
        advanced length so the causal mask never exposes them (decode
        overwrites them in order later), and zeros keep the quantized-KV
        running amax clean of padding garbage.  A slot with ``valid == 0``
        is frozen: it writes back its own C rows as they were, so the call
        leaves its k/v and length exactly as it found them.  Logits come
        back for every chunk position ([B, C, ...]); the caller reads row
        ``valid-1`` of slots whose prompt completed — in-chunk queries
        past valid, and every query of a frozen slot, produce don't-care
        rows.  ``layer`` is as in :meth:`decode_step`: a stacked cache
        written in place."""
        with jax.named_scope("attention"):
            B, C, _ = x.shape
            H, KV, D = self._shapes
            length = cache.length
            if jnp.ndim(length) == 0:
                length = jnp.full((B,), length, jnp.int32)
            positions = length[:, None] + jnp.arange(C)[None, :]      # [B, C]
            q, k, v = self._qkv(params, x, positions)
            if valid is not None:
                keep = (jnp.arange(C)[None, :] < valid[:, None])[..., None, None]
                k = jnp.where(keep, k, jnp.zeros((), k.dtype))
                v = jnp.where(keep, v, jnp.zeros((), v.dtype))
                k = _keep_frozen(cache.k, k, length, valid, layer)
                v = _keep_frozen(cache.v, v, length, valid, layer)
            if layer is not None:
                ks, kc = _write_rows(cache.k, k, length, layer)
                vs, vc = _write_rows(cache.v, v, length, layer)
            else:
                upd = jax.vmap(
                    lambda buf, new, start: jax.lax.dynamic_update_slice_in_dim(
                        buf, new.astype(buf.dtype), start, axis=0))
                kc = ks = upd(cache.k, k, length)
                vc = vs = upd(cache.v, v, length)
            adv = C if valid is None else valid
            new_cache = KVCache(ks, vs, cache.length + adv)

            groups = H // KV
            qg = q.reshape(B, C, KV, groups, D)
            scores = self._scaled(jnp.einsum(
                "bckgd,btkd->bkgct", qg.astype(jnp.float32),
                kc.astype(jnp.float32)))
            t_idx = jnp.arange(kc.shape[1])
            mask = (t_idx[None, None, None, None, :]
                    <= positions[:, None, None, :, None])
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bkgct,btkd->bckgd", probs,
                             vc.astype(jnp.float32)).astype(x.dtype)
            ctx = ctx.reshape(B, C, H, D)
            return self._out(params, ctx), new_cache


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, q_chunk: int, kv_chunk: int,
                        softmax_scale: float | None = None,
                        flash_bwd: bool = True, mesh=None) -> jax.Array:
    """Memory-efficient attention with online softmax (flash-style).

    Never materialises the [T, T] score matrix: scans KV in chunks carrying
    (running max, running denominator, accumulated numerator) — O(T * chunk)
    memory, which is what makes prefill_32k fit HBM at scale.
    GQA: q [B, Tq, H, D], k/v [B, Tk, KV, D] with H = KV * groups.

    ``flash_bwd=True`` routes through a custom VJP whose backward
    *recomputes* per-chunk probabilities from saved (q, k, v, lse) instead
    of letting autodiff stash [nk, ..., q_chunk, kv_chunk] probability
    stacks in HBM — the flash-attention backward.  This was the dominant
    memory-roofline term of every training cell (EXPERIMENTS.md §Perf H1).

    ``mesh`` (the activations' device mesh, if any) lets the Pallas
    forward run per batch shard; the compiler cannot partition it.
    """
    if flash_bwd:
        scale = softmax_scale or 1.0 / math.sqrt(q.shape[-1])
        return _flash_attention(q, k, v, causal, min(q_chunk, q.shape[1]),
                                min(kv_chunk, k.shape[1]), scale, mesh)
    return _blockwise_attention_fwd_only(
        q, k, v, causal=causal, q_chunk=q_chunk, kv_chunk=kv_chunk,
        softmax_scale=softmax_scale)[0]


def _blockwise_attention_fwd_only(q, k, v, *, causal, q_chunk, kv_chunk,
                                  softmax_scale=None):
    """Forward pass; also returns the log-sum-exp stats [B, Tq, KV, G]."""
    B, Tq, H, D = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    groups = H // KV
    scale = softmax_scale or 1.0 / math.sqrt(D)

    q_chunk = min(q_chunk, Tq)
    kv_chunk = min(kv_chunk, Tk)
    assert Tq % q_chunk == 0 and Tk % kv_chunk == 0, (
        f"sequence ({Tq},{Tk}) not divisible by chunks ({q_chunk},{kv_chunk})")
    nq, nk = Tq // q_chunk, Tk // kv_chunk

    # Operands stay in their storage dtype (bf16); f32 appears only in the
    # per-chunk scores and the online-softmax accumulators — no full-
    # sequence f32 copies of Q/K/V are ever materialised.
    qc = q.reshape(B, nq, q_chunk, KV, groups, D)
    kc = k.reshape(B, nk, kv_chunk, KV, D)
    vc = v.reshape(B, nk, kv_chunk, KV, D)

    q_pos = jnp.arange(Tq).reshape(nq, q_chunk)
    k_pos = jnp.arange(Tk).reshape(nk, kv_chunk)

    def per_q_chunk(q_blk, qpos_blk):
        # q_blk: [B, qc, KV, G, D]; qpos_blk: [qc]
        def kv_step(carry, inputs):
            m, l, acc = carry
            k_blk, v_blk, kp = inputs        # [B, kc, KV, D], [kc]
            s = einsum_f32("bqkgd,btkd->bkgqt", q_blk, k_blk) * scale
            if causal:
                mask = qpos_blk[:, None] >= kp[None, :]
                s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + einsum_f32(
                "bkgqt,btkd->bkgqd", p.astype(v_blk.dtype), v_blk)
            return (m_new, l, acc), None

        m0 = jnp.full((B, KV, groups, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, groups, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, groups, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), k_pos))
        out = acc / jnp.maximum(l[..., None], 1e-30)          # [B,KV,G,qc,D]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))              # [B,KV,G,qc]
        return (jnp.transpose(out, (0, 3, 1, 2, 4)),          # [B,qc,KV,G,D]
                jnp.transpose(lse, (0, 3, 1, 2)))             # [B,qc,KV,G]

    if nq == 1:
        out, lse = per_q_chunk(qc[:, 0], q_pos[0])
        out, lse = out[:, None], lse[:, None]
    else:
        # Sequential over q chunks (lax.map): keeps the live f32 score
        # tile at [B,KV,G,q_chunk,kv_chunk] instead of the full
        # [.., Tq, kv_chunk] a vmap would materialise — this is what lets
        # prefill_32k fit HBM.
        out, lse = jax.lax.map(lambda args: per_q_chunk(*args),
                               (jnp.moveaxis(qc, 1, 0), q_pos))
        out, lse = jnp.moveaxis(out, 0, 1), jnp.moveaxis(lse, 0, 1)
    out = out.reshape(B, Tq, H, D)
    lse = lse.reshape(B, Tq, KV, groups)
    return out.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# Flash backward (custom VJP): recompute probabilities chunk-wise
# ---------------------------------------------------------------------------


_USE_PALLAS_FLASH = jax.default_backend() == "tpu"


def _flash_forward_dispatch(q, k, v, causal, q_chunk, kv_chunk, scale,
                            mesh):
    """On TPU the forward runs the Pallas kernel (probability tiles never
    leave VMEM), once per batch shard of ``mesh``; elsewhere the jnp twin
    with identical semantics."""
    if _USE_PALLAS_FLASH:
        from repro.distributed.sharding import batch_parallel
        from repro.kernels.flash_attention import flash_attention_fwd
        fwd = partial(flash_attention_fwd, causal=causal, q_chunk=q_chunk,
                      kv_chunk=kv_chunk, softmax_scale=scale)
        return batch_parallel(fwd, mesh, q.shape[0])(q, k, v)
    return _blockwise_attention_fwd_only(
        q, k, v, causal=causal, q_chunk=q_chunk, kv_chunk=kv_chunk,
        softmax_scale=scale)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal: bool, q_chunk: int, kv_chunk: int,
                     scale: float, mesh):
    return _flash_forward_dispatch(q, k, v, causal, q_chunk, kv_chunk,
                                   scale, mesh)[0]


def _flash_fwd(q, k, v, causal, q_chunk, kv_chunk, scale, mesh):
    out, lse = _flash_forward_dispatch(q, k, v, causal, q_chunk, kv_chunk,
                                       scale, mesh)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_chunk, kv_chunk, scale, mesh, res, do):
    """Flash backward: for each (kv, q) chunk pair, recompute
    p = exp(q k^T scale - lse) from the saved stats, then

        dv_j += p^T do_i
        ds    = p * (do_i v_j^T - delta_i) * scale
        dq_i += ds k_j ;  dk_j += ds^T q_i

    All chunk-pair intermediates are fusion-local; only q/k/v-sized
    accumulators touch HBM (vs autodiff's [nk, ...] probability stacks).
    """
    q, k, v, out, lse = res
    B, Tq, H, D = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    G = H // KV
    nq, nk = Tq // q_chunk, Tk // kv_chunk
    f32 = jnp.float32

    # delta_i = rowsum(do * out)  [B, Tq, KV, G]
    delta = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1)
    delta = delta.reshape(B, Tq, KV, G)

    qc = jnp.moveaxis(q.reshape(B, nq, q_chunk, KV, G, D), 1, 0)
    doc = jnp.moveaxis(do.reshape(B, nq, q_chunk, KV, G, D), 1, 0)
    lsec = jnp.moveaxis(lse.reshape(B, nq, q_chunk, KV, G), 1, 0)
    dlc = jnp.moveaxis(delta.reshape(B, nq, q_chunk, KV, G), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nk, kv_chunk, KV, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, kv_chunk, KV, D), 1, 0)
    q_pos = jnp.arange(Tq).reshape(nq, q_chunk)
    k_pos = jnp.arange(Tk).reshape(nk, kv_chunk)

    def kv_outer(carry_dq, kv_in):
        k_blk, v_blk, kp = kv_in                 # [B, kc, KV, D], [kc]

        def q_inner(carry_kv, q_in):
            dk_j, dv_j = carry_kv
            q_blk, do_blk, lse_blk, dl_blk, qp = q_in
            s = einsum_f32("bqkgd,btkd->bkgqt", q_blk, k_blk) * scale
            if causal:
                mask = qp[:, None] >= kp[None, :]
                s = jnp.where(mask[None, None, None], s, -jnp.inf)
            p = jnp.exp(s - jnp.transpose(lse_blk, (0, 2, 3, 1))[..., None])
            dov = einsum_f32("bqkgd,btkd->bkgqt", do_blk, v_blk)
            ds = p * (dov - jnp.transpose(dl_blk, (0, 2, 3, 1))[..., None]
                      ) * scale
            pb = p.astype(v_blk.dtype)
            dsb = ds.astype(q_blk.dtype)
            dv_j = dv_j + einsum_f32("bkgqt,bqkgd->btkd", pb, do_blk)
            dk_j = dk_j + einsum_f32("bkgqt,bqkgd->btkd", dsb, q_blk)
            dq_i = einsum_f32("bkgqt,btkd->bqkgd", dsb, k_blk)
            return (dk_j, dv_j), dq_i

        zeros_kv = (jnp.zeros((B, kv_chunk, KV, D), f32),
                    jnp.zeros((B, kv_chunk, KV, D), f32))
        (dk_j, dv_j), dq_parts = jax.lax.scan(
            q_inner, zeros_kv, (qc, doc, lsec, dlc, q_pos))
        carry_dq = carry_dq + dq_parts           # [nq, B, qc, KV, G, D]
        return carry_dq, (dk_j, dv_j)

    dq0 = jnp.zeros((nq, B, q_chunk, KV, G, D), f32)
    dq, (dk, dv) = jax.lax.scan(kv_outer, dq0, (kc, vc, k_pos))
    dq = jnp.moveaxis(dq, 0, 1).reshape(B, Tq, H, D).astype(q.dtype)
    dk = jnp.moveaxis(dk, 0, 1).reshape(B, Tk, KV, D).astype(k.dtype)
    dv = jnp.moveaxis(dv, 0, 1).reshape(B, Tk, KV, D).astype(v.dtype)
    return dq, dk, dv


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# MLP (SwiGLU) — dense or tensorized
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SwiGLU:
    d_model: int
    d_ff: int
    tnn: TNNConfig | None = None
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16

    def _proj(self, d_in, d_out) -> Dense:
        tnn = self.tnn if (self.tnn and "mlp" in self.tnn.targets) else None
        return Dense(d_in, d_out, tnn=tnn, param_dtype=self.param_dtype,
                     compute_dtype=self.compute_dtype)

    def init(self, key: jax.Array) -> dict:
        kg, ku, kd = jax.random.split(key, 3)
        return {
            "gate": self._proj(self.d_model, self.d_ff).init(kg),
            "up": self._proj(self.d_model, self.d_ff).init(ku),
            "down": self._proj(self.d_ff, self.d_model).init(kd),
        }

    def __call__(self, params: dict, x: jax.Array,
                 shard: Shard = no_shard) -> jax.Array:
        g = self._proj(self.d_model, self.d_ff)(params["gate"], x)
        u = self._proj(self.d_model, self.d_ff)(params["up"], x)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        h = shard(h, ("batch", "seq", "ff"))
        return self._proj(self.d_ff, self.d_model)(params["down"], h)


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k, capacity-dropped, gather/scatter dispatch)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoE:
    """Top-k routed expert SwiGLU FFN.

    Dispatch uses gather/scatter (O(E*C*D) bytes) rather than one-hot
    einsums (O(T*E*C*D) FLOPs), and is written per token-group so the group
    axis shards over `data` and the expert axis over `model` (expert
    parallelism); XLA then inserts exactly one all-reduce on the combine.
    Tokens beyond an expert's capacity are dropped (standard capacity-factor
    routing); the router carries a load-balance auxiliary loss.

    With ``tnn`` targeting "mlp", each expert's FFN matrices are stored as
    stacked TNN cores — one factorization shared across the expert axis
    (per-arch note in DESIGN.md §Arch-applicability).
    """

    d_model: int
    d_ff: int                      # per-expert hidden dim
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    tnn: TNNConfig | None = None
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16

    def init(self, key: jax.Array) -> dict:
        kr, kg, ku, kd = jax.random.split(key, 4)
        E, D, F = self.num_experts, self.d_model, self.d_ff
        std_in, std_out = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)

        tnn_on = self.tnn is not None and self.tnn.enabled and (
            "mlp" in self.tnn.targets)
        if tnn_on:
            gate_l = make_tensorized_linear(F, D, self.tnn,
                                            param_dtype=self.param_dtype,
                                            compute_dtype=self.compute_dtype)
            down_l = make_tensorized_linear(D, F, self.tnn,
                                            param_dtype=self.param_dtype,
                                            compute_dtype=self.compute_dtype)
            def stack_init(layer, k):
                return jax.vmap(layer.init)(jax.random.split(k, E))
            experts = {
                "gate": stack_init(gate_l, kg),
                "up": stack_init(gate_l, ku),
                "down": stack_init(down_l, kd),
            }
        else:
            experts = {
                "gate": {"w": (jax.random.normal(kg, (E, D, F), jnp.float32)
                               * std_in).astype(self.param_dtype)},
                "up": {"w": (jax.random.normal(ku, (E, D, F), jnp.float32)
                             * std_in).astype(self.param_dtype)},
                "down": {"w": (jax.random.normal(kd, (E, F, D), jnp.float32)
                               * std_out).astype(self.param_dtype)},
            }
        return {
            "router": {"w": (jax.random.normal(kr, (D, E), jnp.float32)
                             / math.sqrt(D)).astype(jnp.float32)},
            "experts": experts,
        }

    def _capacity(self, tokens_per_group: int) -> int:
        c = math.ceil(tokens_per_group * self.top_k * self.capacity_factor
                      / self.num_experts)
        return max(8, -(-c // 8) * 8)   # round up to a multiple of 8

    def __call__(self, params: dict, x: jax.Array,
                 shard: Shard = no_shard) -> tuple[jax.Array, dict]:
        """x: [G, Ts, D] (groups = data shards upstream). Returns (y, aux)."""
        G, Ts, D = x.shape
        E, K = self.num_experts, self.top_k
        C = self._capacity(Ts)
        cd = self.compute_dtype

        logits = jnp.einsum("gtd,de->gte", x.astype(jnp.float32),
                            params["router"]["w"])            # [G, Ts, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, K)                 # [G, Ts, K]
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

        # Load-balance aux loss (Switch-style) + router z-loss.
        me = jnp.mean(probs, axis=(0, 1))                                # [E]
        ce = jnp.mean((jax.nn.one_hot(eidx, E).sum(2) > 0).astype(jnp.float32),
                      axis=(0, 1))
        aux = {
            "lb_loss": E * jnp.sum(me * ce),
            "z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        }

        def route_group(xg, eg, gg):
            # xg: [Ts, D], eg/gg: [Ts, K]
            flat_e = eg.reshape(-1)                           # [Ts*K]
            flat_g = gg.reshape(-1)
            tok = jnp.arange(Ts * K) // K
            onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
            pos = jnp.cumsum(onehot, axis=0) - 1              # [Ts*K, E]
            pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
            keep = pos < C
            # slot tables [E, C]
            slot_tok = jnp.zeros((E, C), jnp.int32).at[flat_e, pos].set(
                jnp.where(keep, tok, 0), mode="drop")
            slot_gate = jnp.zeros((E, C), jnp.float32).at[flat_e, pos].set(
                jnp.where(keep, flat_g, 0.0), mode="drop")
            xe = jnp.take(xg, slot_tok, axis=0)               # [E, C, D]
            return xe, slot_tok, slot_gate

        xe, slot_tok, slot_gate = jax.vmap(route_group)(x, eidx, gates)
        # dispatch layout has its own logical axes: training keeps groups on
        # the batch shards; serving replicates the (tiny) token groups and
        # aligns the expert axis with wherever the expert weights live.
        xe = shard(xe, ("moe_groups", "experts", None, None))  # [G, E, C, D]

        # Expert FFN (einsum over stacked weights, or TNN cores via vmap).
        tnn_on = self.tnn is not None and self.tnn.enabled and (
            "mlp" in self.tnn.targets)
        if tnn_on:
            gate_l = make_tensorized_linear(self.d_ff, D, self.tnn,
                                            param_dtype=self.param_dtype,
                                            compute_dtype=cd)
            down_l = make_tensorized_linear(D, self.d_ff, self.tnn,
                                            param_dtype=self.param_dtype,
                                            compute_dtype=cd)
            def expert_ffn(p_gate, p_up, p_down, xe_e):       # xe_e: [C, D]
                g = gate_l(p_gate, xe_e)
                u = gate_l(p_up, xe_e)
                h = jax.nn.silu(g.astype(jnp.float32)).astype(cd) * u.astype(cd)
                return down_l(p_down, h)
            ye = jax.vmap(jax.vmap(expert_ffn, in_axes=(0, 0, 0, 0)),
                          in_axes=(None, None, None, 0))(
                params["experts"]["gate"], params["experts"]["up"],
                params["experts"]["down"], xe.astype(cd))
        else:
            w = params["experts"]
            g = einsum_f32("gecd,edf->gecf", xe.astype(cd),
                           w["gate"]["w"].astype(cd))
            u = einsum_f32("gecd,edf->gecf", xe.astype(cd),
                           w["up"]["w"].astype(cd))
            h = (jax.nn.silu(g) * u).astype(cd)
            ye = einsum_f32("gecf,efd->gecd", h, w["down"]["w"].astype(cd))
        ye = ye.astype(x.dtype)                               # [G, E, C, D]

        def combine_group(ye_g, slot_tok_g, slot_gate_g):
            weighted = ye_g * slot_gate_g[..., None].astype(ye_g.dtype)
            return jnp.zeros((Ts, D), ye_g.dtype).at[
                slot_tok_g.reshape(-1)].add(weighted.reshape(-1, D))

        y = jax.vmap(combine_group)(ye, slot_tok, slot_gate)
        return y, aux
