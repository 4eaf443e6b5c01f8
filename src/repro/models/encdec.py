"""Encoder-decoder transformer (seamless-m4t backbone).

The modality frontend (speech feature extractor) is a STUB per the
assignment: the encoder consumes precomputed frame embeddings
``[B, S_enc, d_model]`` (see ``repro.models.modality``).  The decoder is a
standard causal transformer with cross-attention into the encoder output.

Encoder layers are bidirectional (non-causal) self-attention; both stacks
scan over stacked params.  Cross-attention reuses the GQA projections with
keys/values from the encoder output (no RoPE on cross-attention, standard
enc-dec practice).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.tensorized import TNNConfig
from repro.models.blocks import (
    Attention, Dense, KVCache, Shard, SwiGLU, blockwise_attention, no_shard,
    rmsnorm, rmsnorm_init,
)


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    name: str
    num_enc_layers: int
    num_dec_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tnn: TNNConfig = TNNConfig()
    q_chunk: int = 512
    kv_chunk: int = 1024
    remat: bool = True
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads


class EncDecCache(NamedTuple):
    enc_out: jax.Array    # [B, S_enc, D] encoder output (frozen during decode)
    self_kv: KVCache      # stacked [L_dec, ...] decoder self-attn cache
    length: jax.Array


class EncDec:
    def __init__(self, cfg: EncDecConfig):
        self.cfg = cfg
        c = cfg
        common = dict(param_dtype=c.param_dtype, compute_dtype=c.compute_dtype)
        tnn = c.tnn if c.tnn.enabled else None
        mk_attn = lambda causal: Attention(  # noqa: E731
            c.d_model, c.num_heads, c.num_kv_heads, c.hd, causal=causal,
            rope_theta=c.rope_theta, q_chunk=c.q_chunk, kv_chunk=c.kv_chunk,
            tnn=tnn, **common)
        self.enc_attn = mk_attn(False)
        self.dec_attn = mk_attn(True)
        self.cross_attn = mk_attn(False)
        self.mlp = SwiGLU(c.d_model, c.d_ff, tnn=tnn, **common)

    # -- init -------------------------------------------------------------

    def _enc_layer_init(self, key):
        c = self.cfg
        k1, k2 = jax.random.split(key)
        return {"ln1": rmsnorm_init(c.d_model), "attn": self.enc_attn.init(k1),
                "ln2": rmsnorm_init(c.d_model), "mlp": self.mlp.init(k2)}

    def _dec_layer_init(self, key):
        c = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        return {"ln1": rmsnorm_init(c.d_model), "self": self.dec_attn.init(k1),
                "ln_x": rmsnorm_init(c.d_model), "cross": self.cross_attn.init(k2),
                "ln2": rmsnorm_init(c.d_model), "mlp": self.mlp.init(k3)}

    def init(self, key: jax.Array) -> dict:
        c = self.cfg
        ke, k1, k2, ko = jax.random.split(key, 4)
        std = 1.0 / math.sqrt(c.d_model)
        return {
            "embed": (jax.random.normal(ke, (c.vocab, c.d_model), jnp.float32)
                      * std).astype(c.param_dtype),
            "enc_layers": jax.vmap(self._enc_layer_init)(
                jax.random.split(k1, c.num_enc_layers)),
            "dec_layers": jax.vmap(self._dec_layer_init)(
                jax.random.split(k2, c.num_dec_layers)),
            "ln_enc": rmsnorm_init(c.d_model),
            "ln_f": rmsnorm_init(c.d_model),
            "lm_head": Dense(c.d_model, c.vocab, param_dtype=c.param_dtype,
                             compute_dtype=c.compute_dtype).init(ko),
        }

    # -- cross attention ----------------------------------------------------

    def _cross(self, params, x, enc_out, shard):
        """q from x [B,T,D]; k/v from enc_out [B,S,D]; no RoPE, full attn."""
        c = self.cfg
        B, T, _ = x.shape
        S = enc_out.shape[1]
        H, KV, D = c.num_heads, c.num_kv_heads, c.hd
        att = self.cross_attn
        q = att._proj(c.d_model, H * D, False, "qkv")(params["q"], x
                                                      ).reshape(B, T, H, D)
        k = att._proj(c.d_model, KV * D, False, "qkv")(params["k"], enc_out
                                                       ).reshape(B, S, KV, D)
        v = att._proj(c.d_model, KV * D, False, "qkv")(params["v"], enc_out
                                                       ).reshape(B, S, KV, D)
        ctx = blockwise_attention(q, k, v, causal=False,
                                  q_chunk=min(c.q_chunk, T),
                                  kv_chunk=min(c.kv_chunk, S))
        return att._proj(H * D, c.d_model, False, "out")(
            params["o"], ctx.reshape(B, T, H * D))

    # -- encoder ------------------------------------------------------------

    def encode(self, params: dict, enc_embeds: jax.Array,
               shard: Shard = no_shard) -> jax.Array:
        c = self.cfg
        B, S = enc_embeds.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = shard(enc_embeds.astype(c.compute_dtype), ("batch", "seq", None))

        def layer_fn(x, lp):
            h = self.enc_attn(lp["attn"], rmsnorm(lp["ln1"], x, c.norm_eps),
                              positions, shard)
            x = x + h
            x = x + self.mlp(lp["mlp"], rmsnorm(lp["ln2"], x, c.norm_eps),
                             shard)
            return x, None

        if c.remat:
            layer_fn = jax.checkpoint(
                layer_fn, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(layer_fn, x, params["enc_layers"])
        return rmsnorm(params["ln_enc"], x, c.norm_eps)

    # -- decoder (teacher-forced) --------------------------------------------

    def __call__(self, params: dict, enc_embeds: jax.Array,
                 dec_tokens: jax.Array, shard: Shard = no_shard
                 ) -> tuple[jax.Array, dict]:
        c = self.cfg
        enc_out = self.encode(params, enc_embeds, shard)
        B, T = dec_tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x = jnp.take(params["embed"].astype(c.compute_dtype), dec_tokens,
                     axis=0)
        x = shard(x, ("batch", "seq", None))

        def layer_fn(x, lp):
            x = x + self.dec_attn(lp["self"], rmsnorm(lp["ln1"], x, c.norm_eps),
                                  positions, shard)
            x = x + self._cross(lp["cross"], rmsnorm(lp["ln_x"], x, c.norm_eps),
                                enc_out, shard)
            x = x + self.mlp(lp["mlp"], rmsnorm(lp["ln2"], x, c.norm_eps),
                             shard)
            return x, None

        if c.remat:
            layer_fn = jax.checkpoint(
                layer_fn, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(layer_fn, x, params["dec_layers"])
        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        logits = Dense(c.d_model, c.vocab, param_dtype=c.param_dtype,
                       compute_dtype=c.compute_dtype)(params["lm_head"], x)
        return shard(logits, ("batch", "seq", "vocab")), {}

    def loss(self, params: dict, batch: dict, shard: Shard = no_shard):
        logits, _ = self(params, batch["enc_embeds"], batch["dec_inputs"],
                         shard)
        targets = batch["dec_targets"]
        mask = batch.get("mask", jnp.ones(targets.shape, jnp.float32))
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape,
                                              lf.ndim - 1)
        gold = jnp.sum(jnp.where(vocab_iota == targets[..., None], lf, 0.0),
                       axis=-1)
        loss = jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss, {"nll": loss}

    # -- serving ----------------------------------------------------------------

    def prefill(self, params: dict, enc_embeds: jax.Array,
                dec_tokens: jax.Array, max_len: int,
                shard: Shard = no_shard) -> tuple[jax.Array, EncDecCache]:
        c = self.cfg
        enc_out = self.encode(params, enc_embeds, shard)
        B, T = dec_tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x = jnp.take(params["embed"].astype(c.compute_dtype), dec_tokens,
                     axis=0)

        def step(x, lp):
            h, kv = self.dec_attn.prefill(
                lp["self"], rmsnorm(lp["ln1"], x, c.norm_eps), positions,
                max_len, shard)
            x = x + h
            x = x + self._cross(lp["cross"], rmsnorm(lp["ln_x"], x, c.norm_eps),
                                enc_out, shard)
            x = x + self.mlp(lp["mlp"], rmsnorm(lp["ln2"], x, c.norm_eps),
                             shard)
            return x, (kv.k, kv.v)

        x, (ks, vs) = jax.lax.scan(step, x, params["dec_layers"])
        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        logits = Dense(c.d_model, c.vocab, param_dtype=c.param_dtype,
                       compute_dtype=c.compute_dtype)(params["lm_head"],
                                                      x[:, -1:])[:, 0]
        cache = EncDecCache(
            enc_out=enc_out,
            self_kv=KVCache(ks, vs, jnp.full((c.num_dec_layers,), T,
                                             jnp.int32)),
            length=jnp.array(T, jnp.int32))
        return logits, cache

    def decode_step(self, params: dict, token: jax.Array, cache: EncDecCache,
                    shard: Shard = no_shard) -> tuple[jax.Array, EncDecCache]:
        c = self.cfg
        B = token.shape[0]
        x = jnp.take(params["embed"].astype(c.compute_dtype), token[:, None],
                     axis=0)
        pos = cache.length

        def step(x, scan_in):
            lp, kv = scan_in
            lkv = KVCache(kv.k, kv.v, pos)
            h, new_kv = self.dec_attn.decode_step(
                lp["self"], rmsnorm(lp["ln1"], x, c.norm_eps), lkv, shard)
            x = x + h
            x = x + self._cross(lp["cross"], rmsnorm(lp["ln_x"], x, c.norm_eps),
                                cache.enc_out, shard)
            x = x + self.mlp(lp["mlp"], rmsnorm(lp["ln2"], x, c.norm_eps),
                             shard)
            return x, (new_kv.k, new_kv.v)

        x, (ks, vs) = jax.lax.scan(step, x, (params["dec_layers"],
                                             cache.self_kv))
        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        logits = Dense(c.d_model, c.vocab, param_dtype=c.param_dtype,
                       compute_dtype=c.compute_dtype)(params["lm_head"], x)[:, 0]
        new_cache = EncDecCache(
            enc_out=cache.enc_out,
            self_kv=KVCache(ks, vs, cache.self_kv.length + 1),
            length=cache.length + 1)
        return logits, new_cache
