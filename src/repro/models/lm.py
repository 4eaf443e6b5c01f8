"""Unified decoder-only language model covering the assigned architectures.

One `LM` class instantiates dense-attention (tinyllama/qwen2/phi4/internlm2/
llava backbone), MoE (qwen3-moe, olmoe), attention-free (rwkv6), and hybrid
(zamba2: Mamba-2 backbone + a parameter-shared attention block every k
layers; granite-4.0-h: Mamba-2 and attention layers in one stack by a
published pattern, each with its own weights and MLP) families from an
:class:`LMConfig`.

Structure notes:
* Homogeneous layer stacks are ``lax.scan``-ned over stacked params (HLO is
  O(1 layer) — the 94-layer MoE compiles in minutes on the dry-run host),
  with optional ``jax.checkpoint`` per layer (activation remat).  A mixed
  stack (``mixer_period``) stores each run of consecutive same-kind layers
  as its own stack, in the published order, and scans each run.
* Inputs are token ids (``int``) or precomputed embeddings (``float`` —
  the VLM/audio modality-frontend stubs feed these).
* Three execution paths: ``__call__`` (teacher-forced training),
  ``prefill`` (chunked-kernel prompt ingestion returning decode state),
  ``decode_step`` (one token).
* The paper's technique enters through ``cfg.tnn`` — every projection
  consults it (see ``blocks.Dense``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tensorized import TNNConfig
from repro.models import ssm
from repro.models.blocks import (
    Attention, Dense, KVCache, MoE, Shard, SwiGLU, einsum_f32, no_shard,
    rmsnorm, rmsnorm_init,
)


@dataclasses.dataclass(frozen=True)
class MoESpec:
    num_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    """Zamba2-style: shared attention block applied every `shared_every`
    backbone layers (same weights each application)."""
    shared_every: int = 27
    d_ff_shared: int | None = None


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None            # default d_model // num_heads
    block: str = "attn"                    # attn | rwkv6 | mamba2
    moe: MoESpec | None = None
    hybrid: HybridSpec | None = None
    ssm_state: int = 64
    ssm_chunk: int = 128                   # Mamba-2 SSD chunk
    mixer_period: int = 0                  # > 0 (block mamba2): attention at
    mixer_offset: int = 0                  #   i % mixer_period == mixer_offset
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    position_embedding: str = "rope"       # rope | nope
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # Granite's scalings; the defaults are the identity
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None   # softmax scale; None: 1/sqrt(hd)
    logits_scaling: float = 1.0
    tnn: TNNConfig = TNNConfig()
    q_chunk: int = 512
    kv_chunk: int = 1024
    remat: bool = True
    remat_group: int = 1       # layers rematted together: stash shrinks by
                               # this factor at +((g-1)/g) fwd recompute
    scan_layers: bool = True
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def layer_types(self) -> tuple[str, ...]:
        """Each layer's mixer, "attention" or "mamba", in order."""
        if not self.mixer_period:
            return (("mamba",) if self.block == "mamba2"
                    else ("attention",)) * self.num_layers
        return tuple("attention" if i % self.mixer_period == self.mixer_offset
                     else "mamba" for i in range(self.num_layers))

    @property
    def runs(self) -> tuple[tuple[str, int], ...]:
        """The layer types as runs of one kind: ``(kind, count)``."""
        out: list[list] = []
        for kind in self.layer_types:
            if out and out[-1][0] == kind:
                out[-1][1] += 1
            else:
                out.append([kind, 1])
        return tuple((k, n) for k, n in out)

    def validate(self):
        assert self.block in ("attn", "rwkv6", "mamba2")
        assert self.position_embedding in ("rope", "nope")
        if self.mixer_period:
            assert self.block == "mamba2" and not self.hybrid and not self.moe
        if self.hybrid:
            assert self.block == "mamba2", "hybrid = mamba2 backbone"
            assert self.num_layers % self.hybrid.shared_every == 0, (
                f"{self.num_layers} layers not divisible by shared_every="
                f"{self.hybrid.shared_every}")


class DecodeCache(NamedTuple):
    """Per-model decode state: stacked per-layer caches + global position."""
    layers: Any           # stacked KVCache / RWKVState / MambaState pytree
    shared: Any           # hybrid only: stacked KVCache per shared-block app
    length: jax.Array     # [] int32


def _shift(z):
    return jnp.pad(z, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _unrolled_scan(step, x, xs, n):
    """Python-unrolled lax.scan twin (used by the dry-run cost probes —
    cost_analysis counts while bodies once, so probes compile unrolled)."""
    ys = []
    for i in range(n):
        sl = jax.tree.map(lambda p: p[i], xs)
        x, y = step(x, sl)
        ys.append(y)
    return x, jax.tree.map(lambda *a: jnp.stack(a), *ys)


def _maybe_scan(step, x, xs, use_scan, n):
    if use_scan:
        return jax.lax.scan(step, x, xs)
    return _unrolled_scan(step, x, xs, n)


def _layer_loop(step, carry, layers, use_scan, n):
    """``carry = step(carry, (layer_params, i))`` over the n layers, with
    no per-layer outputs: state such as the stacked KV cache rides in the
    carry and is updated in place.  ``i`` is static when unrolled."""
    carry, _ = _maybe_scan(lambda c, a: (step(c, a), None), carry,
                           (layers, np.arange(n, dtype=np.int32)), use_scan,
                           n)
    return carry


# ---------------------------------------------------------------------------


class LM:
    def __init__(self, cfg: LMConfig):
        cfg.validate()
        self.cfg = cfg
        c = cfg
        common = dict(param_dtype=c.param_dtype, compute_dtype=c.compute_dtype)
        tnn = c.tnn if c.tnn.enabled else None
        if c.block == "attn":
            self.attn = Attention(c.d_model, c.num_heads, c.num_kv_heads,
                                  c.hd, qkv_bias=c.qkv_bias,
                                  rope_theta=c.rope_theta, q_chunk=c.q_chunk,
                                  kv_chunk=c.kv_chunk, tnn=tnn, **common)
            if c.moe:
                self.mlp = MoE(c.d_model, c.moe.d_ff_expert, c.moe.num_experts,
                               c.moe.top_k, c.moe.capacity_factor, tnn=tnn,
                               **common)
            else:
                self.mlp = SwiGLU(c.d_model, c.d_ff, tnn=tnn, **common)
        elif c.block == "rwkv6":
            self.rwkv = ssm.RWKV6Block(c.d_model, head_dim=c.hd, d_ff=c.d_ff,
                                       tnn=tnn, **common)
        elif c.block == "mamba2":
            self.mamba = ssm.Mamba2Block(
                c.d_model, d_state=c.ssm_state,
                head_dim=c.hd, chunk=c.ssm_chunk,
                norm_eps=c.norm_eps, tnn=tnn, **common)
            if c.mixer_period:
                self.attn = Attention(
                    c.d_model, c.num_heads, c.num_kv_heads, c.hd,
                    qkv_bias=c.qkv_bias, rope_theta=c.rope_theta,
                    use_rope=c.position_embedding == "rope",
                    softmax_scale=c.attention_multiplier, q_chunk=c.q_chunk,
                    kv_chunk=c.kv_chunk, tnn=tnn, **common)
                self.mlp = SwiGLU(c.d_model, c.d_ff, tnn=tnn, **common)
            if c.hybrid:
                self.shared_attn = Attention(
                    c.d_model, c.num_heads, c.num_kv_heads, c.hd,
                    rope_theta=c.rope_theta, q_chunk=c.q_chunk,
                    kv_chunk=c.kv_chunk, tnn=tnn, **common)
                self.shared_mlp = SwiGLU(
                    c.d_model, c.hybrid.d_ff_shared or c.d_ff, tnn=tnn,
                    **common)

    # -- init -----------------------------------------------------------------

    def _mixed_layer_init(self, kind: str, key: jax.Array) -> dict:
        c = self.cfg
        k1, k2 = jax.random.split(key)
        mixer = (("attn", self.attn) if kind == "attention"
                 else ("mamba", self.mamba))
        return {"ln1": rmsnorm_init(c.d_model),
                mixer[0]: mixer[1].init(k1),
                "ln2": rmsnorm_init(c.d_model),
                "mlp": self.mlp.init(k2)}

    def _layer_init(self, key: jax.Array) -> dict:
        c = self.cfg
        if c.block == "attn":
            k1, k2 = jax.random.split(key)
            return {"ln1": rmsnorm_init(c.d_model),
                    "attn": self.attn.init(k1),
                    "ln2": rmsnorm_init(c.d_model),
                    "mlp": self.mlp.init(k2)}
        if c.block == "rwkv6":
            return {"ln1": rmsnorm_init(c.d_model),
                    "ln2": rmsnorm_init(c.d_model),
                    "rwkv": self.rwkv.init(key)}
        return {"ln": rmsnorm_init(c.d_model),
                "mamba": self.mamba.init(key)}

    def init(self, key: jax.Array) -> dict:
        c = self.cfg
        ke, kl, kh, ko = jax.random.split(key, 4)
        std = 1.0 / math.sqrt(c.d_model)
        if c.mixer_period:
            keys = jax.random.split(kl, len(c.runs))
            layers = tuple(
                jax.vmap(partial(self._mixed_layer_init, kind))(
                    jax.random.split(k, n))
                for (kind, n), k in zip(c.runs, keys))
        else:
            layers = jax.vmap(self._layer_init)(
                jax.random.split(kl, c.num_layers))
        params = {
            "embed": (jax.random.normal(ke, (c.vocab, c.d_model), jnp.float32)
                      * std).astype(c.param_dtype),
            "ln_f": rmsnorm_init(c.d_model),
            "layers": layers,
        }
        if not c.tie_embeddings:
            params["lm_head"] = Dense(
                c.d_model, c.vocab, param_dtype=c.param_dtype,
                compute_dtype=c.compute_dtype).init(ko)
        if c.hybrid:
            k1, k2 = jax.random.split(kh)
            params["shared"] = {"ln1": rmsnorm_init(c.d_model),
                                "attn": self.shared_attn.init(k1),
                                "ln2": rmsnorm_init(c.d_model),
                                "mlp": self.shared_mlp.init(k2)}
        return params

    def param_count(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    # -- pieces ---------------------------------------------------------------

    def _embed(self, params, inputs, shard: Shard):
        c = self.cfg
        if jnp.issubdtype(inputs.dtype, jnp.integer):
            table = params["embed"].astype(c.compute_dtype)
            x = jnp.take(table, inputs, axis=0)
        else:
            x = inputs.astype(c.compute_dtype)   # modality stub embeddings
        if c.embedding_multiplier != 1.0:
            x = x * c.embedding_multiplier
        return shard(x, ("batch", "seq", None))

    def _logits(self, params, x):
        c = self.cfg
        with jax.named_scope("lm_head"):
            if c.tie_embeddings:
                w = params["embed"].astype(c.compute_dtype)
                y = einsum_f32("btd,vd->btv", x, w)
                if c.logits_scaling != 1.0:
                    y = y / c.logits_scaling
                return y.astype(c.compute_dtype)
            y = Dense(c.d_model, c.vocab, param_dtype=c.param_dtype,
                      compute_dtype=c.compute_dtype)(params["lm_head"], x)
            return y / c.logits_scaling if c.logits_scaling != 1.0 else y

    def _moe_apply(self, lp_mlp, y, shard):
        """Group tokens by batch row (groups shard over `data`)."""
        c = self.cfg
        B, T, D = y.shape
        ym, aux = self.mlp(lp_mlp, y.reshape(B, T, D), shard)
        return ym.reshape(y.shape), aux

    # -- per-layer functions (train / prefill / decode) ------------------------

    def _attn_layer(self, lp, x, positions, shard):
        c = self.cfg
        h = self.attn(lp["attn"], rmsnorm(lp["ln1"], x, c.norm_eps),
                      positions, shard)
        x = x + h
        y = rmsnorm(lp["ln2"], x, c.norm_eps)
        if c.moe:
            ym, aux = self._moe_apply(lp["mlp"], y, shard)
        else:
            ym, aux = self.mlp(lp["mlp"], y, shard), {}
        x = shard(x + ym, ("batch", "seq", None))
        return x, aux

    def _rwkv_layer(self, lp, x, shard, want_state: bool = False):
        c = self.cfg
        xn1 = rmsnorm(lp["ln1"], x, c.norm_eps)
        tm, wkv = self.rwkv.time_mix(lp["rwkv"], xn1, shard)
        x = x + tm
        xn2 = rmsnorm(lp["ln2"], x, c.norm_eps)
        x = x + self.rwkv.channel_mix(lp["rwkv"], xn2, _shift(xn2))
        if want_state:
            state = ssm.RWKVState(
                wkv=wkv,
                shift_tm=xn1[:, -1].astype(c.compute_dtype),
                shift_cm=xn2[:, -1].astype(c.compute_dtype))
            return x, state
        return x, {}

    def _mamba_layer(self, lp, x, shard, want_state: bool = False):
        c = self.cfg
        xn = rmsnorm(lp["ln"], x, c.norm_eps)
        if want_state:
            h, state = self.mamba(lp["mamba"], xn, shard, return_state=True)
            return x + h, state
        return x + self.mamba(lp["mamba"], xn, shard), {}

    def _shared_block(self, sp, x, positions, shard):
        c = self.cfg
        x = x + self.shared_attn(sp["attn"], rmsnorm(sp["ln1"], x, c.norm_eps),
                                 positions, shard)
        x = x + self.shared_mlp(sp["mlp"], rmsnorm(sp["ln2"], x, c.norm_eps),
                                shard)
        return x

    def _residual(self, h):
        m = self.cfg.residual_multiplier
        return h * m if m != 1.0 else h

    def _mixed_layer(self, lp, x, positions, shard, max_len=None):
        """One layer of a mixed stack: its mixer (attention or Mamba-2, by
        the key its weights are under), then its own MLP, each added on a
        scaled residual.  With ``max_len`` (prefill) the second value is
        the layer's decode state: its KV cache or its Mamba-2 state."""
        c = self.cfg
        h = rmsnorm(lp["ln1"], x, c.norm_eps)
        state = {}
        if "attn" in lp and max_len is None:
            m = self.attn(lp["attn"], h, positions, shard)
        elif "attn" in lp:
            m, state = self.attn.prefill(lp["attn"], h, positions, max_len,
                                         shard)
        elif max_len is None:
            m = self.mamba(lp["mamba"], h, shard)
        else:
            m, state = self.mamba(lp["mamba"], h, shard, return_state=True)
        x = x + self._residual(m)
        y = self.mlp(lp["mlp"], rmsnorm(lp["ln2"], x, c.norm_eps), shard)
        return shard(x + self._residual(y), ("batch", "seq", None)), state

    def _mixed_decode(self, lp, x, state, pos, shard):
        """One token through one layer of a mixed stack; ``state`` is the
        layer's KV cache (its length is bookkeeping, ``pos`` is read) or
        its Mamba-2 state."""
        c = self.cfg
        h = rmsnorm(lp["ln1"], x, c.norm_eps)
        if "attn" in lp:
            m, kv = self.attn.decode_step(
                lp["attn"], h, KVCache(state.k, state.v, pos), shard)
            state = KVCache(kv.k, kv.v, state.length + 1)
        else:
            m, state = self.mamba.decode_step(lp["mamba"], h, state)
        x = x + self._residual(m)
        y = self.mlp(lp["mlp"], rmsnorm(lp["ln2"], x, c.norm_eps), shard)
        return x + self._residual(y), state

    def _scan_runs(self, step, x, layers, *per_run):
        """``step`` scanned over each run of a mixed stack in order, with
        each run's entry of ``per_run`` scanned beside its weights;
        returns x and the runs' stacked outputs."""
        outs = []
        for run, *extra in zip(layers, *per_run):
            n = jax.tree.leaves(run)[0].shape[0]
            x, y = _maybe_scan(step, x, (run, *extra), self.cfg.scan_layers,
                               n)
            outs.append(y)
        return x, tuple(outs)

    # -- full-sequence forward (training) --------------------------------------

    def apply_layers(self, layers: dict, x: jax.Array, positions: jax.Array,
                     shard: Shard = no_shard) -> tuple[jax.Array, dict]:
        """Run a contiguous slice of the homogeneous layer stack.

        ``layers`` is a stacked ``[L', ...]`` pytree — the full
        ``params["layers"]`` in :meth:`__call__`, or a stage's slice of it
        under pipeline parallelism (``repro.distributed.pipeline``).  The
        scan/remat/remat-group lowering is identical either way, so a
        partitioned stack computes the same per-layer values as the
        monolithic forward.  Hybrid (shared-block) stacks interleave
        non-stack params and stay in :meth:`__call__`; a mixed stack (a
        tuple of runs) runs run by run.
        """
        c = self.cfg
        if c.mixer_period and isinstance(layers, tuple):
            for run in layers:
                x, _ = self.apply_layers(run, x, positions, shard)
            return x, {}
        n = jax.tree.leaves(layers)[0].shape[0]

        def layer_fn(x, lp):
            if c.mixer_period:
                return self._mixed_layer(lp, x, positions, shard)
            if c.block == "attn":
                return self._attn_layer(lp, x, positions, shard)
            if c.block == "rwkv6":
                return self._rwkv_layer(lp, x, shard)
            return self._mamba_layer(lp, x, shard)

        if c.remat:
            layer_fn = jax.checkpoint(
                layer_fn, policy=jax.checkpoint_policies.nothing_saveable)

        if c.scan_layers:
            g = max(1, c.remat_group)
            if g > 1 and n % g == 0:
                def group_fn(x, gp):
                    aux = None
                    for li in range(g):
                        lp = jax.tree.map(lambda p: p[li], gp)
                        x, aux = layer_fn(x, lp)
                    return x, aux
                if c.remat:
                    group_fn = jax.checkpoint(
                        group_fn,
                        policy=jax.checkpoint_policies.nothing_saveable)
                grouped = jax.tree.map(
                    lambda p: p.reshape((n // g, g) + p.shape[1:]),
                    layers)
                x, aux = jax.lax.scan(group_fn, x, grouped)
            else:
                x, aux = jax.lax.scan(layer_fn, x, layers)
        else:
            auxes = []
            for li in range(n):
                lp = jax.tree.map(lambda p: p[li], layers)
                x, a = layer_fn(x, lp)
                auxes.append(a)
            aux = (jax.tree.map(lambda *a: jnp.stack(a), *auxes)
                   if auxes and auxes[0] else {})
        return x, aux

    def __call__(self, params: dict, inputs: jax.Array,
                 shard: Shard = no_shard) -> tuple[jax.Array, dict]:
        """inputs: [B, T] ids or [B, T, D] embeds -> (logits [B,T,V], aux)."""
        c = self.cfg
        B, T = inputs.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x = self._embed(params, inputs, shard)

        if c.hybrid:
            def layer_fn(x, lp):
                return self._mamba_layer(lp, x, shard)
            if c.remat:
                layer_fn = jax.checkpoint(
                    layer_fn, policy=jax.checkpoint_policies.nothing_saveable)
            g = c.hybrid.shared_every
            n_groups = c.num_layers // g
            grouped = jax.tree.map(
                lambda p: p.reshape((n_groups, g) + p.shape[1:]),
                params["layers"])
            for gi in range(n_groups):
                gp = jax.tree.map(lambda p: p[gi], grouped)
                x, _ = jax.lax.scan(layer_fn, x, gp)
                x = self._shared_block(params["shared"], x, positions, shard)
            aux = {}
        else:
            x, aux = self.apply_layers(params["layers"], x, positions, shard)

        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        logits = self._logits(params, x)
        return shard(logits, ("batch", "seq", "vocab")), aux

    # -- loss -------------------------------------------------------------------

    def token_loss(self, logits: jax.Array, batch: dict
                   ) -> tuple[jax.Array, dict]:
        """Masked next-token NLL from precomputed logits — the reduction
        half of :meth:`loss`, reused by the pipeline's last stage so staged
        and monolithic execution share one loss definition."""
        targets = batch["targets"]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(targets.shape, jnp.float32)
        with jax.named_scope("lm_head"):
            lf = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(lf, axis=-1)
            # gold logit via masked reduction, not take_along_axis: a gather
            # along the vocab axis would force an all-gather of the
            # vocab-sharded logits; the where+sum stays shard-local and
            # reduces with a tiny all-reduce.
            vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape,
                                                  lf.ndim - 1)
            gold = jnp.sum(jnp.where(vocab_iota == targets[..., None], lf,
                                     0.0), axis=-1)
            nll = (lse - gold) * mask
            denom = jnp.maximum(jnp.sum(mask), 1.0)
            loss = jnp.sum(nll) / denom
        return loss, {"nll": loss, "tokens": jnp.sum(mask)}

    def loss(self, params: dict, batch: dict, shard: Shard = no_shard
             ) -> tuple[jax.Array, dict]:
        """batch: {"inputs": [B,T] or [B,T,D], "targets": [B,T], "mask": [B,T]}"""
        logits, aux = self(params, batch["inputs"], shard)
        loss, metrics = self.token_loss(logits, batch)
        if aux and "lb_loss" in aux:
            lb = jnp.mean(aux["lb_loss"])
            zl = jnp.mean(aux["z_loss"])
            loss = loss + 0.01 * lb + 1e-3 * zl
            metrics.update(lb_loss=lb, z_loss=zl)
        return loss, metrics

    # -- caches -------------------------------------------------------------------

    def init_cache(self, batch: int, max_len: int) -> DecodeCache:
        c = self.cfg
        L = c.num_layers

        def stack(state, n=L):
            return jax.tree.map(
                lambda s: jnp.zeros((n,) + s.shape, s.dtype), state)

        shared = None
        if c.mixer_period:
            def run_cache(kind, n):
                if kind == "attention":
                    shape = (n, batch, max_len, c.num_kv_heads, c.hd)
                    return KVCache(k=jnp.zeros(shape, c.compute_dtype),
                                   v=jnp.zeros(shape, c.compute_dtype),
                                   length=jnp.zeros((n,), jnp.int32))
                return stack(self.mamba.init_state(batch), n)
            layers = tuple(run_cache(kind, n) for kind, n in c.runs)
        elif c.block == "attn":
            layers = KVCache(
                k=jnp.zeros((L, batch, max_len, c.num_kv_heads, c.hd),
                            c.compute_dtype),
                v=jnp.zeros((L, batch, max_len, c.num_kv_heads, c.hd),
                            c.compute_dtype),
                length=jnp.zeros((L,), jnp.int32))
        elif c.block == "rwkv6":
            layers = stack(self.rwkv.init_state(batch))
        else:
            layers = stack(self.mamba.init_state(batch))
            if c.hybrid:
                n_groups = c.num_layers // c.hybrid.shared_every
                shared = KVCache(
                    k=jnp.zeros((n_groups, batch, max_len, c.num_kv_heads,
                                 c.hd), c.compute_dtype),
                    v=jnp.zeros((n_groups, batch, max_len, c.num_kv_heads,
                                 c.hd), c.compute_dtype),
                    length=jnp.zeros((n_groups,), jnp.int32))
        return DecodeCache(layers=layers, shared=shared,
                           length=jnp.array(0, jnp.int32))

    # -- decode -------------------------------------------------------------------

    def decode_step(self, params: dict, token: jax.Array, cache: DecodeCache,
                    shard: Shard = no_shard, valid: jax.Array | None = None
                    ) -> tuple[jax.Array, DecodeCache]:
        """token: [B] ids (or [B, D] embeds) -> (logits [B, V], new cache).

        ``valid`` ([B] int32 of 0 or 1, None = every slot; attention blocks
        only) freezes the slots with 0 inside each layer's KV write, as
        :meth:`Attention.decode_step` says: their k/v and length come back
        as they went in.  An attention stack carries its stacked K/V
        through the layer loop and writes only the new rows, so a caller
        that donates the cache has it updated in place."""
        c = self.cfg
        if valid is not None and c.block != "attn":
            raise ValueError("decode_step(valid=...) requires an "
                             "attention-block model")
        B = token.shape[0]
        inputs = token[:, None] if token.ndim == 1 else token[:, None, :]
        x = self._embed(params, inputs, shard)
        pos = cache.length
        new_shared = None

        if c.mixer_period:
            def step(x, scan_in):
                lp, st = scan_in
                return self._mixed_decode(lp, x, st, pos, shard)
            x, new_layers = self._scan_runs(step, x, params["layers"],
                                            cache.layers)
        elif c.block == "attn":
            def step(carry, scan_in):
                x, k, v = carry
                lp, i = scan_in
                h, new_kv = self.attn.decode_step(
                    lp["attn"], rmsnorm(lp["ln1"], x, c.norm_eps),
                    KVCache(k, v, pos), shard, valid=valid, layer=i)
                x = x + h
                y = rmsnorm(lp["ln2"], x, c.norm_eps)
                if c.moe:
                    ym, _ = self._moe_apply(lp["mlp"], y, shard)
                else:
                    ym = self.mlp(lp["mlp"], y, shard)
                return x + ym, new_kv.k, new_kv.v
            x, ks, vs = _layer_loop(
                step, (x, cache.layers.k, cache.layers.v), params["layers"],
                c.scan_layers, c.num_layers)
            new_layers = KVCache(ks, vs, cache.layers.length + 1)
        elif c.block == "rwkv6":
            def step(x, scan_in):
                lp, st = scan_in
                tm, new_wkv, new_sh_tm = self.rwkv.time_mix_step(
                    lp["rwkv"], rmsnorm(lp["ln1"], x, c.norm_eps),
                    st.wkv, st.shift_tm)
                x = x + tm
                cm, new_sh_cm = self.rwkv.channel_mix_step(
                    lp["rwkv"], rmsnorm(lp["ln2"], x, c.norm_eps), st.shift_cm)
                return x + cm, ssm.RWKVState(new_wkv, new_sh_tm, new_sh_cm)
            if c.scan_layers:
                x, new_layers = jax.lax.scan(step, x, (params["layers"],
                                                       cache.layers))
            else:
                x, new_layers = _unrolled_scan(step, x, (params["layers"],
                                                         cache.layers),
                                               c.num_layers)
        else:
            def step(x, scan_in):
                lp, st = scan_in
                h, new_st = self.mamba.decode_step(
                    lp["mamba"], rmsnorm(lp["ln"], x, c.norm_eps), st)
                return x + h, new_st

            if c.hybrid:
                g = c.hybrid.shared_every
                n_groups = c.num_layers // g
                grouped = jax.tree.map(
                    lambda p: p.reshape((n_groups, g) + p.shape[1:]),
                    params["layers"])
                new_layer_states, new_shared_list = [], []
                for gi in range(n_groups):
                    gp = jax.tree.map(lambda p: p[gi], grouped)
                    gs = jax.tree.map(lambda s: s[gi * g:(gi + 1) * g],
                                      cache.layers)
                    x, ns = jax.lax.scan(step, x, (gp, gs))
                    new_layer_states.append(ns)
                    kv = jax.tree.map(lambda s: s[gi], cache.shared)
                    lkv = KVCache(kv.k, kv.v, pos)
                    h, new_kv = self.shared_attn.decode_step(
                        params["shared"]["attn"],
                        rmsnorm(params["shared"]["ln1"], x, c.norm_eps),
                        lkv, shard)
                    x = x + h
                    x = x + self.shared_mlp(
                        params["shared"]["mlp"],
                        rmsnorm(params["shared"]["ln2"], x, c.norm_eps), shard)
                    new_shared_list.append((new_kv.k, new_kv.v))
                new_layers = jax.tree.map(
                    lambda *s: jnp.concatenate(s), *new_layer_states)
                new_shared = KVCache(
                    k=jnp.stack([k for k, _ in new_shared_list]),
                    v=jnp.stack([v for _, v in new_shared_list]),
                    length=cache.shared.length + 1)
            else:
                x, new_layers = jax.lax.scan(step, x, (params["layers"],
                                                       cache.layers))

        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        logits = self._logits(params, x)[:, 0]
        return logits, DecodeCache(layers=new_layers, shared=new_shared,
                                   length=cache.length + (1 if valid is None
                                                          else valid))

    # -- chunked prefill (serving) --------------------------------------------

    def extend(self, params: dict, tokens: jax.Array, cache: DecodeCache,
               shard: Shard = no_shard, valid: jax.Array | None = None
               ) -> tuple[jax.Array, DecodeCache]:
        """Ingest a ``[B, C]`` token chunk at each slot's current cache
        depth — the serving engine's chunked-prefill tick (attention
        blocks only; SSM blocks go through the engine's sequential
        decode_step fallback).

        ``cache.length`` may be per-slot ([B]); ``valid`` ([B] int32,
        None = all C) bounds how many chunk tokens are real per slot (see
        :meth:`Attention.extend` for the masked-write contract).  Returns
        logits for every chunk position ([B, C, V] — the engine reads row
        ``valid-1`` of slots whose prompt just completed) plus the
        advanced cache."""
        c = self.cfg
        assert c.block == "attn" and not c.hybrid, (
            "extend() requires an attention-block model")
        B, C = tokens.shape[:2]
        x = self._embed(params, tokens, shard)
        pos = cache.length

        def step(carry, scan_in):
            x, k, v = carry
            lp, i = scan_in
            h, new_kv = self.attn.extend(
                lp["attn"], rmsnorm(lp["ln1"], x, c.norm_eps),
                KVCache(k, v, pos), shard, valid=valid, layer=i)
            x = x + h
            y = rmsnorm(lp["ln2"], x, c.norm_eps)
            if c.moe:
                ym, _ = self._moe_apply(lp["mlp"], y, shard)
            else:
                ym = self.mlp(lp["mlp"], y, shard)
            return x + ym, new_kv.k, new_kv.v

        x, ks, vs = _layer_loop(step, (x, cache.layers.k, cache.layers.v),
                                params["layers"], c.scan_layers, c.num_layers)
        # Per-layer lengths are bookkeeping only (decode/extend read the
        # global cache.length); advance by the chunk width.
        new_layers = KVCache(ks, vs, cache.layers.length + C)
        adv = C if valid is None else valid
        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        logits = self._logits(params, x)                      # [B, C, V]
        return logits, DecodeCache(layers=new_layers, shared=None,
                                   length=cache.length + adv)

    # -- prefill --------------------------------------------------------------

    def prefill(self, params: dict, inputs: jax.Array, max_len: int,
                shard: Shard = no_shard) -> tuple[jax.Array, DecodeCache]:
        """Ingest the prompt with full-sequence (chunked-kernel) compute and
        return (last-position logits, decode cache)."""
        c = self.cfg
        B, T = inputs.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x = self._embed(params, inputs, shard)
        new_shared = None

        if c.mixer_period:
            def step(x, scan_in):
                return self._mixed_layer(scan_in[0], x, positions, shard,
                                         max_len=max_len)
            x, new_layers = self._scan_runs(step, x, params["layers"])
        elif c.block == "attn":
            def step(x, lp):
                h, kv = self.attn.prefill(
                    lp["attn"], rmsnorm(lp["ln1"], x, c.norm_eps), positions,
                    max_len, shard)
                x = x + h
                y = rmsnorm(lp["ln2"], x, c.norm_eps)
                if c.moe:
                    ym, _ = self._moe_apply(lp["mlp"], y, shard)
                else:
                    ym = self.mlp(lp["mlp"], y, shard)
                return x + ym, (kv.k, kv.v)
            x, (ks, vs) = _maybe_scan(step, x, params["layers"],
                                      c.scan_layers, c.num_layers)
            new_layers = KVCache(ks, vs, jnp.full((c.num_layers,), T, jnp.int32))
        elif c.block == "rwkv6":
            def step(x, lp):
                return self._rwkv_layer(lp, x, shard, want_state=True)
            x, new_layers = _maybe_scan(step, x, params["layers"],
                                        c.scan_layers, c.num_layers)
        else:
            def step(x, lp):
                return self._mamba_layer(lp, x, shard, want_state=True)
            if c.hybrid:
                g = c.hybrid.shared_every
                n_groups = c.num_layers // g
                grouped = jax.tree.map(
                    lambda p: p.reshape((n_groups, g) + p.shape[1:]),
                    params["layers"])
                states, shared_kvs = [], []
                for gi in range(n_groups):
                    gp = jax.tree.map(lambda p: p[gi], grouped)
                    x, st = jax.lax.scan(step, x, gp)
                    states.append(st)
                    sp = params["shared"]
                    h, kv = self.shared_attn.prefill(
                        sp["attn"], rmsnorm(sp["ln1"], x, c.norm_eps),
                        positions, max_len, shard)
                    x = x + h
                    x = x + self.shared_mlp(
                        sp["mlp"], rmsnorm(sp["ln2"], x, c.norm_eps), shard)
                    shared_kvs.append((kv.k, kv.v))
                new_layers = jax.tree.map(lambda *s: jnp.concatenate(s),
                                          *states)
                new_shared = KVCache(
                    k=jnp.stack([k for k, _ in shared_kvs]),
                    v=jnp.stack([v for _, v in shared_kvs]),
                    length=jnp.full((n_groups,), T, jnp.int32))
            else:
                x, new_layers = _maybe_scan(step, x, params["layers"],
                                            c.scan_layers, c.num_layers)

        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        logits = self._logits(params, x[:, -1:])[:, 0]
        return logits, DecodeCache(layers=new_layers, shared=new_shared,
                                   length=jnp.array(T, jnp.int32))
