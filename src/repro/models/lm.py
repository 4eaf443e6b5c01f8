"""Unified decoder-only language model covering the assigned architectures.

One `LM` class instantiates dense-attention (tinyllama/qwen2/phi4/internlm2/
llava backbone), MoE (qwen3-moe, olmoe), attention-free (rwkv6), and hybrid
(zamba2: Mamba-2 backbone + a parameter-shared attention block every k
layers; granite-4.0-h: Mamba-2 and attention layers in one stack by a
published pattern, each with its own weights and MLP) families from an
:class:`LMConfig`.

Structure notes:
* A layer is one of three kinds: attention, Mamba-2 or RWKV-6.  Each kind
  has one body (``_attention``, ``_mamba``, ``_rwkv``) that serves every
  phase: norm → the mixer's call for the phase → residual, then, in layers
  that have one, norm → MLP (RWKV: its channel mix) → residual.  The phase
  (:class:`_Phase`) decides only which mixer method is called and what
  state comes back.
* One walker (``_walk``) runs the stack.  Each run of consecutive
  same-kind layers is ``lax.scan``-ned over its stacked params (HLO is
  O(1 layer) — the 94-layer MoE compiles in minutes on the dry-run host),
  with optional ``jax.checkpoint`` per layer in training.  A mixed stack
  (``mixer_period``) stores each run as its own stack, in the published
  order; Zamba2's shared block is the attention body applied to
  ``params["shared"]`` after each group.  In decode and extend, attention
  runs carry their stacked K/V through the layer loop and write only the
  new rows, and state runs freeze a slot's state with a ``where``.
* Inputs are token ids (``int``) or precomputed embeddings (``float`` —
  the VLM/audio modality-frontend stubs feed these).
* Four execution paths: ``__call__`` (teacher-forced training),
  ``prefill`` (chunked-kernel prompt ingestion returning decode state),
  ``decode_step`` (one token) and ``extend`` (a chunk of tokens at each
  slot's depth: the serving engine's prefill tick).
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
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def layer_types(self) -> tuple[str, ...]:
        """Each layer's mixer, "attention", "mamba" or "rwkv", in order."""
        if not self.mixer_period:
            kind = {"attn": "attention", "rwkv6": "rwkv",
                    "mamba2": "mamba"}[self.block]
            return (kind,) * self.num_layers
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
    layers: Any           # stacked KVCache / RWKVState / MambaState pytree;
                          #   a mixed stack: a tuple of them, one per run
    shared: Any           # hybrid only: stacked KVCache per shared-block app
    length: jax.Array     # [] int32, or per slot [B]


class _Phase(NamedTuple):
    """What one pass over the stack calls each layer's mixer with.
    ``kind`` is "train" (``__call__``), "prefill", "decode" or "extend"."""
    kind: str
    shard: Shard
    positions: jax.Array | None = None    # train, prefill: [B, T]
    max_len: int | None = None            # prefill: the cache's length
    pos: jax.Array | None = None          # decode, extend: cache.length
    valid: jax.Array | None = None        # decode, extend: [B], 0 = frozen


def _shift(z):
    return jnp.pad(z, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _layer_loop(step, carry, layers):
    """``carry = step(carry, (layer_params, i))`` over the stacked layers,
    with no per-layer outputs: state such as the stacked KV cache rides in
    the carry and is updated in place."""
    n = jax.tree.leaves(layers)[0].shape[0]
    carry, _ = jax.lax.scan(lambda c, a: (step(c, a), None), carry,
                            (layers, np.arange(n, dtype=np.int32)))
    return carry


def _freeze(valid, new, old):
    """A state layer's ``new`` state where ``valid > 0``, and ``old``
    where a slot is frozen (every leaf is ``[B, ...]``)."""
    if valid is None:
        return new
    with jax.named_scope("engine_select"):
        return jax.tree.map(lambda n, o: jnp.where(
            (valid > 0).reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, old)


# ---------------------------------------------------------------------------


class LM:
    def __init__(self, cfg: LMConfig):
        cfg.validate()
        self.cfg = cfg
        c = cfg
        common = dict(param_dtype=c.param_dtype, compute_dtype=c.compute_dtype)
        tnn = c.tnn if c.tnn.enabled else None
        # Zamba2: the layers between two calls of the shared block
        self.group = c.hybrid.shared_every if c.hybrid else 0
        # extend() takes a stack with any state layer token by token
        self.token_major = set(c.layer_types) != {"attention"}
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

    # -- layer bodies: one per mixer kind, shared by the four phases -----------

    def _residual(self, h):
        m = self.cfg.residual_multiplier
        return h * m if m != 1.0 else h

    def _mlp(self, mlp, lp, x, ph):
        """norm → MLP (SwiGLU, or MoE with its aux) → residual."""
        y = mlp(lp["mlp"], rmsnorm(lp["ln2"], x, self.cfg.norm_eps),
                ph.shard)
        y, aux = y if isinstance(mlp, MoE) else (y, {})
        x = x + self._residual(y)
        if ph.kind in ("train", "prefill"):
            x = ph.shard(x, ("batch", "seq", None))
        return x, aux

    def _attention(self, lp, x, ph, kv=None, i=None, attn=None, mlp=None):
        """An attention layer ``{"ln1", "attn", "ln2", "mlp"}``, with the
        stack's attention and MLP unless ``attn`` / ``mlp`` are given
        (Zamba2's shared block).  In decode and extend ``kv`` is the run's
        stacked ``(k, v)`` and ``i`` this layer's index in it.  Returns
        (x, the layer's KV cache, aux)."""
        attn, mlp = attn or self.attn, mlp or self.mlp
        h = rmsnorm(lp["ln1"], x, self.cfg.norm_eps)
        if ph.kind == "train":
            m, kv = attn(lp["attn"], h, ph.positions, ph.shard), None
        elif ph.kind == "prefill":
            m, kv = attn.prefill(lp["attn"], h, ph.positions, ph.max_len,
                                 ph.shard)
        else:
            call = attn.decode_step if ph.kind == "decode" else attn.extend
            m, kv = call(lp["attn"], h, KVCache(*kv, ph.pos), ph.shard,
                         valid=ph.valid, layer=i)
        x, aux = self._mlp(mlp, lp, x + self._residual(m), ph)
        return x, kv, aux

    def _mamba(self, lp, x, ph, state=None):
        """A Mamba-2 layer: ``{"ln", "mamba"}`` (Zamba2) or, with its own
        MLP, ``{"ln1", "mamba", "ln2", "mlp"}`` (Granite).  Returns (x, the
        layer's state, {})."""
        h = rmsnorm(lp["ln"] if "ln" in lp else lp["ln1"], x,
                    self.cfg.norm_eps)
        if ph.kind == "train":
            m, state = self.mamba(lp["mamba"], h, ph.shard), None
        elif ph.kind == "prefill":
            m, state = self.mamba(lp["mamba"], h, ph.shard, return_state=True)
        else:
            m, new = self.mamba.decode_step(lp["mamba"], h, state)
            state = _freeze(ph.valid, new, state)
        x = x + self._residual(m)
        if "mlp" in lp:
            x, _ = self._mlp(self.mlp, lp, x, ph)
        return x, state, {}

    def _rwkv(self, lp, x, ph, state=None):
        """An RWKV-6 layer ``{"ln1", "ln2", "rwkv"}``: the time mix, then
        the channel mix in the MLP's place.  Returns (x, the layer's
        state, {})."""
        c, blk = self.cfg, self.rwkv
        xn1 = rmsnorm(lp["ln1"], x, c.norm_eps)
        if ph.kind == "decode":
            tm, wkv, shift_tm = blk.time_mix_step(lp["rwkv"], xn1, state.wkv,
                                                  state.shift_tm)
            x = x + tm
            cm, shift_cm = blk.channel_mix_step(
                lp["rwkv"], rmsnorm(lp["ln2"], x, c.norm_eps), state.shift_cm)
            new = ssm.RWKVState(wkv, shift_tm, shift_cm)
            return x + cm, _freeze(ph.valid, new, state), {}
        tm, wkv = blk.time_mix(lp["rwkv"], xn1, ph.shard)
        x = x + tm
        xn2 = rmsnorm(lp["ln2"], x, c.norm_eps)
        x = x + blk.channel_mix(lp["rwkv"], xn2, _shift(xn2))
        if ph.kind == "train":
            return x, None, {}
        return x, ssm.RWKVState(wkv, xn1[:, -1].astype(c.compute_dtype),
                                xn2[:, -1].astype(c.compute_dtype)), {}

    # -- the stack walker ---------------------------------------------------------

    def _train_run(self, body, lp, x, ph):
        """A run in training: scanned, each layer (or each group of
        ``remat_group`` layers) under ``jax.checkpoint`` when ``remat``."""
        c = self.cfg

        def layer_fn(x, p):
            x, _, aux = body(p, x, ph)
            return x, aux

        if c.remat:
            layer_fn = jax.checkpoint(
                layer_fn, policy=jax.checkpoint_policies.nothing_saveable)
        n = jax.tree.leaves(lp)[0].shape[0]
        g = max(1, c.remat_group)
        if g > 1 and n % g == 0:
            def group_fn(x, gp):
                aux = None
                for li in range(g):
                    x, aux = layer_fn(x, jax.tree.map(lambda p: p[li], gp))
                return x, aux
            if c.remat:
                group_fn = jax.checkpoint(
                    group_fn, policy=jax.checkpoint_policies.nothing_saveable)
            grouped = jax.tree.map(
                lambda p: p.reshape((n // g, g) + p.shape[1:]), lp)
            return jax.lax.scan(group_fn, x, grouped)
        return jax.lax.scan(layer_fn, x, lp)

    def _run(self, lp, x, ph, states=None):
        """One run of same-kind layers: stacked ``[n, ...]`` params ``lp``
        and, in decode and extend, their stacked states.  Returns (x, the
        run's stacked states, aux)."""
        body = (self._attention if "attn" in lp else
                self._mamba if "mamba" in lp else self._rwkv)
        if ph.kind == "train":
            x, aux = self._train_run(body, lp, x, ph)
            return x, None, aux
        if ph.kind == "prefill":
            def step(x, p):
                x, state, _ = body(p, x, ph)
                return x, state
            x, states = jax.lax.scan(step, x, lp)
            return x, states, {}
        if "attn" in lp:
            def step(carry, scan_in):
                x, k, v = carry
                p, i = scan_in
                x, kv, _ = body(p, x, ph, (k, v), i)
                return x, kv.k, kv.v
            x, ks, vs = _layer_loop(step, (x, states.k, states.v), lp)
            # Per-layer lengths are bookkeeping only (decode and extend
            # read the global cache.length): advance by the tokens fed.
            return x, KVCache(ks, vs, states.length + x.shape[1]), {}

        def step(x, scan_in):
            p, state = scan_in
            x, state, _ = body(p, x, ph, state)
            return x, state
        x, states = jax.lax.scan(step, x, (lp, states))
        return x, states, {}

    def _runs(self, tree):
        """``params["layers"]`` or ``DecodeCache.layers`` as the walker's
        runs: a mixed stack's tuple as it is, a homogeneous stack as one
        run, a Zamba2 stack cut into its groups.  (A run's own states may
        be a NamedTuple, so the test is for a plain tuple.)"""
        if type(tree) is tuple:
            return tree
        n, g = jax.tree.leaves(tree)[0].shape[0], self.group
        if not g or g == n:
            return (tree,)
        return tuple(jax.tree.map(lambda a: a[i:i + g], tree)
                     for i in range(0, n, g))

    def _walk(self, params, x, ph, cache: DecodeCache | None = None):
        """The whole stack in phase ``ph``: each run of ``params["layers"]``
        in order, with Zamba2's shared block after each group.  ``cache``
        (decode, extend) holds the states going in.  Returns (x, the
        layers' states and the shared block's, in ``DecodeCache``'s
        layouts, and the training aux)."""
        runs = self._runs(params["layers"])
        states = ((None,) * len(runs) if cache is None
                  else self._runs(cache.layers))
        shared = None if cache is None else cache.shared
        shared_kv = None if shared is None else (shared.k, shared.v)
        outs, kvs, aux = [], [], {}
        for gi, (lp, st) in enumerate(zip(runs, states)):
            x, st, run_aux = self._run(lp, x, ph, st)
            outs.append(st)
            aux.update(run_aux)
            if self.group:
                x, kv, _ = self._attention(params["shared"], x, ph, shared_kv,
                                           gi, self.shared_attn,
                                           self.shared_mlp)
                kvs.append(kv)
                shared_kv = None if kv is None else (kv.k, kv.v)
        if ph.kind == "train":
            return x, None, None, aux
        if type(params["layers"]) is tuple:
            layers = tuple(outs)
        elif len(outs) == 1:
            layers = outs[0]
        else:
            layers = jax.tree.map(lambda *s: jnp.concatenate(s), *outs)
        if shared is not None:
            shared = KVCache(*shared_kv, shared.length + x.shape[1])
        elif kvs:
            shared = jax.tree.map(lambda *s: jnp.stack(s), *kvs)
        return x, layers, shared, aux

    # -- full-sequence forward (training) --------------------------------------

    def apply_layers(self, layers, x: jax.Array, positions: jax.Array,
                     shard: Shard = no_shard) -> tuple[jax.Array, dict]:
        """Run a contiguous slice of the layer stack in training.

        ``layers`` is a stacked ``[L', ...]`` pytree (a mixed stack: a
        tuple of runs) — the full ``params["layers"]``, or a stage's slice
        of it under pipeline parallelism (``repro.distributed.pipeline``).
        The scan/remat/remat-group lowering is identical either way, so a
        partitioned stack computes the same per-layer values as the
        monolithic forward.  Hybrid (shared-block) stacks need
        ``params["shared"]`` and run through :meth:`__call__`.
        """
        x, _, _, aux = self._walk({"layers": layers}, x,
                                  _Phase("train", shard, positions))
        return x, aux

    def __call__(self, params: dict, inputs: jax.Array,
                 shard: Shard = no_shard) -> tuple[jax.Array, dict]:
        """inputs: [B, T] ids or [B, T, D] embeds -> (logits [B,T,V], aux)."""
        c = self.cfg
        B, T = inputs.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x = self._embed(params, inputs, shard)
        x, _, _, aux = self._walk(params, x, _Phase("train", shard, positions))
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

        def kv(n):
            shape = (n, batch, max_len, c.num_kv_heads, c.hd)
            return KVCache(k=jnp.zeros(shape, c.compute_dtype),
                           v=jnp.zeros(shape, c.compute_dtype),
                           length=jnp.zeros((n,), jnp.int32))

        def run(kind, n):
            if kind == "attention":
                return kv(n)
            block = self.mamba if kind == "mamba" else self.rwkv
            return jax.tree.map(lambda s: jnp.zeros((n,) + s.shape, s.dtype),
                                block.init_state(batch))

        layers = tuple(run(kind, n) for kind, n in c.runs)
        return DecodeCache(
            layers=layers if c.mixer_period else layers[0],
            shared=kv(c.num_layers // self.group) if self.group else None,
            length=jnp.array(0, jnp.int32))

    # -- decode -------------------------------------------------------------------

    def decode_step(self, params: dict, token: jax.Array, cache: DecodeCache,
                    shard: Shard = no_shard, valid: jax.Array | None = None
                    ) -> tuple[jax.Array, DecodeCache]:
        """token: [B] ids (or [B, D] embeds) -> (logits [B, V], new cache).

        ``valid`` ([B] int32 of 0 or 1, None = every slot) freezes the
        slots with 0: their state, k/v and length come back as they went
        in.  An attention layer freezes inside its KV write, as
        :meth:`Attention.decode_step` says; a state layer keeps a frozen
        slot's state with a ``where`` over that layer's ``[B, ...]``
        state.  Attention runs carry their stacked K/V through the layer
        loop and write only the new rows, so a caller that donates the
        cache has it updated in place."""
        c = self.cfg
        inputs = token[:, None] if token.ndim == 1 else token[:, None, :]
        x = self._embed(params, inputs, shard)
        x, layers, shared, _ = self._walk(
            params, x, _Phase("decode", shard, pos=cache.length, valid=valid),
            cache)
        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        logits = self._logits(params, x)[:, 0]
        return logits, DecodeCache(layers=layers, shared=shared,
                                   length=cache.length + (1 if valid is None
                                                          else valid))

    # -- chunked prefill (serving) --------------------------------------------

    def extend(self, params: dict, tokens: jax.Array, cache: DecodeCache,
               shard: Shard = no_shard, valid: jax.Array | None = None
               ) -> tuple[jax.Array, DecodeCache]:
        """Ingest a ``[B, C]`` token chunk at each slot's current cache
        depth — the serving engine's chunked-prefill tick.

        ``cache.length`` may be per-slot ([B]); ``valid`` ([B] int32,
        None = all C) bounds how many chunk tokens are real per slot, and
        a slot with 0 comes back as it went in (see
        :meth:`Attention.extend` for the masked-write contract).  Returns
        logits for every chunk position ([B, C, V] — the engine reads row
        ``valid-1`` of slots whose prompt just completed) plus the
        advanced cache.  An attention stack takes the chunk layer by
        layer; a stack with any state layer takes it token by token, a
        ``lax.scan`` of :meth:`decode_step` over the chunk's columns in
        which column ``i`` of slot ``b`` is real while ``i < valid[b]``."""
        c = self.cfg
        C = tokens.shape[1]
        if self.token_major:
            def column(cache, scan_in):
                col, i = scan_in
                live = None if valid is None else (i < valid).astype(jnp.int32)
                logits, cache = self.decode_step(params, col, cache, shard,
                                                 valid=live)
                return cache, logits

            cache, logits = jax.lax.scan(
                column, cache, (jnp.moveaxis(tokens, 1, 0), jnp.arange(C)))
            return jnp.moveaxis(logits, 0, 1), cache
        x = self._embed(params, tokens, shard)
        x, layers, shared, _ = self._walk(
            params, x, _Phase("extend", shard, pos=cache.length, valid=valid),
            cache)
        adv = C if valid is None else valid
        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        logits = self._logits(params, x)                      # [B, C, V]
        return logits, DecodeCache(layers=layers, shared=shared,
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
        x, layers, shared, _ = self._walk(
            params, x, _Phase("prefill", shard, positions, max_len))
        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        logits = self._logits(params, x[:, -1:])[:, 0]
        return logits, DecodeCache(layers=layers, shared=shared,
                                   length=jnp.array(T, jnp.int32))
