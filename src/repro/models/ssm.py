"""State-space / linear-attention blocks: RWKV-6 (Finch) and Mamba-2 (SSD).

Both token mixers reduce to the chunked linear recurrence implemented in
``repro.kernels.ssm_scan`` (Pallas) / ``repro.kernels.ref`` (oracle):

    S_t = diag(d_t) S_{t-1} + k_t^T v_t,   o_t = q_t (...)

* RWKV-6: per-channel **data-dependent decay** (the defining Finch feature,
  via a low-rank MLP on the shifted input) plus the "bonus" ``u`` weight on
  the current token.  Token-shift mixing uses static per-channel mix
  coefficients (RWKV-5 style) for r/k/v/g — the data-dependent LoRA mix on
  those four is an accuracy refinement orthogonal to the compute pattern;
  decay keeps the full data-dependent path.  (Documented simplification.)
* Mamba-2: SSD with scalar-per-head decay exp(a·dt), shared B/C across
  heads (MQA-like, one group), depthwise causal conv on x/B/C, and the
  gated RMSNorm of the published mixer: ``y * silu(z)`` normalized over
  d_inner, then scaled.

Both blocks expose train (full-sequence, chunked kernel) and decode
(single-step recurrence on a carried state) paths.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import telemetry as tm
from repro.kernels import ops
from repro.models.blocks import Dense, Shard, groupnorm_heads, no_shard

from repro.core.tensorized import TNNConfig


# ---------------------------------------------------------------------------
# RWKV-6
# ---------------------------------------------------------------------------


class RWKVState(NamedTuple):
    wkv: jax.Array        # [B, H, dk, dv] recurrence state
    shift_tm: jax.Array   # [B, D] previous token (time mix)
    shift_cm: jax.Array   # [B, D] previous token (channel mix)


@dataclasses.dataclass(frozen=True)
class RWKV6Block:
    d_model: int
    head_dim: int = 64
    d_ff: int | None = None           # channel-mix hidden (defaults 3.5x)
    decay_lora: int = 64              # rank of the data-dependent decay MLP
    tnn: TNNConfig | None = None
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def num_heads(self) -> int:
        return self.d_model // self.head_dim

    @property
    def ff(self) -> int:
        return self.d_ff or int(3.5 * self.d_model)

    def _proj(self, d_in, d_out, target="mix") -> Dense:
        tnn = self.tnn if (self.tnn and target in self.tnn.targets) else None
        return Dense(d_in, d_out, tnn=tnn, param_dtype=self.param_dtype,
                     compute_dtype=self.compute_dtype)

    def init(self, key: jax.Array) -> dict:
        D, H, hd = self.d_model, self.num_heads, self.head_dim
        ks = jax.random.split(key, 12)
        lora = self.decay_lora
        return {
            "mix": {name: jnp.full((D,), v, jnp.float32) for name, v in
                    [("r", 0.5), ("k", 0.5), ("v", 0.5), ("g", 0.5), ("w", 0.5)]},
            "r": self._proj(D, D).init(ks[0]),
            "k": self._proj(D, D).init(ks[1]),
            "v": self._proj(D, D).init(ks[2]),
            "g": self._proj(D, D).init(ks[3]),
            "o": self._proj(D, D, target="out").init(ks[4]),
            # data-dependent decay: w_t = exp(-exp(w0 + tanh(x A) B))
            "w0": jnp.full((D,), -2.0, jnp.float32),
            "wA": (jax.random.normal(ks[5], (D, lora), jnp.float32) * 0.01
                   ).astype(self.param_dtype),
            "wB": (jax.random.normal(ks[6], (lora, D), jnp.float32) * 0.01
                   ).astype(self.param_dtype),
            "u": (jax.random.normal(ks[7], (H, hd), jnp.float32) * 0.1),
            "ln_x": jnp.ones((H, 1, hd), jnp.float32).reshape(H, hd),
            # channel mix
            "cm_mix": {"r": jnp.full((D,), 0.5, jnp.float32),
                       "k": jnp.full((D,), 0.5, jnp.float32)},
            "cm_k": self._proj(D, self.ff, target="mlp").init(ks[8]),
            "cm_v": self._proj(self.ff, D, target="mlp").init(ks[9]),
            "cm_r": self._proj(D, D).init(ks[10]),
        }

    # -- helpers -------------------------------------------------------------

    def _log_decay(self, params, xw):
        """Data-dependent per-channel log-decay (<= 0)."""
        lo = jnp.tanh(xw.astype(jnp.float32) @ params["wA"].astype(jnp.float32))
        lo = lo @ params["wB"].astype(jnp.float32)
        return -jnp.exp(params["w0"] + lo)       # [B, T, D], strictly < 0

    def _time_mix(self, params, x, x_prev):
        """x: [B, T, D]; x_prev: [B, T, D] (token-shifted input)."""
        B, T, D = x.shape
        H, hd = self.num_heads, self.head_dim
        mix = params["mix"]
        def mx(name):
            return x + (x_prev - x) * mix[name].astype(x.dtype)
        r = self._proj(D, D)(params["r"], mx("r"))
        k = self._proj(D, D)(params["k"], mx("k"))
        v = self._proj(D, D)(params["v"], mx("v"))
        g = self._proj(D, D)(params["g"], mx("g"))
        ld = self._log_decay(params, mx("w"))     # [B, T, D]
        return r, k, v, g, ld

    def _wkv_out(self, params, wkv, g, B, T):
        H, hd, D = self.num_heads, self.head_dim, self.d_model
        out = groupnorm_heads(wkv, params["ln_x"])            # [B,T,H,hd]
        out = out.reshape(B, T, D) * jax.nn.silu(g.astype(jnp.float32)
                                                 ).astype(out.dtype)
        return self._proj(D, D, target="out")(params["o"], out)

    def channel_mix(self, params, x, x_prev):
        D = self.d_model
        mix = params["cm_mix"]
        xk = x + (x_prev - x) * mix["k"].astype(x.dtype)
        xr = x + (x_prev - x) * mix["r"].astype(x.dtype)
        k = self._proj(D, self.ff, target="mlp")(params["cm_k"], xk)
        k = (jax.nn.relu(k.astype(jnp.float32)) ** 2).astype(x.dtype)
        v = self._proj(self.ff, D, target="mlp")(params["cm_v"], k)
        r = jax.nn.sigmoid(self._proj(D, D)(params["cm_r"], xr)
                           .astype(jnp.float32)).astype(x.dtype)
        return r * v

    # -- full-sequence (training / prefill) ------------------------------------

    def time_mix(self, params: dict, x: jax.Array, shard: Shard = no_shard,
                 chunk: int = 128, use_pallas: bool | None = None
                 ) -> tuple[jax.Array, jax.Array]:
        """x: [B, T, D] (pre-normed).  Returns (out, final wkv state
        [B, H, hd, hd] f32) — the state feeds decode after prefill."""
        B, T, D = x.shape
        H, hd = self.num_heads, self.head_dim
        shift = lambda z: jnp.pad(z, ((0, 0), (1, 0), (0, 0)))[:, :-1]  # noqa: E731

        r, k, v, g, ld = self._time_mix(params, x, shift(x))

        def heads(z):
            return (z.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
                    .reshape(B * H, T, hd))
        u = jnp.broadcast_to(params["u"], (B, H, hd)).reshape(B * H, hd)
        if T % chunk != 0:
            chunk = math.gcd(T, chunk) or 1
        wkv, state = ops.linear_scan(heads(r), heads(k), heads(v), heads(ld),
                                     u, mode="rwkv6", chunk=min(chunk, T),
                                     use_pallas=use_pallas)
        wkv = (wkv.reshape(B, H, T, hd).transpose(0, 2, 1, 3))  # [B,T,H,hd]
        tm_out = self._wkv_out(params, wkv, g, B, T)
        return tm_out, state.reshape(B, H, hd, hd)

    # -- decode ----------------------------------------------------------------

    def init_state(self, batch: int) -> RWKVState:
        H, hd, D = self.num_heads, self.head_dim, self.d_model
        return RWKVState(
            wkv=jnp.zeros((batch, H, hd, hd), jnp.float32),
            shift_tm=jnp.zeros((batch, D), self.compute_dtype),
            shift_cm=jnp.zeros((batch, D), self.compute_dtype),
        )

    def time_mix_step(self, params: dict, x: jax.Array, wkv_state: jax.Array,
                      shift: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Single-token time-mix.  x: [B, 1, D] (pre-normed);
        wkv_state: [B, H, hd, hd] f32; shift: [B, D] previous token.
        Returns (out [B,1,D], new_wkv_state, new_shift)."""
        B, _, D = x.shape
        H, hd = self.num_heads, self.head_dim
        prev = shift[:, None, :].astype(x.dtype)
        r, k, v, g, ld = self._time_mix(params, x, prev)
        rh = r.reshape(B, H, hd).astype(jnp.float32)
        kh = k.reshape(B, H, hd).astype(jnp.float32)
        vh = v.reshape(B, H, hd).astype(jnp.float32)
        dh = jnp.exp(ld.reshape(B, H, hd).astype(jnp.float32))
        u = params["u"][None]                                  # [1, H, hd]
        kv = jnp.einsum("bhk,bhv->bhkv", kh, vh)
        seen = wkv_state + u[..., None] * kv
        wkv = jnp.einsum("bhk,bhkv->bhv", rh, seen)            # [B, H, hd]
        new_wkv = wkv_state * dh[..., None] + kv
        out = self._wkv_out(params, wkv.reshape(B, 1, H, hd).astype(x.dtype),
                            g, B, 1)
        return out, new_wkv, x[:, -1].astype(shift.dtype)

    def channel_mix_step(self, params: dict, x: jax.Array, shift: jax.Array
                         ) -> tuple[jax.Array, jax.Array]:
        """Single-token channel mix.  x: [B, 1, D] (pre-normed)."""
        prev = shift[:, None, :].astype(x.dtype)
        out = self.channel_mix(params, x, prev)
        return out, x[:, -1].astype(shift.dtype)


# ---------------------------------------------------------------------------
# Mamba-2 (SSD)
# ---------------------------------------------------------------------------


class MambaState(NamedTuple):
    ssm: jax.Array        # [B, H, dk, hd] recurrence state
    conv: jax.Array       # [B, conv_w - 1, conv_dim] rolling conv window


@dataclasses.dataclass(frozen=True)
class Mamba2Block:
    d_model: int
    d_state: int = 64
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 128                  # SSD chunk (the published chunk size)
    norm_eps: float = 1e-5            # the gated RMSNorm's epsilon
    tnn: TNNConfig | None = None
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def num_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.d_state

    def _proj(self, d_in, d_out, target="mix") -> Dense:
        tnn = self.tnn if (self.tnn and target in self.tnn.targets) else None
        return Dense(d_in, d_out, tnn=tnn, param_dtype=self.param_dtype,
                     compute_dtype=self.compute_dtype)

    def init(self, key: jax.Array) -> dict:
        D, DI, H = self.d_model, self.d_inner, self.num_heads
        ks = jax.random.split(key, 4)
        return {
            # in_proj -> [z (DI), x (DI), B (S), C (S), dt (H)]
            "in": self._proj(D, 2 * DI + 2 * self.d_state + H).init(ks[0]),
            "conv_w": (jax.random.normal(ks[1], (self.conv_width, self.conv_dim),
                                         jnp.float32) * 0.1),
            "conv_b": jnp.zeros((self.conv_dim,), jnp.float32),
            "A_log": jnp.zeros((H,), jnp.float32),     # a = -exp(A_log)
            "D_skip": jnp.ones((H,), jnp.float32),
            "dt_bias": jnp.zeros((H,), jnp.float32),
            "norm": jnp.ones((DI,), jnp.float32),
            "out": self._proj(DI, D, target="out").init(ks[2]),
        }

    def _split(self, params, x):
        """in_proj + split.  x: [B, T, D]."""
        DI, S, H = self.d_inner, self.d_state, self.num_heads
        zxbcdt = self._proj(self.d_model, 2 * DI + 2 * S + H)(params["in"], x)
        z, xs, Bm, Cm, dt = jnp.split(
            zxbcdt, [DI, 2 * DI, 2 * DI + S, 2 * DI + 2 * S], axis=-1)
        return z, xs, Bm, Cm, dt

    def _conv_train(self, params, u):
        """Depthwise causal conv over [B, T, conv_dim]."""
        w = params["conv_w"].astype(jnp.float32)               # [W, C]
        pads = [(0, 0), (self.conv_width - 1, 0), (0, 0)]
        up = jnp.pad(u.astype(jnp.float32), pads)
        out = sum(up[:, i:i + u.shape[1]] * w[i] for i in range(self.conv_width))
        return jax.nn.silu(out + params["conv_b"]).astype(u.dtype)

    def _gated_norm(self, params, y, z):
        """RMSNorm of ``y * silu(z)`` over d_inner, then the weight."""
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        var = jnp.mean(g * g, axis=-1, keepdims=True)
        return (g * jax.lax.rsqrt(var + self.norm_eps)
                * params["norm"]).astype(z.dtype)

    def _ssd(self, params, xs, Bm, Cm, dt, use_pallas=None):
        B_, T = xs.shape[:2]
        H, hd, S = self.num_heads, self.head_dim, self.d_state
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + params["dt_bias"])              # [B, T, H]
        a = -jnp.exp(params["A_log"])                          # [H]
        ld = dt * a                                            # [B, T, H] log decay
        xh = xs.reshape(B_, T, H, hd)
        # streams per (batch, head): k = B*dt, q = C, v = x_head
        def stream(z, d):                                       # [B,T,d] shared
            return (jnp.broadcast_to(z[:, :, None], (B_, T, H, d))
                    .transpose(0, 2, 1, 3).reshape(B_ * H, T, d))
        k = stream(Bm, S) * dt.transpose(0, 2, 1).reshape(B_ * H, T, 1)
        q = stream(Cm, S)
        v = xh.transpose(0, 2, 1, 3).reshape(B_ * H, T, hd)
        ld = ld.transpose(0, 2, 1).reshape(B_ * H, T)         # [B*H, T]
        chunk = math.gcd(T, self.chunk)
        if use_pallas is None:
            use_pallas = ops.USE_PALLAS_DEFAULT
        tm.inc("ssd.calls." + ("pallas" if use_pallas else "jnp"))
        tm.inc(f"ssd.chunk.{chunk}")
        with jax.named_scope("ssd"):
            y, state = ops.linear_scan(q.astype(self.compute_dtype),
                                       k.astype(self.compute_dtype),
                                       v.astype(self.compute_dtype),
                                       ld, mode="ssd", chunk=chunk,
                                       use_pallas=use_pallas)  # [B*H, T, hd]
        y = y.reshape(B_, H, T, hd).transpose(0, 2, 1, 3)      # [B, T, H, hd]
        y = y + xh * params["D_skip"][None, None, :, None]
        return y.reshape(B_, T, self.d_inner), state.reshape(B_, H, S, hd)

    def __call__(self, params: dict, x: jax.Array, shard: Shard = no_shard,
                 use_pallas: bool | None = None, return_state: bool = False):
        with jax.named_scope("mamba"):
            B, T, D = x.shape
            z, xs, Bm, Cm, dt = self._split(params, x)
            conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
            conv_out = self._conv_train(params, conv_in)
            xs, Bm, Cm = jnp.split(conv_out, [self.d_inner, self.d_inner
                                              + self.d_state], axis=-1)
            y, ssm_state = self._ssd(params, xs, Bm, Cm, dt, use_pallas)
            y = self._gated_norm(params, y, z)
            out = self._proj(self.d_inner, D, target="out")(params["out"], y)
            if return_state:
                w = self.conv_width - 1
                tail = conv_in[:, -w:].astype(jnp.float32)
                pad = jnp.zeros((B, max(0, w - T), self.conv_dim), jnp.float32)
                state = MambaState(ssm=ssm_state,
                                   conv=jnp.concatenate([pad, tail], axis=1))
                return out, state
            return out

    # -- decode ----------------------------------------------------------------

    def init_state(self, batch: int) -> MambaState:
        return MambaState(
            ssm=jnp.zeros((batch, self.num_heads, self.d_state, self.head_dim),
                          jnp.float32),
            conv=jnp.zeros((batch, self.conv_width - 1, self.conv_dim),
                           jnp.float32),
        )

    def decode_step(self, params: dict, x: jax.Array, state: MambaState
                    ) -> tuple[jax.Array, MambaState]:
        """x: [B, 1, D]."""
        with jax.named_scope("mamba"):
            B = x.shape[0]
            H, hd, S = self.num_heads, self.head_dim, self.d_state
            z, xs, Bm, Cm, dt = self._split(params, x)
            u = jnp.concatenate([xs, Bm, Cm], axis=-1)[:, 0]       # [B, conv_dim]
            window = jnp.concatenate([state.conv, u[:, None].astype(jnp.float32)],
                                     axis=1)                        # [B, W, C]
            w = params["conv_w"].astype(jnp.float32)
            conv_out = jax.nn.silu(jnp.sum(window * w[None], axis=1)
                                   + params["conv_b"])              # [B, C]
            xs, Bm, Cm = (conv_out[:, :self.d_inner],
                          conv_out[:, self.d_inner:self.d_inner + S],
                          conv_out[:, self.d_inner + S:])
            dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                                  + params["dt_bias"])              # [B, H]
            decay = jnp.exp(dtv * -jnp.exp(params["A_log"]))        # [B, H]
            xh = xs.reshape(B, H, hd).astype(jnp.float32)
            kv = jnp.einsum("bs,bhp->bhsp", Bm.astype(jnp.float32), xh)
            new_ssm = (state.ssm * decay[..., None, None]
                       + kv * dtv[..., None, None])
            y = jnp.einsum("bs,bhsp->bhp", Cm.astype(jnp.float32), new_ssm)
            y = y + xh * params["D_skip"][None, :, None]
            y = self._gated_norm(params, y.reshape(B, 1, self.d_inner), z)
            out = self._proj(self.d_inner, self.d_model, target="out")(
                params["out"], y)
            new_state = MambaState(ssm=new_ssm,
                                   conv=window[:, 1:].astype(jnp.float32))
            return out, new_state
