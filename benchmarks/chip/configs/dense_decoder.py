"""Plain float32 reference of a dense GQA decoder with a TT-factorized MLP.

It stands beside the configuration files that name it (``"reference":
"dense_decoder"``): InternLM2-1.8B and Qwen2-7B with the MLP's three
projections stored as tensor-train cores.  It imports nothing of the system
under test.  Everything is written from the published layer equations:

    x    = embed[tokens]
    per layer:
      h  = rmsnorm(x) * ln1
      q, k, v = h Wq (+ bq), h Wk (+ bk), h Wv (+ bv)      (GQA, head 128)
      q, k = rope(q), rope(k)                              (rotate-half)
      x  = x + softmax(q k^T / sqrt(hd), causal) v Wo
      h  = rmsnorm(x) * ln2
      x  = x + (silu(h Wg^T) * (h Wu^T)) Wd^T
    logits = (rmsnorm(x) * ln_f) Whead

Each TT-factorized ``W[M, N]`` is contracted to a dense matrix from its
cores ``G0[m0, r] G1[r, m1, r] G2[r, n0, r] G3[r, n1]`` before use.

``prec`` selects the arithmetic: ``"f32"`` is float32 with every matrix
product at ``Precision.HIGHEST``; ``"fp8"`` rounds both operands of every
matrix product to float8_e4m3 (per-tensor scale) first, which is the
control that the output comparison has to reject.

The weights are made here as well, from a key, leaf by leaf in the layout
the system stores them in (``param_shapes``); the benchmark hands the same
function's output to the system, and later makes them again for this
reference.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from ..flops import factorize_dim

HI = jax.lax.Precision.HIGHEST


# -- sizes ------------------------------------------------------------------


def tt_core_shapes(m: int, n: int, rank: int, k: int) -> list[tuple]:
    """TT cores of ``W[m, n]``: k output cores, then k input cores."""
    dims = factorize_dim(m, k) + factorize_dim(n, k)
    d = len(dims)
    return [((rank,) if i else ()) + (dims[i],) + ((rank,) if i < d - 1 else ())
            for i in range(d)]


def hparams(cfg: dict) -> dict:
    """The model sizes of a configuration file, by short name."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=h,
                KV=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // h), F=cfg["intermediate_size"],
                V=cfg["vocab_size"], bias=cfg.get("qkv_bias", False),
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
                rank=cfg["tnn"]["rank"], k=cfg["tnn"]["num_factors"])


def param_shapes(cfg: dict) -> dict:
    """The parameter tree, as shapes, in the layout the system stores it."""
    p = hparams(cfg)
    L, d, H, KV, hd, F, V = (p[x] for x in ("L", "d", "H", "KV", "hd", "F", "V"))

    def dense(i, o):
        out = {"w": (L, i, o)}
        if p["bias"]:
            out["b"] = (L, o)
        return out

    def tt(i, o):
        return {"cores": tuple((L,) + s
                               for s in tt_core_shapes(o, i, p["rank"], p["k"]))}

    attn = {"q": dense(d, H * hd), "k": dense(d, KV * hd),
            "v": dense(d, KV * hd), "o": {"w": (L, H * hd, d)}}
    return {"embed": (V, d), "ln_f": {"scale": (d,)},
            "layers": {"ln1": {"scale": (L, d)}, "attn": attn,
                       "ln2": {"scale": (L, d)},
                       "mlp": {"gate": tt(d, F), "up": tt(d, F),
                               "down": tt(F, d)}},
            "lm_head": {"w": (d, V)}}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _leaf_std(path: str, shape: tuple, p: dict) -> tuple[float, float]:
    """(mean, std) of a leaf's entries.  Norm scales and biases are random
    so that a path that drops them shows; TT cores are scaled so that the
    dense matrix they make has std 1/sqrt(fan-in)."""
    if path.endswith("scale"):
        return 1.0, 0.1
    if path.endswith("/b"):
        return 0.0, 0.1
    if "/cores/" in path:
        n_cores = 2 * p["k"]
        fan_in = p["F"] if "/down/" in path else p["d"]
        paths = p["rank"] ** (n_cores - 1)
        return 0.0, (1.0 / fan_in / paths) ** (1.0 / (2 * n_cores))
    if path == "embed":
        return 0.0, 1.0 / math.sqrt(shape[-1])
    return 0.0, 1.0 / math.sqrt(shape[-2])


def init_params(cfg: dict, key: jax.Array) -> dict:
    """f32 weights from ``key``; each leaf from its own key, folded from
    the leaf's path, so that a leaf does not depend on the others."""
    p = hparams(cfg)

    def walk(node, path):
        if _is_shape(node):
            mean, std = _leaf_std(path, node, p)
            k = jax.random.fold_in(key, zlib.crc32(path.encode()))
            return mean + std * jax.random.normal(k, node, jnp.float32)
        if isinstance(node, tuple):
            return tuple(walk(c, f"{path}/{i}") for i, c in enumerate(node))
        return {n: walk(c, f"{path}/{n}" if path else n)
                for n, c in node.items()}

    return walk(param_shapes(cfg), "")


# -- arithmetic -------------------------------------------------------------


def _round(x, dtype, top: float):
    """``x`` rounded to ``dtype`` under a per-tensor scale that maps its
    largest magnitude to ``top``."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    """Operands in float8_e4m3; their gradients, as fp8 training does, in
    float8_e5m2, each under its own scale."""
    return _round(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def mm(spec: str, a, b, prec: str):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if prec == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def tt_dense(cores, prec: str) -> jax.Array:
    """``W[M, N]`` from its TT cores (one layer's, no layer axis)."""
    w = cores[0].astype(jnp.float32)                       # [m0, r]
    for c in cores[1:]:
        w = jnp.tensordot(w, c.astype(jnp.float32), axes=1, precision=HI)
    k = len(cores) // 2
    m = math.prod(w.shape[:k])
    return w.reshape(m, -1)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """Rotate-half rotary embedding.  x [T, n, hd], pos [T]."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq           # [T, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, prec: str, q_block: int):
    """Causal GQA attention of one sequence, in blocks of query rows.
    q [T, H, hd]; k, v [T, KV, hd]."""
    T, H, hd = q.shape
    KV = k.shape[1]
    g = H // KV
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    qb = min(q_block, T)
    nb = T // qb

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)       # [qb, H, hd]
        s = mm("qhd,khd->hqk", qi, kk, prec) / math.sqrt(hd)
        rows = i * qb + jnp.arange(qb)
        s = jnp.where(rows[None, :, None] >= jnp.arange(T)[None, None], s,
                      -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return mm("hqk,khd->qhd", pr, vv, prec)

    return jax.lax.map(block, jnp.arange(nb)).reshape(T, H, hd)


def layer(p: dict, lp: dict, x, prec: str, q_block: int = 512):
    """One decoder layer on one sequence x [T, d]."""
    T = x.shape[0]
    H, KV, hd = p["H"], p["KV"], p["hd"]
    h = rmsnorm(x, lp["ln1"]["scale"], p["eps"])
    a = lp["attn"]

    def proj(name, n):
        y = mm("td,de->te", h, a[name]["w"], prec)
        if "b" in a[name]:
            y = y + a[name]["b"]
        return y.reshape(T, n, hd)

    pos = jnp.arange(T)
    q = rope(proj("q", H), pos, p["theta"])
    k = rope(proj("k", KV), pos, p["theta"])
    v = proj("v", KV)
    ctx = attention(q, k, v, prec, q_block).reshape(T, H * hd)
    x = x + mm("te,ed->td", ctx, a["o"]["w"], prec)
    h = rmsnorm(x, lp["ln2"]["scale"], p["eps"])
    m = lp["mlp"]
    wg, wu, wd = (tt_dense(m[n]["cores"], prec) for n in ("gate", "up", "down"))
    g = mm("td,fd->tf", h, wg, prec)
    u = mm("td,fd->tf", h, wu, prec)
    return x + mm("tf,df->td", jax.nn.silu(g) * u, wd, prec)


def hidden(cfg: dict, params: dict, tokens, prec: str = "f32"):
    """Final normed hidden states of one sequence, [T, d]."""
    p = hparams(cfg)
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        return jax.checkpoint(lambda x, lp: layer(p, lp, x, prec))(x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["ln_f"]["scale"], p["eps"])


def logits(cfg: dict, params: dict, tokens, prec: str = "f32"):
    """Logits of one sequence, [T, V]."""
    return mm("td,dv->tv", hidden(cfg, params, tokens, prec),
              params["lm_head"]["w"], prec)


def loss(cfg: dict, params: dict, inputs, targets, prec: str = "f32",
         row_block: int = 1024):
    """Mean next-token NLL over a batch [B, T], the head and the softmax
    taken in blocks of rows so that the full logits never exist."""
    B, T = inputs.shape

    @jax.checkpoint
    def seq_nll(tok, tgt):
        x = hidden(cfg, params, tok, prec)
        rb = min(row_block, T)

        @jax.checkpoint
        def block(xs, ts):
            lg = mm("td,dv->tv", xs, params["lm_head"]["w"], prec)
            gold = jnp.take_along_axis(lg, ts[:, None], -1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(lg, -1) - gold)

        xs = x.reshape(T // rb, rb, -1)
        ts = tgt.reshape(T // rb, rb)
        return jnp.sum(jax.lax.map(lambda a: block(*a), (xs, ts)))

    total = jnp.sum(jax.lax.map(lambda a: seq_nll(*a), (inputs, targets)))
    return total / (B * T)


# -- optimizer ----------------------------------------------------------------


def adamw_step(params, grads, m, v, step, opt: dict):
    """One AdamW step as the trainer's optimizer states it: global-norm
    clipping, bias-corrected moments, decoupled weight decay on every leaf
    with two or more axes, linear warm-up then cosine decay.  ``step``
    counts from 1.  Returns the new weights, moments and the clip scale."""
    flat_g, tree = jax.tree.flatten(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in flat_g))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    frac = jnp.clip((step - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    decay = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * frac))
    lr = opt["lr"] * warm * decay
    b1, b2 = opt["b1"], opt["b2"]
    b1c, b2c = 1 - b1 ** step, 1 - b2 ** step
    out = []
    for p, g, m_, v_ in zip(tree.flatten_up_to(params), flat_g,
                            tree.flatten_up_to(m), tree.flatten_up_to(v)):
        g = g * scale
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        delta = (m_ / b1c) / (jnp.sqrt(v_ / b2c) + opt["eps"])
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p
        out.append((p - lr * delta, m_, v_))
    return (*(tree.unflatten([o[i] for o in out]) for i in range(3)), scale)
