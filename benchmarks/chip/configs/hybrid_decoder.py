"""Plain float32 reference of a hybrid Mamba-2 / attention decoder with a
TT-factorized MLP in every layer: Granite-4.0-H (``granitemoehybrid``,
no experts), as the GraniteMoeHybrid and Bamba layers of the published
release write it.  It imports nothing of the system under test.

    x = embed[tokens] * embedding_multiplier
    per layer i:  h = rmsnorm(x) * ln1
                  m = mamba2(h)  if layer_types[i] == "mamba"  else  attn(h)
                  x = x + residual_multiplier * m
                  h = rmsnorm(x) * ln2
                  x = x + residual_multiplier * (silu(h Wg) * (h Wu)) Wd
    logits = (rmsnorm(x) * ln_f) embed^T / logits_scaling
    attn:   GQA, no position embedding,
            softmax(q k^T * attention_multiplier, causal) v, then Wo
    mamba2: [z | xBC | dt] = h Win
            xBC = silu(causal_depthwise_conv(xBC) + b)
            x, B, C = split(xBC)
            dt = softplus(dt + dt_bias);  A = -exp(A_log)
            S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T   (per head; B and C
                  shared by the heads of the one group)
            y_t = C_t S_t + D x_t
            y = rmsnorm_gated(y * silu(z)) * w_norm     (over d_inner)
            out = y Wout

The Mamba-2 mixer's scan is the minimal chunked SSD of the Mamba-2 paper
(arXiv:2405.21060, section 6), taken one chunk at a time so that it fits at
full size: within a chunk the output is ``(C B^T * L) X`` with the decay
matrix ``L = exp(segsum(dt A))``, plus the carried state read through C;
the state passes from chunk to chunk.  Departures from the release: the
MLP's three projections are stored as TT cores and contracted to dense
matrices before use; nothing else.

The stored layout is the system's: each run of consecutive layers of one
kind is one stack, in order (``params["layers"]`` is a tuple of runs).
``prec`` is as in ``dense_decoder``: ``"f32"`` at ``Precision.HIGHEST``, or
``"fp8"`` with both operands of every matrix product rounded to float8.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from .dense_decoder import adamw_step, mm, rmsnorm, tt_core_shapes, tt_dense

__all__ = ["param_shapes", "init_params", "hidden", "logits", "loss",
           "adamw_step", "mm", "ssd_chunked", "ssd_sequential", "runs"]


# -- sizes ------------------------------------------------------------------


def hparams(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    types = tuple(cfg["layer_types"])
    assert len(types) == cfg["num_hidden_layers"], "layer_types as run"
    assert cfg["mamba_n_groups"] == 1 and not cfg["attention_bias"]
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["tie_word_embeddings"]
    return dict(types=types, d=d, H=h, KV=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // h), F=cfg["intermediate_size"],
                V=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
                mh=cfg["mamba_n_heads"], mp=cfg["mamba_d_head"],
                N=cfg["mamba_d_state"], W=cfg["mamba_d_conv"],
                chunk=cfg["mamba_chunk_size"],
                emb=float(cfg["embedding_multiplier"]),
                res=float(cfg["residual_multiplier"]),
                att=float(cfg["attention_multiplier"]),
                logit=float(cfg["logits_scaling"]),
                rank=cfg["tnn"]["rank"], k=cfg["tnn"]["num_factors"])


def runs(types) -> list[tuple[str, int]]:
    """Consecutive layers of one kind: ``(kind, count)`` in order."""
    out: list[list] = []
    for t in types:
        if out and out[-1][0] == t:
            out[-1][1] += 1
        else:
            out.append([t, 1])
    return [(t, n) for t, n in out]


def param_shapes(cfg: dict) -> dict:
    """The parameter tree, as shapes, in the layout the system stores it."""
    p = hparams(cfg)
    d, H, KV, hd, F = p["d"], p["H"], p["KV"], p["hd"], p["F"]
    DI = p["mh"] * p["mp"]
    conv = DI + 2 * p["N"]

    def run(kind, n):
        def tt(i, o):
            return {"cores": tuple((n,) + s for s in
                                   tt_core_shapes(o, i, p["rank"], p["k"]))}
        if kind == "attention":
            mixer = {"attn": {"q": {"w": (n, d, H * hd)},
                              "k": {"w": (n, d, KV * hd)},
                              "v": {"w": (n, d, KV * hd)},
                              "o": {"w": (n, H * hd, d)}}}
        else:
            mixer = {"mamba": {"in": {"w": (n, d, 2 * DI + 2 * p["N"]
                                            + p["mh"])},
                               "conv_w": (n, p["W"], conv),
                               "conv_b": (n, conv), "A_log": (n, p["mh"]),
                               "D_skip": (n, p["mh"]),
                               "dt_bias": (n, p["mh"]), "norm": (n, DI),
                               "out": {"w": (n, DI, d)}}}
        return {"ln1": {"scale": (n, d)}, **mixer, "ln2": {"scale": (n, d)},
                "mlp": {"gate": tt(d, F), "up": tt(d, F), "down": tt(F, d)}}

    return {"embed": (p["V"], d), "ln_f": {"scale": (d,)},
            "layers": tuple(run(k, n) for k, n in runs(p["types"]))}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _leaf(path: str, shape: tuple, p: dict, key) -> jax.Array:
    """A leaf's weights.  Norm scales, biases and the skip weight are
    random around their usual values, so that a path that drops them shows;
    ``A_log`` and ``dt_bias`` are drawn as the published Mamba-2 init draws
    them (A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1] floored at
    1e-4, ``dt_bias`` its inverse softplus); TT cores are scaled so that
    the dense matrix they make has std 1/sqrt(fan-in)."""
    name = path.rsplit("/", 1)[-1]
    if name in ("A_log", "dt_bias"):
        uniform = jax.random.uniform(key, shape, jnp.float32)
    else:
        normal = jax.random.normal(key, shape, jnp.float32)
    if name in ("scale", "norm", "D_skip"):
        return 1.0 + 0.1 * normal
    if name == "conv_b":
        return 0.1 * normal
    if name == "conv_w":
        return normal / math.sqrt(shape[-2])
    if name == "A_log":
        return jnp.log(1.0 + 15.0 * uniform)
    if name == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.maximum(jnp.exp(lo + (hi - lo) * uniform), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    if "/cores/" in path:
        n_cores = 2 * p["k"]
        fan_in = p["F"] if "/down/" in path else p["d"]
        paths = p["rank"] ** (n_cores - 1)
        return normal * (1.0 / fan_in / paths) ** (1.0 / (2 * n_cores))
    if path == "embed":
        return normal / math.sqrt(shape[-1])
    return normal / math.sqrt(shape[-2])


def init_params(cfg: dict, key: jax.Array) -> dict:
    """f32 weights from ``key``; each leaf from its own key, folded from
    the leaf's path."""
    p = hparams(cfg)

    def walk(node, path):
        if _is_shape(node):
            return _leaf(path, node, p,
                         jax.random.fold_in(key, zlib.crc32(path.encode())))
        if isinstance(node, tuple):
            return tuple(walk(c, f"{path}/{i}") for i, c in enumerate(node))
        return {n: walk(c, f"{path}/{n}" if path else n)
                for n, c in node.items()}

    return walk(param_shapes(cfg), "")


# -- the mixers ----------------------------------------------------------------


def attention(q, k, v, scale: float, prec: str, q_block: int = 512):
    """Causal GQA attention of one sequence, in blocks of query rows.
    q [T, H, hd]; k, v [T, KV, hd]."""
    T, H, hd = q.shape
    g = H // k.shape[1]
    kk, vv = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    qb = min(q_block, T)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        s = mm("qhd,khd->hqk", qi, kk, prec) * scale
        rows = i * qb + jnp.arange(qb)
        s = jnp.where(rows[None, :, None] >= jnp.arange(T)[None, None], s,
                      -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vv, prec)

    return jax.lax.map(block, jnp.arange(T // qb)).reshape(T, H, hd)


def segsum(a):
    """``out[..., i, j] = a[..., j+1] + ... + a[..., i]`` for j <= i, and
    -inf above the diagonal (the log of the decay from j to i)."""
    T = a.shape[-1]
    c = jnp.cumsum(a, axis=-1)
    s = c[..., :, None] - c[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def ssd_chunked(x, a, B, C, chunk: int, prec: str = "f32"):
    """The chunked SSD of arXiv:2405.21060 section 6, one chunk at a time.
    x [T, h, p] (already times dt), a [T, h] (dt * A), B, C [T, n] (one
    group).  Returns y [T, h, p] and the final state [h, p, n]."""
    T, h, pdim = x.shape
    n = B.shape[-1]
    c = min(chunk, T)
    pad = -T % c       # zeros at the end: no input, no decay, no output read
    if pad:
        x, a, B, C = (jnp.pad(z, [(0, pad)] + [(0, 0)] * (z.ndim - 1))
                      for z in (x, a, B, C))

    def step(S, blk):
        xc, ac, Bc, Cc = blk                              # [c, ...]
        acs = jnp.cumsum(ac, axis=0)                      # [c, h]
        L = jnp.exp(segsum(ac.T))                         # [h, c, c]
        cb = mm("ln,sn->ls", Cc, Bc, prec)                # [c, c]
        y_diag = mm("hls,shp->lhp", cb[None] * L, xc, prec)
        y_off = mm("ln,hpn->lhp", Cc, S, prec) * jnp.exp(acs)[..., None]
        decay = jnp.exp(acs[-1:] - acs)                   # [c, h]
        S = (S * jnp.exp(acs[-1])[:, None, None]
             + mm("shp,sn->hpn", xc * decay[..., None], Bc, prec))
        return S, y_diag + y_off

    blocks = tuple(z.reshape((-1, c) + z.shape[1:]) for z in (x, a, B, C))
    S, y = jax.lax.scan(step, jnp.zeros((h, pdim, n), jnp.float32), blocks)
    return y.reshape(-1, h, pdim)[:T], S


def ssd_sequential(x, a, B, C):
    """The same recurrence token by token: S_t = exp(a_t) S_{t-1} +
    x_t B_t^T, y_t = S_t C_t (the check on :func:`ssd_chunked`)."""
    def step(S, t):
        xt, at, Bt, Ct = t
        S = S * jnp.exp(at)[:, None, None] + xt[..., None] * Bt
        return S, jnp.sum(S * Ct, axis=-1)

    h, pdim, n = x.shape[1], x.shape[2], B.shape[-1]
    S, y = jax.lax.scan(step, jnp.zeros((h, pdim, n), jnp.float32),
                        (x, a, B, C))
    return y, S


def mamba2(p: dict, mp: dict, h, prec: str, sequential: bool = False):
    """The Mamba-2 mixer of one sequence h [T, d]; ``sequential`` takes
    the scan token by token (:func:`ssd_sequential`, for the tests)."""
    T = h.shape[0]
    DI, N, nh, hp, W = p["mh"] * p["mp"], p["N"], p["mh"], p["mp"], p["W"]
    zxd = mm("td,de->te", h, mp["in"]["w"], prec)
    z, xbc, dt = zxd[:, :DI], zxd[:, DI:2 * DI + 2 * N], zxd[:, 2 * DI + 2 * N:]
    pad = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    conv = sum(pad[i:i + T] * mp["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + mp["conv_b"])
    xs, B, C = xbc[:, :DI], xbc[:, DI:DI + N], xbc[:, DI + N:]
    dt = jax.nn.softplus(dt + mp["dt_bias"])              # [T, nh]
    A = -jnp.exp(mp["A_log"])                             # [nh]
    xh = xs.reshape(T, nh, hp)
    if sequential:
        y, _ = ssd_sequential(xh * dt[..., None], dt * A, B, C)
    else:
        y, _ = ssd_chunked(xh * dt[..., None], dt * A, B, C, p["chunk"], prec)
    y = (y + xh * mp["D_skip"][:, None]).reshape(T, DI)
    g = y * jax.nn.silu(z)
    g = rmsnorm(g, mp["norm"], p["eps"])
    return mm("te,ed->td", g, mp["out"]["w"], prec)


def attn(p: dict, ap: dict, h, prec: str):
    T = h.shape[0]
    H, KV, hd = p["H"], p["KV"], p["hd"]

    def proj(name, n):
        return mm("td,de->te", h, ap[name]["w"], prec).reshape(T, n, hd)

    ctx = attention(proj("q", H), proj("k", KV), proj("v", KV), p["att"],
                    prec).reshape(T, H * hd)
    return mm("te,ed->td", ctx, ap["o"]["w"], prec)


def layer(p: dict, lp: dict, x, prec: str):
    """One layer (of either kind, by the key its weights are under) on one
    sequence x [T, d]."""
    h = rmsnorm(x, lp["ln1"]["scale"], p["eps"])
    m = (attn(p, lp["attn"], h, prec) if "attn" in lp
         else mamba2(p, lp["mamba"], h, prec))
    x = x + p["res"] * m
    h = rmsnorm(x, lp["ln2"]["scale"], p["eps"])
    ml = lp["mlp"]
    wg, wu, wd = (tt_dense(ml[n]["cores"], prec) for n in ("gate", "up", "down"))
    g = mm("td,fd->tf", h, wg, prec)
    u = mm("td,fd->tf", h, wu, prec)
    return x + p["res"] * mm("tf,df->td", jax.nn.silu(g) * u, wd, prec)


def hidden(cfg: dict, params: dict, tokens, prec: str = "f32"):
    """Final normed hidden states of one sequence, [T, d]."""
    p = hparams(cfg)
    x = params["embed"][tokens].astype(jnp.float32) * p["emb"]

    def body(x, lp):
        return jax.checkpoint(lambda x, lp: layer(p, lp, x, prec))(x, lp), None

    for run in params["layers"]:
        x, _ = jax.lax.scan(body, x, run)
    return rmsnorm(x, params["ln_f"]["scale"], p["eps"])


def _head(cfg, params, xs, prec):
    return mm("td,vd->tv", xs, params["embed"], prec) / float(
        cfg["logits_scaling"])


def logits(cfg: dict, params: dict, tokens, prec: str = "f32"):
    """Logits of one sequence, [T, V]."""
    return _head(cfg, params, hidden(cfg, params, tokens, prec), prec)


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
            lg = _head(cfg, params, xs, prec)
            gold = jnp.take_along_axis(lg, ts[:, None], -1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(lg, -1) - gold)

        xs = x.reshape(T // rb, rb, -1)
        ts = tgt.reshape(T // rb, rb)
        return jnp.sum(jax.lax.map(lambda a: block(*a), (xs, ts)))

    total = jnp.sum(jax.lax.map(lambda a: seq_nll(*a), (inputs, targets)))
    return total / (B * T)
