"""Operations and bytes that the work of a cell needs, from its shapes.

The shares of a peak that the per-layer metrics report divide these counts
by measured time, so the counts are lower bounds of what the algorithm has
to do, never what one implementation happens to do:

* a dense projection costs 2 * in * out per token;
* causal attention costs 4 * head_dim * heads per (query, key) pair with
  the key not after the query, i.e. the half square and its diagonal;
* a tensor-train layer costs the least FLOPs over every order in which its
  network can be contracted (an exact search over subsets);
* training is forward plus backward: 3x for dense projections and
  attention; for a TT layer its forward network, its input-gradient
  network and the cheaper of its two core-gradient strategies, each at its
  own least cost;
* recomputation is never counted.
"""

from __future__ import annotations

import functools
import math

# -- tensor networks ----------------------------------------------------------


def least_flops(nodes: list[tuple[str, ...]], sizes: dict[str, int],
                output: tuple[str, ...]) -> int:
    """Least FLOPs to contract ``nodes`` (each a tuple of axis names) to
    ``output`` by pairwise contractions, over every contraction order.

    A pairwise contraction of A and B costs 2 * (product of the sizes of
    every axis of A or B) when an axis is summed, and half that (products
    only) when none is."""
    n = len(nodes)
    full = (1 << n) - 1
    node_axes = [frozenset(a) for a in nodes]
    out = frozenset(output)

    def axes_of(mask: int) -> frozenset:
        s = frozenset()
        for i in range(n):
            if mask >> i & 1:
                s |= node_axes[i]
        return s

    @functools.lru_cache(maxsize=None)
    def live(mask: int) -> frozenset:
        rest = axes_of(full & ~mask) | out
        return axes_of(mask) & rest

    @functools.lru_cache(maxsize=None)
    def best(mask: int) -> int:
        if mask & (mask - 1) == 0:
            return 0
        result = None
        sub = (mask - 1) & mask
        while sub:
            other = mask & ~sub
            if sub < other:
                a, b = live(sub), live(other)
                union = a | b
                summed = union - live(mask)
                cost = math.prod(sizes[x] for x in union) * (2 if summed else 1)
                total = best(sub) + best(other) + cost
                if result is None or total < result:
                    result = total
            sub = (sub - 1) & mask
        return result

    return best(full)


def factorize_dim(n: int, k: int) -> tuple[int, ...]:
    """``n`` split into ``k`` balanced factors, largest first."""
    primes, x, p = [], n, 2
    while p * p <= x:
        while x % p == 0:
            primes.append(p)
            x //= p
        p += 1
    if x > 1:
        primes.append(x)
    f = [1] * k
    for p in sorted(primes, reverse=True):
        i = min(range(k), key=lambda i: f[i])
        f[i] *= p
    return tuple(sorted(f, reverse=True))


def tt_network(m: int, n: int, rank: int, k: int):
    """Axes of the TT cores of ``W[m, n]`` and their sizes."""
    mo, ni = factorize_dim(m, k), factorize_dim(n, k)
    modes = [f"m{i}" for i in range(k)] + [f"n{j}" for j in range(k)]
    sizes = dict(zip(modes, mo + ni))
    d = 2 * k
    cores = []
    for i in range(d):
        ax = ([f"r{i}"] if i else []) + [modes[i]] + \
            ([f"r{i + 1}"] if i < d - 1 else [])
        cores.append(tuple(ax))
    for i in range(1, d):
        sizes[f"r{i}"] = rank
    return cores, sizes, modes[:k], modes[k:]


@functools.lru_cache(maxsize=None)
def tt_forward_flops(m: int, n: int, rank: int, k: int, tokens: int) -> int:
    cores, sizes, mo, ni = tt_network(m, n, rank, k)
    sizes = {**sizes, "b": tokens}
    return least_flops([("b", *ni)] + cores, sizes, ("b", *mo))


@functools.lru_cache(maxsize=None)
def tt_train_flops(m: int, n: int, rank: int, k: int, tokens: int) -> int:
    """Forward + input gradient + core gradients of one TT layer."""
    cores, sizes, mo, ni = tt_network(m, n, rank, k)
    sizes = {**sizes, "b": tokens}
    x, dy = ("b", *ni), ("b", *mo)
    fwd = least_flops([x] + cores, sizes, dy)
    bwd = least_flops([dy] + cores, sizes, x)
    indep = sum(least_flops([x, dy] + cores[:i] + cores[i + 1:], sizes,
                            cores[i]) for i in range(len(cores)))
    dw = least_flops([x, dy], sizes, tuple(mo) + tuple(ni))
    shared = dw + sum(least_flops([tuple(mo) + tuple(ni)] + cores[:i]
                                  + cores[i + 1:], sizes, cores[i])
                      for i in range(len(cores)))
    return fwd + bwd + min(indep, shared)


# -- the dense TT decoder -----------------------------------------------------


def model_sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=h,
                KV=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // h), F=cfg["intermediate_size"],
                V=cfg["vocab_size"], rank=cfg["tnn"]["rank"],
                k=cfg["tnn"]["num_factors"])


def attention_pairs(lengths_before: int, new: int) -> int:
    """(query, key) pairs, key not after query, for ``new`` queries that
    follow ``lengths_before`` cached keys."""
    return new * lengths_before + new * (new + 1) // 2


def forward_flops(cfg: dict, tokens: int, pairs: int) -> dict:
    """Forward FLOPs of ``tokens`` tokens through every layer and the
    head, with ``pairs`` attention pairs in each layer, by part."""
    s = model_sizes(cfg)
    L, d, H, KV, hd, F, V = (s[x] for x in ("L", "d", "H", "KV", "hd", "F", "V"))
    proj = 2 * tokens * (d * H * hd * 2 + d * KV * hd * 2)
    attn = 4 * hd * H * pairs
    mlp = (2 * tt_forward_flops(F, d, s["rank"], s["k"], tokens)
           + tt_forward_flops(d, F, s["rank"], s["k"], tokens))
    return {"proj": L * proj, "attn": L * attn, "mlp": L * mlp,
            "head": 2 * tokens * d * V}


def train_step_flops(cfg: dict, batch: int, seq: int) -> int:
    """FLOPs one training step needs: forward and backward, no recompute."""
    s = model_sizes(cfg)
    tokens = batch * seq
    f = forward_flops(cfg, tokens, batch * attention_pairs(0, seq))
    mlp = s["L"] * (2 * tt_train_flops(s["F"], s["d"], s["rank"], s["k"], tokens)
                    + tt_train_flops(s["d"], s["F"], s["rank"], s["k"], tokens))
    return 3 * (f["proj"] + f["attn"] + f["head"]) + mlp


def weight_count(cfg: dict) -> int:
    """Parameters the forward pass reads (embedding rows aside)."""
    s = model_sizes(cfg)
    L, d, H, KV, hd, F, V = (s[x] for x in ("L", "d", "H", "KV", "hd", "F", "V"))

    def tt(m, n):
        cores, sizes, _, _ = tt_network(m, n, s["rank"], s["k"])
        return sum(math.prod(sizes[a] for a in c) for c in cores)

    per_layer = (d * H * hd * 2 + d * KV * hd * 2 + 2 * tt(F, d) + tt(d, F)
                 + 2 * d)
    return L * per_layer + d * V + d


def serve_call_least_s(cfg: dict, peak: dict, new_tokens: list[int],
                       cached: list[int]) -> float:
    """Least time of one serving call (a decode or a prefill chunk) in
    which slot i adds ``new_tokens[i]`` tokens after ``cached[i]`` cached
    ones: the larger of its FLOPs at the peak rate and the bytes it must
    move (every weight once, every live key and value once, one byte each)
    at the peak bandwidth."""
    s = model_sizes(cfg)
    tokens = sum(new_tokens)
    if tokens == 0:
        return 0.0
    pairs = sum(attention_pairs(c, t) for c, t in zip(cached, new_tokens))
    flops = sum(forward_flops(cfg, tokens, pairs).values())
    kv = 2 * s["L"] * s["KV"] * s["hd"] * sum(
        c + t for c, t in zip(cached, new_tokens) if t)
    nbytes = weight_count(cfg) + kv
    return max(flops / peak["flops_s"], nbytes / peak["hbm_bytes_s"])


def flash_call(batch: int, seq: int, heads: int, kv_heads: int, hd: int
               ) -> tuple[int, int]:
    """(FLOPs, bytes) one causal flash-attention forward needs: the half
    square of QK^T and PV, and q, k, v read and out written in bf16 with
    the f32 log-sum-exp."""
    flops = 4 * hd * heads * batch * attention_pairs(0, seq)
    nbytes = 2 * batch * seq * hd * (2 * heads + 2 * kv_heads) \
        + 4 * batch * heads * seq
    return flops, nbytes
