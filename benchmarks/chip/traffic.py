"""Serving traffic from a mix file's parameters and a seed.

Every seed gets the same set of prompt lengths, output lengths and
inter-arrival gaps (stratified quantiles of the mix's distributions), in
an order drawn from the seed, with prompt tokens drawn from the seed.  So
two seeds do the same amount of work, and a seed changes only which
request comes when and what it says.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Spec:
    idx: int
    due_s: float          # open loop: offset from the window's start
    prompt: np.ndarray
    max_new: int
    temperature: float


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a clipped lognormal (or a fixed value)."""
    if dist["dist"] == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def count(mix: dict, seconds: float) -> int:
    """How many requests a run of ``seconds`` draws."""
    arr = mix["arrival"]
    if arr["kind"] == "poisson":
        return max(1, math.ceil(arr["rate_per_s"] * seconds))
    return arr["clients"] * arr["requests_per_client"]


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list[Spec]:
    """The run's requests, in the order they are offered."""
    n = count(mix, seconds)
    rng = np.random.default_rng([seed, 7])
    plen = rng.permutation(lengths(mix["prompt_len"], n))
    olen = rng.permutation(lengths(mix["output_len"], n))
    arr = mix["arrival"]
    if arr["kind"] == "poisson":
        gaps = -np.log1p(-_quantiles(n)) / arr["rate_per_s"]
        due = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])
    else:
        due = np.zeros(n)
    every = mix.get("greedy_every", 1)
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, size=int(plen[i]), dtype=np.int32)
        temp = 0.0 if i % every == 0 else float(mix["temperature"])
        out.append(Spec(i, float(due[i]), toks, int(olen[i]), temp))
    return out
