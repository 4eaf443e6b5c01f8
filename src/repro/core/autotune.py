"""Measurement-driven autotuner — calibrates the planning stack against
the real Pallas lowering.

The paper's stage-2 reranks contraction sequences with a cycle-accurate
model of the target hardware (§IV, §VI-C).  Our ``perf_model`` is an
analytic roofline that had never been checked against what
``plan_compiler`` actually emits.  This module closes that measure→model
loop.  Since PR 7 the tuner is configured from the unified
:class:`repro.core.policy.ExecutionPolicy` (its *tile axis*:
``tile_sweep`` grid + ``sweep_strategy``) — build one with
:meth:`Tuner.from_policy`, price a plan under a policy with
:meth:`Tuner.plan_latency_policy`:

* **Sweep** — for each lowered GEMM / chain step shape, time real
  ``matmul_pallas`` / ``chain_pallas`` executions over the policy's grid
  of tile sizes (``block_m/n/k``), plus the fuse-vs-no-fuse decision for
  chain candidates (measured chain against the measured two-GEMM split).
  ``sweep_strategy="full"`` times every candidate;
  ``"halving"`` is the successive-halving sweep the joint planner
  (:mod:`repro.core.search`) uses — a utilisation-ranked seed set is
  timed cheaply, survivors re-timed at higher fidelity, cutting timed
  trials per shape by ~2x with the same winner in practice
  (docs/SEARCH.md).  ``stats["trials"]`` counts every timed config — the
  measurement-count currency ``bench_search.py`` gates on.  On CPU hosts
  the kernels run in interpret mode — wall times then measure the
  interpreter, which is still the honest cost of *this* backend and is
  what CI exercises; on a TPU the same sweep times compiled kernels.

* **Cache** — results persist in a content-addressed on-disk cache (same
  sha256-of-JSON signature scheme as the CSSE memo), keyed by (op kind,
  dims, transpose, dtype, quantization-policy tag, phase, tile grid,
  sweep strategy, jax backend, device kind, device count, interpret,
  ``SWEEP_VERSION``).  Tuning is paid once per key: a second invocation
  is a 100% cache hit and re-measures nothing.  ``REPRO_AUTOTUNE_CACHE``
  relocates the cache directory (tests point it at a tmpdir).  The
  learned cost model of :mod:`repro.core.search` is fit *from* this DB
  and persists alongside it, invalidated by the same ``SWEEP_VERSION``.

* **Feedback** — :class:`CalibratedModel` prices a ``ContractionPlan`` by
  compiling it (tile choices and fuse decisions from the cache) and summing
  measured step costs, falling back to the analytic roofline for steps that
  were skipped (too big to measure) or lowered to the einsum fallback.
  ``csse.search`` with an ExecutionPolicy whose ``objective="measured"``
  (or the legacy ``SearchOptions`` view) reranks stage-2 candidates with
  it instead of the analytic model.

Entry points: :func:`default_tuner` (process-wide singleton used when a
``Tuner`` isn't passed explicitly), ``Tuner.plan_latency`` /
``CalibratedModel.evaluate`` for costing, ``compare_plan`` for the
calibration report (:mod:`repro.analysis.calibrate`).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import hashlib
import itertools
import json
import math
import os
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro import telemetry as tm
from repro.core import perf_model
from repro.core.plan_compiler import (
    ChainOp, CompiledPlan, GemmOp, TileConfig, compile_plan,
)
from repro.core.tnetwork import ContractionPlan
from repro.kernels.fused_contraction import (
    CHAIN_VMEM_BUDGET_BYTES, INTERPRET, chain_n_pallas, chain_vmem_bytes,
    chain_plan, matmul_pallas,
)

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_DEFAULT_CACHE_DIR = os.path.join(os.path.dirname(__file__), "..", "..",
                                  "..", ".cache", "autotune")

# Bump to invalidate every cached measurement (sweep or timing change).
# v2: device count entered the signature (multi-device hosts time kernels
# under a different runtime than single-device ones; sharded runs must not
# be served single-device entries).
# v3: quantization policy entered the signature and the sweep — quantized
# step shapes time the fp8/int8 scaled kernels (different operand dtypes,
# scale-epilogue inputs), so a bf16 entry must never be served to a
# quantized run nor vice versa.
# v4: execution phase entered the signature — serving's phase-specialized
# profiles (prefill vs decode) tune and cache their own tile winners.
# v5: tile grid + sweep strategy entered the signature — halving-tuned
# winners and custom grids (ExecutionPolicy.tile_sweep) must not collide
# with full-sweep entries, and the learned cost model fit from this DB
# (core/search.py) invalidates with it.
# v6: chain keys generalized from the pairwise ``(m, k, h, n)`` to the
# flat N-ary ``(m0, k1, n1, ..., kL, nL)`` (``ChainOp.dims``) — the two
# formats would alias, and v5 chain entries describe a kernel the
# regroup-capable ``chain_n_pallas`` no longer dispatches verbatim.
SWEEP_VERSION = 6


# ---------------------------------------------------------------------------
# Step shapes and analytic fallbacks
# ---------------------------------------------------------------------------


def _chain_links(dims: tuple[int, ...]
                 ) -> tuple[int, tuple[tuple[int, int], ...]]:
    """Parse a flat chain key ``(m0, k1, n1, ..., kL, nL)`` into
    ``(m0, ((k1, n1), ...))``."""
    if len(dims) < 5 or len(dims) % 2 == 0:
        raise ValueError(f"bad chain dims {dims}: want (m0, k1, n1, ..., "
                         "kL, nL)")
    return dims[0], tuple((dims[i], dims[i + 1])
                          for i in range(1, len(dims), 2))


@dataclass(frozen=True)
class StepShape:
    """The tuning key of one lowered op, before backend/device qualifiers.

    ``dims`` is ``(m, n, k)`` for a GEMM and the flat
    ``(m0, k1, n1, ..., kL, nL)`` (``ChainOp.dims``) for a fused chain —
    unambiguous for any length, regroup factors implied by the (k, n)
    pairs.  ``policy`` is the quantization tag (``QuantPolicy.tag``, e.g.
    ``"fp8_e4m3/tensor"``; empty = unquantized): quantized shapes sweep
    the scaled kernels over fp8/int8 operands, and the tag keys the cache
    so bf16 winners are never served to quantized runs.
    """

    kind: str                           # "gemm" | "chain"
    dims: tuple[int, ...]
    transpose_rhs: bool = False         # gemm only
    dtype: str = "float32"
    policy: str = ""                    # QuantPolicy.tag ("" = unquantized)
    phase: str = ""                     # execution phase ("" = training;
                                        # "prefill"/"decode" for serving's
                                        # phase-specialized profiles) — keys
                                        # the cache so each phase tunes its
                                        # own tile winners

    def quant_policy(self):
        if not self.policy:
            return None
        from repro.precision.policy import QuantPolicy
        return QuantPolicy.from_tag(self.policy)

    def elems(self) -> int:
        """Total operand+result elements — the measurement size guard."""
        if self.kind == "gemm":
            m, n, k = self.dims
            return m * k + k * n + m * n
        m0, links = _chain_links(self.dims)
        rows, _ = chain_plan(m0, links)
        weights = sum(k * n for k, n in links)
        inters = sum(r * n for r, (_, n) in zip(rows, links[:-1]))
        return m0 * links[0][0] + weights + inters + rows[-1] * links[-1][1]


def analytic_gemm_s(m: int, n: int, k: int,
                    hw: perf_model.HardwareModel = perf_model.TPU_V5E
                    ) -> float:
    """Roofline latency of one ``C[M,N] = A[M,K] @ B[K,N]`` step."""
    compute = 2 * m * n * k / (hw.peak_flops * hw.mxu_utilisation(m, n, k))
    memory = (m * k + k * n + m * n) * hw.dtype_bytes / hw.hbm_bw
    return max(compute, memory) + hw.step_overhead_s


def analytic_chain_s(*dims: int,
                     hw: perf_model.HardwareModel = perf_model.TPU_V5E
                     ) -> float:
    """Roofline latency of a fused chain whose intermediates never
    round-trip HBM.

    Accepts either the legacy pairwise form ``(m, k, h, n)`` for
    ``(X[m,k] @ A[k,h]) @ B[h,n]`` or the flat N-ary key
    ``(m0, k1, n1, ..., kL, nL)`` — the legacy form is exactly the flat
    ``(m, k, h, h, n)``."""
    if len(dims) == 4:
        m, k, h, n = dims
        dims = (m, k, h, h, n)
    m0, links = _chain_links(tuple(dims))
    rows, _ = chain_plan(m0, links)
    compute = sum(
        2 * r * n_i * k_i / (hw.peak_flops * hw.mxu_utilisation(r, n_i, k_i))
        for r, (k_i, n_i) in zip(rows, links))
    hbm_elems = (m0 * links[0][0] + sum(k * n for k, n in links)
                 + rows[-1] * links[-1][1])
    memory = hbm_elems * hw.dtype_bytes / hw.hbm_bw
    return max(compute, memory) + hw.step_overhead_s


def analytic_step_s(shape: StepShape,
                    hw: perf_model.HardwareModel = perf_model.TPU_V5E
                    ) -> float:
    if shape.kind == "gemm":
        return analytic_gemm_s(*shape.dims, hw=hw)
    return analytic_chain_s(*shape.dims, hw=hw)


# ---------------------------------------------------------------------------
# Tune records
# ---------------------------------------------------------------------------


@dataclass
class TuneRecord:
    """Outcome of tuning one :class:`StepShape` on one backend/device."""

    shape: StepShape
    best: TileConfig                    # winning tiles (defaults if skipped)
    best_s: float                       # measured wall s (inf when skipped)
    analytic_s: float                   # roofline prediction for the shape
    measured: bool                      # False => size guard skipped timing
    trials: list[dict] = field(default_factory=list)
    source: str = "measured"            # measured | memo | disk

    @property
    def latency_s(self) -> float:
        """What the calibrated model charges: measured, else analytic."""
        return self.best_s if self.measured else self.analytic_s

    def to_json(self) -> dict:
        return {
            "kind": self.shape.kind, "dims": list(self.shape.dims),
            "transpose_rhs": self.shape.transpose_rhs,
            "dtype": self.shape.dtype,
            "policy": self.shape.policy,
            "phase": self.shape.phase,
            "best": [self.best.block_m, self.best.block_n,
                     self.best.block_k],
            "best_s": self.best_s, "analytic_s": self.analytic_s,
            "measured": self.measured, "trials": self.trials,
        }

    @classmethod
    def from_json(cls, d: dict) -> "TuneRecord":
        shape = StepShape(kind=d["kind"], dims=tuple(d["dims"]),
                          transpose_rhs=d["transpose_rhs"],
                          dtype=d["dtype"], policy=d.get("policy", ""),
                          phase=d.get("phase", ""))
        bm, bn, bk = d["best"]
        return cls(shape=shape,
                   best=TileConfig(block_m=bm, block_n=bn, block_k=bk),
                   best_s=d["best_s"], analytic_s=d["analytic_s"],
                   measured=d["measured"], trials=list(d["trials"]),
                   source="disk")


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------


def _dedupe_tile_candidates(cands, effective):
    """Drop candidates whose *effective* (clamped) tiles coincide."""
    seen, out = set(), []
    for c in cands:
        key = effective(c)
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


class Tuner:
    """Times real Pallas executions per step shape and caches the winners.

    One instance per process is enough (see :func:`default_tuner`); the
    disk cache makes tuning persistent across processes and the in-process
    memo makes repeated lookups free.  ``stats`` counts where answers came
    from: ``measured`` (shapes timed now), ``disk_hits``, ``memo_hits``,
    ``skipped`` (size guard → analytic fallback), and ``trials`` — every
    individual (shape, tile config) timing performed, the measurement
    count ``bench_search.py`` compares strategies on.

    ``tile_sweep`` / ``sweep_strategy`` are the ExecutionPolicy tile axis:
    the grid of candidate block sizes and how it is searched (``"full"``
    times every deduped candidate, ``"halving"`` successive-halves a
    utilisation-ranked seed set).  Both enter :meth:`signature`, so tuners
    with different grids or strategies never share cache entries.
    """

    #: tile sizes swept per GEMM dim (clamped to the dim by the kernel)
    TILE_SWEEP = (128, 256, 512)

    def __init__(self, hw: perf_model.HardwareModel = perf_model.TPU_V5E,
                 cache_dir: str | None = None, iters: int = 2,
                 warmup: int = 1, max_measure_elems: int = 1 << 22,
                 max_configs: int = 27, interpret: bool | None = None,
                 tile_sweep: tuple[int, ...] | None = None,
                 sweep_strategy: str = "full"):
        if sweep_strategy not in ("full", "halving"):
            raise ValueError(f"unknown sweep_strategy {sweep_strategy!r}")
        self.hw = hw
        self._cache_dir = cache_dir
        self.iters = iters
        self.warmup = warmup
        self.max_measure_elems = max_measure_elems
        self.max_configs = max_configs
        self.interpret = INTERPRET if interpret is None else interpret
        self.tile_sweep = tuple(tile_sweep) if tile_sweep else self.TILE_SWEEP
        self.sweep_strategy = sweep_strategy
        self._memo: dict[str, TuneRecord] = {}
        self.stats = {"measured": 0, "disk_hits": 0, "memo_hits": 0,
                      "skipped": 0, "trials": 0}

    @classmethod
    def from_policy(cls, policy, hw: perf_model.HardwareModel | None = None,
                    **kwargs) -> "Tuner":
        """Build a tuner from an ExecutionPolicy's tile axis."""
        return cls(hw=hw or perf_model.TPU_V5E,
                   tile_sweep=policy.tile_sweep,
                   sweep_strategy=policy.sweep_strategy, **kwargs)

    # -- cache plumbing -----------------------------------------------------

    @property
    def cache_dir(self) -> str:
        return (self._cache_dir
                or os.environ.get(_CACHE_ENV, _DEFAULT_CACHE_DIR))

    def signature(self, shape: StepShape) -> str:
        payload = {
            "kind": shape.kind, "dims": shape.dims,
            "transpose_rhs": shape.transpose_rhs, "dtype": shape.dtype,
            "policy": shape.policy,
            "phase": shape.phase,
            "backend": jax.default_backend(),
            "device": jax.devices()[0].device_kind,
            "num_devices": jax.device_count(),
            "interpret": self.interpret,
            "sweep": SWEEP_VERSION,
            "grid": self.tile_sweep,
            "strategy": self.sweep_strategy,
        }
        return hashlib.sha256(
            json.dumps(payload, default=str).encode()).hexdigest()

    def _disk_load(self, sig: str) -> TuneRecord | None:
        path = os.path.join(self.cache_dir, sig + ".json")
        try:
            with open(path) as f:
                return TuneRecord.from_json(json.load(f))
        except (OSError, ValueError, KeyError, TypeError):
            return None

    def _disk_store(self, sig: str, rec: TuneRecord) -> None:
        try:
            os.makedirs(self.cache_dir, exist_ok=True)
            path = os.path.join(self.cache_dir, sig + ".json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(rec.to_json(), f)
            os.replace(tmp, path)
        except OSError:
            pass

    def clear_memo(self) -> None:
        self._memo.clear()

    # -- measurement --------------------------------------------------------

    def _time(self, fn, iters: int | None = None,
              warmup: int | None = None) -> float:
        self.stats["trials"] += 1
        tm.inc("autotune.trials")
        iters = self.iters if iters is None else iters
        warmup = self.warmup if warmup is None else warmup
        for _ in range(warmup):
            fn().block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            fn().block_until_ready()
        return (time.perf_counter() - t0) / iters

    def _operands(self, shape: StepShape):
        pol = shape.quant_policy()
        if pol is not None:
            return self._quant_operands(shape, pol)
        dtype = jnp.dtype(shape.dtype)
        key = jax.random.key(0)
        if shape.kind == "gemm":
            m, n, k = shape.dims
            kx, kw = jax.random.split(key)
            x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
            wshape = (n, k) if shape.transpose_rhs else (k, n)
            w = jax.random.normal(kw, wshape, jnp.float32).astype(dtype)
            return x, w
        m0, links = _chain_links(shape.dims)
        keys = jax.random.split(key, 1 + len(links))
        x = jax.random.normal(keys[0], (m0, links[0][0]),
                              jnp.float32).astype(dtype)
        ws = [jax.random.normal(kw, (k, n), jnp.float32).astype(dtype)
              for kw, (k, n) in zip(keys[1:], links)]
        return (x, *ws)

    def _quant_operands(self, shape: StepShape, pol):
        """Quantized operands + the scale vectors the scaled kernels take —
        the sweep must time exactly the dispatch the quantized executor
        performs (epilogue inputs included)."""
        from repro.precision import quant as _q
        key = jax.random.key(0)
        if shape.kind == "gemm":
            m, n, k = shape.dims
            kx, kw = jax.random.split(key)
            qx = _q.quantize(jax.random.normal(kx, (m, k), jnp.float32), pol)
            wshape = (n, k) if shape.transpose_rhs else (k, n)
            qw = _q.quantize(jax.random.normal(kw, wshape, jnp.float32), pol,
                             scale=jnp.float32(1.0))
            sr = jnp.full((1, n), qw.scale, jnp.float32)
            return qx.q, qw.q, qx.row_scales(), sr
        m0, links = _chain_links(shape.dims)
        keys = jax.random.split(key, 1 + len(links))
        qx = _q.quantize(jax.random.normal(keys[0], (m0, links[0][0]),
                                           jnp.float32), pol)
        qws = [_q.quantize(jax.random.normal(kw, (k, n), jnp.float32), pol,
                           scale=jnp.float32(1.0))
               for kw, (k, n) in zip(keys[1:], links)]
        s_first = qx.row_scales() * qws[0].scale
        mids = [jnp.full((1, 1), q.scale, jnp.float32) for q in qws[1:-1]]
        s_last = jnp.full((1, links[-1][1]), qws[-1].scale, jnp.float32)
        return (qx.q, *(q.q for q in qws), s_first, *mids, s_last)

    def _candidates(self, shape: StepShape) -> list[TileConfig]:
        if shape.kind == "gemm":
            m, n, k = shape.dims
            raw = itertools.product(self.tile_sweep, self.tile_sweep,
                                    self.tile_sweep)
            cands = [TileConfig(block_m=a, block_n=b, block_k=c)
                     for a, b, c in raw]
            eff = lambda t: (min(t.block_m, m), min(t.block_n, n),  # noqa: E731
                             min(t.block_k, k))
        else:
            m0, links = _chain_links(shape.dims)
            rows, _ = chain_plan(m0, links)
            m, n = rows[-1], links[-1][1]
            raw = itertools.product(self.tile_sweep, self.tile_sweep)
            cands = [TileConfig(block_m=a, block_n=b) for a, b in raw]
            # chain tiles must respect the kernel's VMEM budget check
            cands = [t for t in cands
                     if chain_vmem_bytes(m0, links, t.block_m, t.block_n)
                     <= CHAIN_VMEM_BUDGET_BYTES]
            eff = lambda t: (min(t.block_m, m), min(t.block_n, n))  # noqa: E731
        cands = _dedupe_tile_candidates(cands, eff)
        if len(cands) > self.max_configs:
            # Truncate round-robin across block_m groups (product order
            # would keep only the smallest block_m values).
            groups: dict[int, list[TileConfig]] = {}
            for t in cands:
                groups.setdefault(t.block_m, []).append(t)
            interleaved = [t for tiles in itertools.zip_longest(
                *groups.values()) for t in tiles if t is not None]
            cands = interleaved[:self.max_configs]
        return cands or [TileConfig()]

    def _run_config(self, shape: StepShape, tiles: TileConfig, operands):
        if shape.kind == "gemm":
            x, w, *scales = operands

            def call():
                return matmul_pallas(
                    x, w, transpose_rhs=shape.transpose_rhs,
                    block_m=tiles.block_m, block_n=tiles.block_n,
                    block_k=tiles.block_k, interpret=self.interpret,
                    scales=tuple(scales) or None)
        else:
            _, links = _chain_links(shape.dims)
            x, *rest = operands
            ws, scales = rest[:len(links)], rest[len(links):]

            def call():
                return chain_n_pallas(
                    x, ws, block_m=tiles.block_m, block_n=tiles.block_n,
                    interpret=self.interpret, scales=tuple(scales) or None)
        # Always jit (also in interpret mode): measurement may run at trace
        # time under ensure_compile_time_eval, where a bare pallas_call has
        # no evaluation rule; the warmup iteration absorbs compile time.
        return jax.jit(call)

    def _measure(self, shape: StepShape) -> TuneRecord:
        # Quantized shapes get a byte-repriced analytic prediction (and
        # fallback) — the roofline must describe the same dispatch the
        # sweep times.
        analytic = analytic_step_s(
            shape, perf_model.apply_policy(self.hw, shape.quant_policy()))
        if shape.elems() > self.max_measure_elems:
            self.stats["skipped"] += 1
            tm.inc("autotune.skipped")
            return TuneRecord(shape=shape, best=TileConfig(),
                              best_s=math.inf, analytic_s=analytic,
                              measured=False, trials=[], source="measured")
        # Tuning often fires at trace time (CSSE searches run inside a
        # jitted train step).  jax trace contexts are thread-local, so the
        # sweep always runs on a worker thread, where the timed kernels
        # execute for real instead of being staged into the outer trace.
        # Tracer context is thread-local too: hand the caller's span
        # across so the sweep parents under csse.stage2 (or whoever asked).
        ctx = tm.current_context()

        def job():
            with tm.attach(ctx):
                with tm.span("autotune.sweep", kind=shape.kind,
                             dims=list(shape.dims), dtype=shape.dtype):
                    return self._sweep(shape)

        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            best, best_s, trials = pool.submit(job).result()
        self.stats["measured"] += 1
        tm.inc("autotune.measured")
        if math.isfinite(best_s):
            tm.drift("autotune.step", predicted_s=analytic,
                     measured_s=best_s, kind=shape.kind,
                     dims=list(shape.dims))
        return TuneRecord(shape=shape, best=best, best_s=best_s,
                          analytic_s=analytic, measured=True, trials=trials,
                          source="measured")

    def _sweep(self, shape: StepShape):
        operands = self._operands(shape)
        cands = self._candidates(shape)
        # Halving only pays when the grid is big enough for its seed round
        # to prune anything; on clamped grids (small dims collapse the
        # candidate set) it would cost MORE than timing every config once.
        if (self.sweep_strategy == "halving"
                and len(cands) > self.HALVING_SEED):
            return self._sweep_halving(shape, cands, operands)
        trials = []
        best, best_s = None, math.inf
        for tiles in cands:
            wall = self._time(self._run_config(shape, tiles, operands))
            trials.append({"tiles": [tiles.block_m, tiles.block_n,
                                     tiles.block_k], "wall_s": wall})
            if wall < best_s:
                best, best_s = tiles, wall
        return best, best_s, trials

    #: halving sweep: seed-set size and per-round survivor fraction
    HALVING_SEED = 9
    HALVING_ETA = 3

    def _sweep_halving(self, shape: StepShape, cands, operands):
        """Successive-halving tile sweep — fewer timed trials per shape.

        Candidates are pre-ranked by effective tile coverage (larger
        clamped tiles → fewer grid steps → less launch overhead, until
        VMEM caps them — the same monotone prior the full sweep's winners
        show), the top :data:`HALVING_SEED` are timed at low fidelity
        (1 iteration), and each round keeps the fastest ``1/HALVING_ETA``
        and re-times them with one extra iteration.  9 → 3 → 1 costs 13
        trials against the full sweep's up-to-27, and every trial still
        goes through :meth:`_time` so ``stats["trials"]`` stays the
        comparable currency.
        """
        if shape.kind == "gemm":
            dims = shape.dims
        else:
            m0, links = _chain_links(shape.dims)
            dims = (chain_plan(m0, links)[0][-1], links[-1][1])

        def coverage(t: TileConfig) -> int:
            if shape.kind == "gemm":
                m, n, k = dims
                return (min(t.block_m, m) * min(t.block_n, n)
                        * min(t.block_k, k))
            m, n = dims
            return min(t.block_m, m) * min(t.block_n, n)

        survivors = sorted(cands, key=coverage,
                           reverse=True)[:self.HALVING_SEED]
        trials = []
        rung = 0
        walls: dict[TileConfig, float] = {}
        while True:
            iters = min(self.iters, 1 + rung)
            for tiles in survivors:
                wall = self._time(
                    self._run_config(shape, tiles, operands), iters=iters)
                walls[tiles] = wall
                trials.append({"tiles": [tiles.block_m, tiles.block_n,
                                         tiles.block_k], "wall_s": wall,
                               "rung": rung})
            if len(survivors) == 1:
                break
            survivors = sorted(survivors, key=walls.__getitem__)[
                :max(1, len(survivors) // self.HALVING_ETA)]
            rung += 1
        best = survivors[0]
        return best, walls[best], trials

    # -- lookup (memo -> disk -> measure) -----------------------------------

    def record(self, shape: StepShape) -> TuneRecord:
        sig = self.signature(shape)
        rec = self._memo.get(sig)
        if rec is not None:
            self.stats["memo_hits"] += 1
            tm.inc("autotune.memo_hits")
            return rec
        rec = self._disk_load(sig)
        if rec is not None:
            self.stats["disk_hits"] += 1
            tm.inc("autotune.disk_hits")
            self._memo[sig] = rec
            return rec
        rec = self._measure(shape)
        self._memo[sig] = rec
        if rec.measured:
            # Skipped records (size guard) stay memo-only: the skip decision
            # is free to recompute and depends on max_measure_elems, which
            # the signature deliberately does not key on — persisting would
            # pin the analytic fallback even after the budget is raised.
            self._disk_store(sig, rec)
        return rec

    # -- the protocol compile_plan consumes ---------------------------------

    def gemm_tiles(self, m: int, n: int, k: int, *, transpose_rhs: bool,
                   dtype: str, policy: str = "",
                   phase: str = "") -> TileConfig:
        return self.record(StepShape("gemm", (m, n, k),
                                     transpose_rhs=transpose_rhs,
                                     dtype=dtype, policy=policy,
                                     phase=phase)).best

    def chain_tiles(self, m: int, k: int, h: int, n: int, *,
                    dtype: str, policy: str = "",
                    phase: str = "") -> TileConfig:
        """Legacy pairwise protocol — the fixed-M two-step chain
        ``(m, k, h, n)`` is the flat key ``(m, k, h, h, n)``."""
        return self.chain_n_tiles((m, k, h, h, n), dtype=dtype,
                                  policy=policy, phase=phase)

    def chain_n_tiles(self, dims: tuple[int, ...], *, dtype: str,
                      policy: str = "", phase: str = "") -> TileConfig:
        """Tile winner for an N-ary chain keyed by ``ChainOp.dims``."""
        return self.record(StepShape("chain", tuple(dims),
                                     dtype=dtype, policy=policy,
                                     phase=phase)).best

    def should_fuse(self, m: int, k: int, h: int, n: int, *, dtype: str,
                    transpose_rhs1: bool = False,
                    transpose_rhs2: bool = False,
                    policy: str = "", phase: str = "") -> bool:
        """Legacy pairwise fuse decision — see :meth:`should_fuse_n`."""
        return self.should_fuse_n(
            (m, k, h, h, n), dtype=dtype,
            transpose_rhs=(transpose_rhs1, transpose_rhs2),
            policy=policy, phase=phase)

    def should_fuse_n(self, dims: tuple[int, ...], *, dtype: str,
                      transpose_rhs: tuple[bool, ...] = (),
                      policy: str = "", phase: str = "") -> bool:
        """Measured fuse decision: chain vs the per-link GEMM split.

        ``transpose_rhs`` holds the split GemmOps' actual VMEM-flip flags,
        so the comparison times exactly the kernels the unfused path would
        dispatch (and reuses their ``gemm_tiles`` cache entries).
        Unmeasured shapes (size guard) keep the structural default (fuse),
        matching what CSSE stage-2 models as ``fused_chain=True``.
        """
        dims = tuple(dims)
        m0, links = _chain_links(dims)
        rows, _ = chain_plan(m0, links)
        chain = self.record(StepShape("chain", dims, dtype=dtype,
                                      policy=policy, phase=phase))
        if not transpose_rhs:
            transpose_rhs = (False,) * len(links)
        gemms = [self.record(StepShape("gemm", (r, n_i, k_i),
                                       transpose_rhs=tr, dtype=dtype,
                                       policy=policy, phase=phase))
                 for r, (k_i, n_i), tr in zip(rows, links, transpose_rhs)]
        if not (chain.measured and all(g.measured for g in gemms)):
            return True
        return chain.best_s <= sum(g.best_s for g in gemms)

    # -- plan-level costing --------------------------------------------------

    def op_latency(self, op, sizes, dtype: str = "float32",
                   policy_tag: str = "", phase: str = "",
                   hw: perf_model.HardwareModel | None = None
                   ) -> tuple[float, bool]:
        """(seconds, measured?) for one lowered op."""
        if isinstance(op, GemmOp):
            rec = self.record(StepShape(
                "gemm", (op.mat.m, op.mat.n, op.mat.k),
                transpose_rhs=op.mat.transpose_rhs, dtype=dtype,
                policy=policy_tag, phase=phase))
            return rec.latency_s, rec.measured
        if isinstance(op, ChainOp):
            rec = self.record(StepShape(
                "chain", op.dims, dtype=dtype,
                policy=policy_tag, phase=phase))
            return rec.latency_s, rec.measured
        cost = perf_model.evaluate_step(op.step, sizes, hw or self.hw)
        return cost.latency_s, False

    def plan_latency(self, plan: ContractionPlan, *,
                     fused_chain: bool = True, max_chain_len: int = 2,
                     dtype: str = "float32",
                     mesh: perf_model.MeshSpec | None = None,
                     policy=None, phase: str = "") -> float:
        """Total measured latency of a plan's compiled lowering.

        Steps the size guard skipped and einsum-fallback steps are charged
        at the analytic roofline — the "fall back to perf_model for
        unmeasured steps" contract of ``objective="measured"``.

        With ``mesh``, compilation and measurement happen at the *per-shard*
        step shapes every device actually runs (so tile winners and fuse
        decisions are tuned for the sharded kernels), and the deferred-psum
        collective term is added analytically — ICI transfers cannot be
        timed on a single host, so communication stays model-priced exactly
        as in :func:`perf_model.evaluate`, same byte convention included
        (``hw.dtype_bytes``, like every HBM term in the model): the two
        objectives must rank a given plan's collective identically.

        With ``policy``, the sweep times the *quantized* kernels (fp8/int8
        operands, scale epilogues) under policy-qualified cache keys, the
        analytic fallback and the collective term both reprice at the
        policy's byte width — the measured half of the precision-aware
        stage 2.
        """
        hw = perf_model.apply_policy(self.hw, policy)
        ptag = "" if policy is None or not policy.quantized else policy.tag
        coll = perf_model.collective_cost(plan, mesh, hw)
        plan = perf_model.localize_plan(plan, mesh)
        compiled = compile_plan(plan, fuse=fused_chain,
                                max_chain_len=max_chain_len, tuner=self,
                                dtype=dtype, policy=policy, phase=phase)
        sizes = plan.network.sizes
        return coll.latency_s + sum(
            self.op_latency(op, sizes, dtype, policy_tag=ptag, phase=phase,
                            hw=hw)[0]
            for op in compiled.ops)

    def plan_latency_policy(self, plan: ContractionPlan, policy) -> float:
        """:meth:`plan_latency` with every axis read off one
        :class:`repro.core.policy.ExecutionPolicy`."""
        return self.plan_latency(
            plan, fused_chain=policy.fused_chain,
            max_chain_len=policy.max_chain_len,
            dtype=policy.measure_dtype, mesh=policy.mesh,
            policy=policy.quant_policy, phase=policy.phase)


# ---------------------------------------------------------------------------
# CSSE stage-2 adapter
# ---------------------------------------------------------------------------


@dataclass
class CalibratedModel:
    """Stage-2 cost model backed by measurements instead of the roofline.

    ``evaluate`` mirrors :func:`perf_model.evaluate`'s shape: the returned
    :class:`perf_model.PlanCost` carries the *measured* latency (energy and
    byte counts stay analytic — we do not measure joules).  With ``mesh``
    set, measured step costs come from the per-shard lowering and the
    collective term is the analytic deferred-psum price — the
    communication-aware ``objective="measured"``.
    """

    tuner: Tuner
    hw: perf_model.HardwareModel = perf_model.TPU_V5E
    dtype: str = "float32"
    mesh: perf_model.MeshSpec | None = None
    policy: object = None        # QuantPolicy: time the quantized kernels
    phase: str = ""              # phase-qualified measurement cache keys

    def latency(self, plan: ContractionPlan,
                fused_chain: bool = True,
                max_chain_len: int = 2) -> float:
        return self.tuner.plan_latency(plan, fused_chain=fused_chain,
                                       max_chain_len=max_chain_len,
                                       dtype=self.dtype, mesh=self.mesh,
                                       policy=self.policy, phase=self.phase)

    def evaluate(self, plan: ContractionPlan,
                 fused_chain: bool = True,
                 max_chain_len: int = 2) -> perf_model.PlanCost:
        analytic = perf_model.evaluate(plan, self.hw,
                                       fused_chain=fused_chain,
                                       max_chain_len=max_chain_len,
                                       mesh=self.mesh, policy=self.policy)
        return dataclasses.replace(
            analytic,
            latency_s=self.latency(plan, fused_chain=fused_chain,
                                   max_chain_len=max_chain_len))


# ---------------------------------------------------------------------------
# Calibration report helper (analysis/calibrate.py, bench_autotune)
# ---------------------------------------------------------------------------


def compare_plan(tuner: Tuner, plan: ContractionPlan, *,
                 fused_chain: bool = True,
                 dtype: str = "float32") -> tuple[CompiledPlan, list[dict]]:
    """Per-op analytic-vs-measured rows for one plan (where the roofline
    lies).  Returns the compiled plan and one row per lowered op."""
    compiled = compile_plan(plan, fuse=fused_chain, tuner=tuner, dtype=dtype)
    sizes = plan.network.sizes
    rows = []
    for op in compiled.ops:
        if isinstance(op, GemmOp):
            shape = StepShape("gemm", (op.mat.m, op.mat.n, op.mat.k),
                              transpose_rhs=op.mat.transpose_rhs,
                              dtype=dtype)
            rec = tuner.record(shape)
            kind, analytic_s = "gemm", rec.analytic_s
            measured_s = rec.best_s if rec.measured else None
            tiles = op.tiles
        elif isinstance(op, ChainOp):
            shape = StepShape("chain", op.dims, dtype=dtype)
            rec = tuner.record(shape)
            kind, analytic_s = "chain", rec.analytic_s
            measured_s = rec.best_s if rec.measured else None
            tiles = op.tiles
        else:
            shape = None
            kind = "einsum"
            analytic_s = perf_model.evaluate_step(
                op.step, sizes, tuner.hw).latency_s
            measured_s, tiles = None, None
        rows.append({
            "kind": kind,
            "dims": list(shape.dims) if shape else list(op.step.out_shape),
            "analytic_s": analytic_s,
            "measured_s": measured_s,
            "ratio": (measured_s / analytic_s
                      if measured_s is not None and analytic_s > 0 else None),
            "tiles": ([tiles.block_m, tiles.block_n, tiles.block_k]
                      if tiles is not None else None),
            "nondefault_tiles": tiles is not None and tiles != TileConfig(),
        })
    return compiled, rows


# ---------------------------------------------------------------------------
# Process-wide default instance
# ---------------------------------------------------------------------------


_DEFAULT: Tuner | None = None


def default_tuner() -> Tuner:
    """The singleton every implicit ``objective="measured"`` search uses."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Tuner()
    return _DEFAULT


def set_default_tuner(tuner: Tuner | None) -> None:
    """Swap (or reset, with None) the process-wide tuner — tests use this
    to point measurements at a fresh cache directory."""
    global _DEFAULT
    _DEFAULT = tuner
