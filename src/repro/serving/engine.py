"""Batched serving engine: slot-table continuous batching with chunked
prefill, admission control, and an optionally quantized KV cache.

The engine owns a fixed table of ``batch_size`` slots and advances in
**ticks**.  Each tick:

1. **admit** — free slots refill from the request queue immediately
   (true continuous batching: a queued request lands mid-decode in the
   slot another request just vacated, it does not wait for the wave to
   drain).  Admission is bounded by the memory budget: each slot's KV
   cache is priced by :func:`repro.serving.kv_cache.slot_bytes` (the
   modeled number the ``--serve-memory-budget`` flag gates against),
   and slots beyond ``budget // slot_bytes`` are never occupied.
2. **prefill** — slots still ingesting their prompt consume up to
   ``prefill_chunk`` prompt tokens each through one ``model.extend``
   call, bounded globally by ``max_prefill_tokens`` per tick (the
   lmdeploy-style token-budget knob that keeps a long prompt from
   starving decode latency).  A slot whose prompt completes samples its
   first token from its last valid chunk position and flips to decode.
3. **decode** — every decoding slot feeds its last sampled token
   through one ``model.decode_step`` call; EOS or the per-request
   ``max_new_tokens`` budget frees the slot at end of tick.

Slots are **right-aligned**: every slot's KV history starts at buffer
offset 0 and rope positions are per-slot logical positions, so a
request's outputs are independent of which slot it lands in and what
its neighbours are doing (no left-padding, no cross-slot contamination
— the invariants ``tests/test_serving.py`` pins).  Host-side numpy
arrays are the authoritative slot state; the device cache's ``length``
is overwritten from them before every call.

Every tick program takes the device state donated and returns its
successor, which the engine keeps in place of what it passed.  The
engine goes through one path for every model: ``extend`` and
``decode_step`` take ``valid`` and freeze inactive slots themselves, so a
garbage write from a padded lane can never corrupt a live slot's state.
How a layer freezes is the model's business (``LM.decode_step``): an
attention layer puts back a frozen slot's rows where it would write,
inside its KV write into the donated cache, and a state layer (RWKV,
Mamba-2) keeps a frozen slot's state with a ``where`` over that layer's
state; no call selects over the whole cache.  The quantized cache
rebuilds and requantizes its K/V around each call, and keeps padding out
of its monotone amax with a select (``requant``).  The slot-zeroing at
admission is the one whole-state select left (``_select``): recurrent
states must start from zero.  The counter ``serve.kv_inplace`` counts the
bf16 extend and decode calls; ``serve.kv_whole`` counts the quantized
calls and the slot-zeroing.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry as tm
from repro.memory.planner import parse_budget
from repro.precision.policy import QuantPolicy
from repro.serving import kv_cache as kvq

FREE, PREFILL, DECODE = 0, 1, 2


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [T] int32
    max_new_tokens: int = 32
    temperature: float = 0.0        # 0 = greedy
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float | None = None   # wall-clock hooks for the bench
    t_admit: float | None = None
    t_first: float | None = None
    t_done: float | None = None

    @property
    def ttft_s(self) -> float | None:
        if self.t_submit is None or self.t_first is None:
            return None
        return self.t_first - self.t_submit


class ServeEngine:
    def __init__(self, model, params, *, batch_size: int, max_len: int,
                 shard=None, eos_id: int | None = None, seed: int = 0,
                 prefill_chunk: int = 32,
                 max_prefill_tokens: int | None = None,
                 kv_policy: QuantPolicy | str | None = None,
                 memory_budget: int | str | None = None):
        self.model = model
        self.params = params
        self.batch = batch_size
        self.max_len = max_len
        self.eos_id = eos_id
        self.shard = shard or (lambda x, a: x)
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if max_prefill_tokens is not None and max_prefill_tokens < 1:
            raise ValueError("max_prefill_tokens must be >= 1")
        self.prefill_chunk = prefill_chunk
        self.max_prefill_tokens = max_prefill_tokens
        self.queue: deque[Request] = deque()
        self.key = jax.random.key(seed)

        if isinstance(kv_policy, str):
            kv_policy = QuantPolicy.parse(kv_policy)
        if kv_policy is not None and not kv_policy.quantized:
            kv_policy = None
        self.kv_policy = kv_policy

        cfg = getattr(model, "cfg", None)
        attn_only = cfg is None or (getattr(cfg, "block", "attn") == "attn"
                                    and not getattr(cfg, "hybrid", None))
        if kv_policy is not None and not attn_only:
            raise ValueError("quantized KV requires an attention-only model")

        # -- admission capacity: memory budget / modeled per-slot bytes ----
        if cfg is not None and attn_only and hasattr(cfg, "num_kv_heads"):
            self.slot_cost = kvq.slot_bytes(cfg, max_len, kv_policy)
        else:
            per = kvq.model_slot_bytes(model, max_len)
            self.slot_cost = {"payload": per, "meta": 0, "total": per}
        budget = parse_budget(memory_budget)
        self.memory_budget = budget
        if budget is None:
            self.capacity = batch_size
        else:
            self.capacity = min(batch_size,
                                budget // max(self.slot_cost["total"], 1))
            if self.capacity == 0:
                raise ValueError(
                    f"memory budget {budget} bytes cannot hold one slot "
                    f"({self.slot_cost['total']} bytes at max_len={max_len})")

        # -- slot table (host-authoritative) --------------------------------
        B = batch_size
        self.slot_req: list[Request | None] = [None] * B
        self.phase = np.full(B, FREE, np.int32)
        self.lengths = np.zeros(B, np.int32)        # KV tokens written
        self.prefill_pos = np.zeros(B, np.int32)    # prompt tokens consumed
        self.next_tok = np.zeros(B, np.int32)       # last sampled token
        self._admit_seq = np.zeros(B, np.int64)     # admission order
        self._seq = 0
        self.tick = 0
        self.events: list[tuple[int, str, int]] = []
        self.max_occupancy = 0
        self.completed: list[Request] = []
        self._slot_of: dict[int, int] = {}   # rid -> slot (for the trace)

        # prefill writes a full chunk of (masked) positions starting at a
        # slot's current length, so the buffer carries chunk-width slack —
        # dynamic_update_slice must never clamp a write back onto live
        # entries.
        self.cache_len = max_len + prefill_chunk
        self._init_device_cache()
        self._build_step_fns()

    # -- device cache -------------------------------------------------------

    def _init_device_cache(self):
        cache = self.model.init_cache(self.batch, self.cache_len)
        if self.kv_policy is None:
            # per-slot [B] length from the start — the pytree structure the
            # jitted tick fns return; a scalar here would force a recompile
            # on the first real tick
            self.cache = cache._replace(
                length=jnp.zeros(self.batch, jnp.int32))
            self.qkv = None
        else:
            self.cache = None
            self.qkv = kvq.quantize_kv(cache.layers.k, cache.layers.v,
                                       self.kv_policy)
            self._layer_len = cache.layers.length   # [L] bookkeeping shape

    def _select(self, active, new, old):
        """Per-leaf batch-axis select over the whole state: inactive slots
        keep their old state.  Only the slot-zeroing at admission uses it;
        the tick programs freeze inside the model and the quantized cache
        selects in ``requant``.
        Axis rule: every stacked per-layer buffer in
        this repo is >= 3-D with batch on axis 1 ([L, B, ...]), per-slot
        vectors are 1-/2-D with batch on axis 0 — checked in that order,
        so the rule stays correct when num_layers happens to equal
        batch_size.
        Leaves without a batch axis pass through from ``new``."""
        B = self.batch

        def sel(n, o):
            if n.ndim >= 3 and n.shape[1] == B:
                m = active.reshape((1, B) + (1,) * (n.ndim - 2))
            elif n.ndim >= 1 and n.shape[0] == B:
                m = active.reshape((B,) + (1,) * (n.ndim - 1))
            else:
                return n
            return jnp.where(m, n, o)

        with jax.named_scope("engine_select"):
            return jax.tree.map(sel, new, old)

    def _build_step_fns(self):
        model, shard, policy = self.model, self.shard, self.kv_policy

        if policy is None:
            # Inactive slots have valid == 0: the model freezes them, and
            # their lengths advance by 0.
            def extend_fn(params, toks, cache, lengths, valid, active):
                cache = cache._replace(length=lengths)
                return model.extend(params, toks, cache, shard, valid=valid)

            def decode_fn(params, tok, cache, lengths, active):
                cache = cache._replace(length=lengths)
                return model.decode_step(params, tok, cache, shard,
                                         valid=active.astype(jnp.int32))

            def zero_fn(cache, admit):
                zeros = jax.tree.map(jnp.zeros_like, cache)
                return self._select(admit, zeros, cache)
        else:
            from repro.models.lm import DecodeCache, KVCache
            layer_len = self._layer_len
            dtype = getattr(getattr(model, "cfg", None), "compute_dtype",
                            jnp.bfloat16)

            def rebuild(qkv, lengths):
                k, v = kvq.dequantize_kv(qkv, policy, dtype)
                return (DecodeCache(KVCache(k, v, layer_len), None, lengths),
                        k, v)

            def requant(new, k, v, qkv, active):
                m = active[None, :, None, None, None]
                nk = jnp.where(m, new.layers.k, k)
                nv = jnp.where(m, new.layers.v, v)
                return kvq.quantize_kv(nk, nv, policy, prev=qkv)

            def extend_fn(params, toks, qkv, lengths, valid, active):
                cache, k, v = rebuild(qkv, lengths)
                logits, new = model.extend(params, toks, cache, shard,
                                           valid=valid)
                return logits, requant(new, k, v, qkv, active)

            def decode_fn(params, tok, qkv, lengths, active):
                cache, k, v = rebuild(qkv, lengths)
                logits, new = model.decode_step(params, tok, cache, shard)
                return logits, requant(new, k, v, qkv, active)

            zero_fn = None

        # The state is donated: each call's result replaces it
        # (``_set_state``), and nothing reads a state once passed.
        self._extend_fn = jax.jit(extend_fn, donate_argnums=2)
        self._decode_fn = jax.jit(decode_fn, donate_argnums=2)
        self._zero_fn = (jax.jit(zero_fn, donate_argnums=0)
                         if zero_fn is not None else None)
        self._kv_counter = ("serve.kv_inplace" if policy is None
                            else "serve.kv_whole")

    def _state(self):
        return self.cache if self.kv_policy is None else self.qkv

    def _set_state(self, s):
        if self.kv_policy is None:
            self.cache = s
        else:
            self.qkv = s

    # -- public API ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        if len(req.prompt) < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds "
                f"max_len={self.max_len}")
        if req.t_submit is None:
            req.t_submit = time.monotonic()
        self.queue.append(req)

    @property
    def occupancy(self) -> int:
        return int(np.sum(self.phase != FREE))

    @property
    def busy(self) -> bool:
        return bool(self.queue) or self.occupancy > 0

    def warmup(self) -> None:
        """Compile the tick functions outside the serving clock, then
        reset device state."""
        with tm.span("serve.warmup"):
            self._warmup()

    def _warmup(self) -> None:
        B, C = self.batch, self.prefill_chunk
        key0 = self.key           # warmup must not advance the sample stream
        zl = jnp.zeros(B, jnp.int32)
        toks = jnp.zeros((B, C), jnp.int32)
        act = jnp.zeros(B, bool)
        # Each call donates its state: thread the result into the next.
        logits, state = self._extend_fn(self.params, toks, self._state(), zl,
                                        zl, act)
        last = logits[jnp.arange(B), zl]
        self._sample(last, np.zeros(B, np.float32))
        dlogits, state = self._decode_fn(self.params, jnp.zeros(B, jnp.int32),
                                         state, zl, act)
        self._sample(dlogits, np.zeros(B, np.float32))
        if self._zero_fn is not None:
            self._zero_fn(state, jnp.zeros(B, bool))
        self.key = key0
        self._init_device_cache()

    # -- tick phases --------------------------------------------------------

    def _admit(self) -> list[int]:
        admitted = []
        for slot in range(self.batch):
            if not self.queue:
                break
            if self.phase[slot] != FREE or self.occupancy >= self.capacity:
                continue
            req = self.queue.popleft()
            req.t_admit = time.monotonic()
            self.slot_req[slot] = req
            self.phase[slot] = PREFILL
            self.lengths[slot] = 0
            self.prefill_pos[slot] = 0
            self._admit_seq[slot] = self._seq
            self._seq += 1
            self.events.append((self.tick, "admit", req.rid))
            self._slot_of[req.rid] = slot
            admitted.append(slot)
        if admitted and self._zero_fn is not None:
            mask = np.zeros(self.batch, bool)
            mask[admitted] = True
            self._set_state(self._zero_fn(self._state(), jnp.asarray(mask)))
            tm.inc("serve.kv_whole")
        self.max_occupancy = max(self.max_occupancy, self.occupancy)
        if admitted:
            tm.inc("serve.admitted", len(admitted))
        tm.sample("serve.occupancy", self.occupancy)
        return admitted

    def _sample(self, logits: jax.Array, temps: np.ndarray) -> np.ndarray:
        greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1)
        self.key, sub = jax.random.split(self.key)
        temped = jax.random.categorical(
            sub, logits.astype(jnp.float32)
            / jnp.maximum(jnp.asarray(temps)[:, None], 1e-4))
        pick = jnp.where(jnp.asarray(temps) > 0, temped, greedy)
        with tm.span("serve.fetch", tick=self.tick):
            return np.asarray(pick, np.int32)

    def _append_token(self, slot: int, tok: int) -> None:
        """Record a sampled token; finish the request when EOS or the
        budget lands (EOS honored on every token including the first)."""
        req = self.slot_req[slot]
        req.out_tokens.append(tok)
        if req.t_first is None:
            req.t_first = time.monotonic()
        self.next_tok[slot] = tok
        if (tok == self.eos_id if self.eos_id is not None else False) or \
                len(req.out_tokens) >= req.max_new_tokens:
            self._finish(slot)
        else:
            self.phase[slot] = DECODE

    def _finish(self, slot: int) -> None:
        req = self.slot_req[slot]
        req.done = True
        req.t_done = time.monotonic()
        self.completed.append(req)
        self.events.append((self.tick, "finish", req.rid))
        self._emit_request_trace(req, slot)
        self.slot_req[slot] = None
        self.phase[slot] = FREE

    def _emit_request_trace(self, req: Request, slot: int) -> None:
        """Reconstruct the finished request's lifecycle as trace spans.

        The engine keeps monotonic stamps (submit/admit/first/done), the
        tracer's own clock (``tm.mono_us``); at finish they are laid out
        on virtual lanes:
        queue-wait on the shared ``queue`` lane, prefill (admission to
        first token) and decode on the request's ``slot<n>`` lane, so
        overlapping requests render side by side in Perfetto."""
        if not tm.enabled() or req.t_submit is None:
            return
        at = tm.mono_us
        lane = f"slot{slot}"
        if req.t_admit is not None:
            tm.complete_span("serve.queue_wait", at(req.t_submit),
                             at(req.t_admit), lane="queue", rid=req.rid)
            if req.t_first is not None:
                tm.complete_span("serve.prefill", at(req.t_admit),
                                 at(req.t_first), lane=lane, rid=req.rid,
                                 ttft_s=req.ttft_s)
        if req.t_first is not None:
            tm.complete_span("serve.decode", at(req.t_first),
                             at(req.t_done), lane=lane, rid=req.rid,
                             tokens=len(req.out_tokens))
        tm.inc("serve.completed")

    def _prefill_tick(self) -> None:
        B, C = self.batch, self.prefill_chunk
        budget = self.max_prefill_tokens or B * C
        valid = np.zeros(B, np.int32)
        toks = np.zeros((B, C), np.int32)
        slots = [s for s in range(B) if self.phase[s] == PREFILL]
        # token budget distributes in admission order (oldest first)
        for slot in sorted(slots, key=lambda s: self._admit_seq[s]):
            if budget <= 0:
                break
            req = self.slot_req[slot]
            pos = int(self.prefill_pos[slot])
            take = min(C, len(req.prompt) - pos, budget)
            if take <= 0:
                continue
            toks[slot, :take] = req.prompt[pos:pos + take]
            valid[slot] = take
            budget -= take
        if not valid.any():
            return
        active = valid > 0
        tm.inc("serve.prefill_tokens", int(valid.sum()))
        with tm.span("serve.prefill_chunk", tick=self.tick,
                     tokens=int(valid.sum()), slots=int(active.sum())):
            # A copy: the host array is bumped below while the tick may
            # still be reading it (jax aliases host numpy memory on CPU).
            logits, state = self._extend_fn(
                self.params, jnp.asarray(toks), self._state(),
                jnp.asarray(self.lengths.copy()), jnp.asarray(valid),
                jnp.asarray(active))
            self._set_state(state)
            tm.inc(self._kv_counter)
            self.lengths[active] += valid[active]
            self.prefill_pos[active] += valid[active]

            finishing = [s for s in np.nonzero(active)[0] if
                         self.prefill_pos[s] >= len(self.slot_req[s].prompt)]
            if finishing:
                # gather + sample at full batch width so the eager sampling
                # kernels compile once (warmup covers them), regardless of
                # how many slots finish this tick
                cols = jnp.asarray(np.maximum(valid - 1, 0))
                last = logits[jnp.arange(B), cols]            # [B, V]
                temps = np.zeros(B, np.float32)
                for s in finishing:
                    temps[s] = self.slot_req[s].temperature
                picks = self._sample(last, temps)
                for s in finishing:
                    self._append_token(int(s), int(picks[s]))

    def _decode_tick(self) -> None:
        active = self.phase == DECODE
        if not active.any():
            return
        tm.inc("serve.decode_tokens", int(active.sum()))
        with tm.span("serve.decode_step", tick=self.tick,
                     slots=int(active.sum())):
            logits, state = self._decode_fn(
                self.params, jnp.asarray(self.next_tok.copy()),
                self._state(), jnp.asarray(self.lengths.copy()),
                jnp.asarray(active))
            self._set_state(state)
            tm.inc(self._kv_counter)
            self.lengths[active] += 1
            temps = np.array([self.slot_req[s].temperature if active[s]
                              else 0.0 for s in range(self.batch)],
                             np.float32)
            picks = self._sample(logits, temps)
            for slot in np.nonzero(active)[0]:
                self._append_token(int(slot), int(picks[slot]))

    # -- main loop ----------------------------------------------------------

    def step(self) -> list[Request]:
        """One tick: admit, prefill chunk, decode.  Returns the requests
        that completed during the tick."""
        before = len(self.completed)
        with tm.span("serve.tick", tick=self.tick):
            with tm.span("serve.admit", tick=self.tick):
                self._admit()
            self._prefill_tick()
            self._decode_tick()
        self.tick += 1
        return self.completed[before:]

    def run(self, max_ticks: int | None = None) -> list[Request]:
        """Drain the queue; returns all completed requests."""
        limit = max_ticks if max_ticks is not None else 10_000_000
        while self.busy:
            if limit <= 0:
                raise RuntimeError("ServeEngine.run(): tick limit exceeded")
            self.step()
            limit -= 1
        return self.completed
