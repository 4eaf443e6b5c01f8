"""Fault tolerance: step watchdog, straggler detection, elastic restart.

At thousand-node scale the failure model is: (a) a host dies (job must
restart from the last committed checkpoint, possibly on fewer hosts),
(b) a host straggles (slow HBM, thermal throttling — the whole pod waits on
collectives), (c) transient step failures.  This module provides the
harness pieces that are testable without real hardware; the policies are
the production ones:

* :class:`StepWatchdog` — per-step wall-time monitor.  A step exceeding
  ``p95 * straggler_factor`` is flagged (on real pods the action is to
  report the slow host for drain/eviction); a step exceeding ``hang_factor``
  raises, forcing the restart path.
* :class:`ElasticTrainer` logic lives in ``launch/train.py``: on restart it
  rebuilds the mesh from the devices that are actually present and restores
  the last committed checkpoint onto the new mesh (checkpoints are saved as
  logical arrays, so re-sharding onto a different mesh shape is free —
  see ``repro.checkpoint.store``).
* :func:`run_with_restarts` — supervisor loop: run a step function, on
  failure restore from checkpoint and continue, bounded retries.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable


@dataclasses.dataclass
class WatchdogReport:
    step: int
    duration_s: float
    p50: float
    p95: float
    straggler: bool


class StepWatchdog:
    def __init__(self, straggler_factor: float = 1.5,
                 hang_factor: float = 10.0, warmup_steps: int = 5):
        self.straggler_factor = straggler_factor
        self.hang_factor = hang_factor
        self.warmup_steps = warmup_steps
        self.durations: list[float] = []
        self.straggler_events: list[WatchdogReport] = []

    def _quantile(self, q: float) -> float:
        xs = sorted(self.durations)
        if not xs:
            return float("inf")
        idx = min(int(q * len(xs)), len(xs) - 1)
        return xs[idx]

    def observe(self, step: int, duration_s: float) -> WatchdogReport:
        p50, p95 = self._quantile(0.5), self._quantile(0.95)
        straggler = (len(self.durations) >= self.warmup_steps
                     and duration_s > p95 * self.straggler_factor)
        report = WatchdogReport(step, duration_s, p50, p95, straggler)
        if straggler:
            self.straggler_events.append(report)
        if (len(self.durations) >= self.warmup_steps
                and duration_s > max(p50, 1e-9) * self.hang_factor):
            raise TimeoutError(
                f"step {step} took {duration_s:.2f}s (p50 {p50:.2f}s) — "
                f"presumed hung host, forcing restart")
        self.durations.append(duration_s)
        return report


def run_with_restarts(run: Callable[[int], int], *, max_restarts: int = 3,
                      on_failure: Callable[[BaseException], None] | None = None
                      ) -> int:
    """Supervisor: ``run(start_step) -> final_step``; on exception, call
    again from the last checkpointed step (the callee restores).  Returns
    the final step.  Used by launch/train.py and exercised by the
    fault-injection tests."""
    restarts = 0
    start_step = 0
    while True:
        try:
            return run(start_step)
        except (TimeoutError, RuntimeError, OSError) as e:  # recoverable
            restarts += 1
            if on_failure:
                on_failure(e)
            if restarts > max_restarts:
                raise
            start_step = -1   # sentinel: restore from latest checkpoint
            time.sleep(0.01)


def healthy_device_mesh(min_devices: int = 1):
    """Build the largest (data, model) mesh from currently-visible devices —
    the elastic-restart path when a pod comes back smaller.  Keeps the model
    axis if the device count still factors, else collapses to pure DP."""
    import jax

    from repro.distributed.sharding import make_mesh

    n = len(jax.devices())
    assert n >= min_devices, f"only {n} devices visible"
    model = 1
    for cand in (16, 8, 4, 2):
        if n % cand == 0:
            model = cand
            break
    return make_mesh((n // model, model), ("data", "model"))
