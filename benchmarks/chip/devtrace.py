"""Reduction of a JAX profiler trace to device busy time, kernel time,
exposed collective time and idle gaps.

A trace is read into plain lists with :func:`load` (``.xplane.pb`` through
``jax.profiler.ProfileData``), so that everything after it runs on recorded
or synthetic data in the tests:

* ``ops``: per device, ``(name, start_s, end_s)`` of every event on the
  device plane's ``XLA Ops`` line;
* ``spans``: ``(name, start_s, end_s)`` of the host's annotations (the
  benchmark's and the program's own spans, bridged into the profiler).

On a TPU the op events are named by their HLO text (``%fusion.3 = bf16[...]
fusion(...)``), so a kernel is found by a pattern in that text.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def load(trace_dir: str) -> dict:
    """Device ops and host spans of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops: dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    spans += [(ev.name, ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9)
                              for ev in line.events]
    return {"ops": ops, "spans": spans}


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that merged ``intervals`` cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def window(trace: dict, span_name: str) -> tuple[float, float] | None:
    """The first host span of that name, as the traced window."""
    for name, s, e in trace["spans"]:
        if name == span_name:
            return s, e
    return None


def busy(trace: dict, lo: float, hi: float) -> float:
    """Device busy seconds in [lo, hi], averaged over the devices."""
    devs = trace["ops"]
    if not devs:
        return 0.0
    return sum(covered(union((s, e) for _, s, e in ops), lo, hi)
               for ops in devs.values()) / len(devs)


def kernel_events(trace: dict, pattern: str, lo: float, hi: float):
    """Op events whose HLO text holds ``pattern`` and that start in
    [lo, hi), over every device."""
    return [(n, s, e) for ops in trace["ops"].values() for n, s, e in ops
            if pattern in n and lo <= s < hi]


def exposed_collective_s(trace: dict, lo: float, hi: float) -> float:
    """Collective seconds in [lo, hi] during which no other op runs on the
    same device, averaged over the devices."""
    devs = trace["ops"]
    if not devs:
        return 0.0
    total = 0.0
    for ops in devs.values():
        coll = union((s, e) for n, s, e in ops if _is_collective(n))
        comp = union((s, e) for n, s, e in ops if not _is_collective(n))
        for s, e in coll:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                total += (e - s) - covered(comp, s, e)
    return total / len(devs)


def _is_collective(name: str) -> bool:
    head = name.split("=", 1)[-1]
    return any(f" {c}(" in head or f" {c}-start(" in head for c in COLLECTIVES)


def op_label(name: str) -> str:
    """A short name of an op: its HLO instruction name and opcode."""
    if name.startswith("%") and "=" in name:
        lhs, rhs = name.split("=", 1)
        m = re.search(r"\s([a-z][\w\-.]*)\(", rhs)
        return f"{lhs.strip()} {m.group(1) if m else ''}".strip()
    return name[:80]


CONTAINERS = ("while", "conditional", "call")


def top_ops(trace: dict, lo: float, hi: float, n: int = 10):
    """The ops that took most device time in [lo, hi], by label, summed
    over their events and averaged over the devices.  Loops and calls,
    whose bodies' ops are listed themselves, are left out."""
    acc: dict[str, float] = {}
    devs = trace["ops"]
    for ops in devs.values():
        for name, s, e in ops:
            if op_label(name).rsplit(" ", 1)[-1] in CONTAINERS:
                continue
            d = max(0.0, min(e, hi) - max(s, lo))
            if d > 0:
                lab = op_label(name)
                acc[lab] = acc.get(lab, 0.0) + d / len(devs)
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: dict, lo: float, hi: float, n: int = 10):
    """The longest stretches of [lo, hi] in which no device op runs on
    device 0, each named by the innermost host span that covers its
    middle (what the host was doing), with its length in seconds."""
    devs = trace["ops"]
    if not devs:
        return []
    first = sorted(devs)[0]
    merged = union((s, e) for _, s, e in devs[first])
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    out = []
    for s, e in gaps:
        if e <= s:
            continue
        mid = (s + e) / 2
        inner = [(sn, ss, se) for sn, ss, se in trace["spans"]
                 if ss <= mid <= se]
        label = min(inner, key=lambda x: x[2] - x[1])[0] if inner else "none"
        out.append((label, e - s))
    return sorted(out, key=lambda x: -x[1])[:n]
