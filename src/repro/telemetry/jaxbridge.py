"""Mirror tracer spans into ``jax.profiler.TraceAnnotation``.

When a jax profiler trace is being captured (``jax.profiler.trace`` or
TensorBoard's capture button), TraceAnnotation rows make the planning
stack's host-side phases — CSSE stages, autotune sweeps, plan compiles —
visible on the profiler's host timeline next to the device ops they
caused.  The bridge is opt-in (``configure(jax_bridge=True)`` or
``REPRO_TRACE_JAX=1``): jax has no public "is a profiler active" probe,
and an always-on annotation would put jax imports and annotation
overhead on the disabled-tracer fast path.  jax itself is imported
lazily and only on the first bridged span, so the telemetry package
stays importable (and the logger usable) in jax-free contexts.

Two more jax hooks live here for the same reason: the compile watcher
(:func:`watch_compiles`, a ``jax.monitoring`` listener registered once)
and the probe that tells the tracer a new profiler capture has started
(:func:`new_profiler_session`), so that it can sync its clock to it.
"""

from __future__ import annotations

_TraceAnnotation = None
_import_failed = False
_profile_state = None
_last_session = None
_compile_callback = None

# Fired once per executable JAX builds, for a backend compile and for a
# persistent compile-cache load alike (both run inside the same timer).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def annotation(name: str):
    """A ``TraceAnnotation`` context manager for ``name``, or None when
    jax is unavailable (the bridge then degrades to a no-op)."""
    global _TraceAnnotation, _import_failed
    if _import_failed:
        return None
    if _TraceAnnotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except Exception:
            _import_failed = True
            return None
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name)


def new_profiler_session() -> bool:
    """True at the first call made while a JAX profiler capture runs that
    this function has not seen yet.  Reads jax's private capture state
    (there is no public probe); False where that is missing."""
    global _profile_state, _last_session
    if _profile_state is None:
        try:
            from jax._src import profiler
            _profile_state = profiler._profile_state
        except Exception:
            return False
    session = getattr(_profile_state, "profile_session", None)
    if session is None or session is _last_session:
        return False
    _last_session = session
    return True


def watch_compiles(callback) -> None:
    """Call ``callback(seconds, fun_name)`` for every executable JAX builds
    from now on.  Registers one ``jax.monitoring`` listener per process;
    a later call only swaps the callback."""
    global _compile_callback
    first = _compile_callback is None
    _compile_callback = callback
    if not first:
        return
    try:
        from jax import monitoring
    except Exception:
        return
    monitoring.register_event_duration_secs_listener(_on_duration)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == COMPILE_EVENT and _compile_callback is not None:
        _compile_callback(duration, kwargs.get("fun_name"))
