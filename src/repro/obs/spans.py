"""Spans and model scopes on the profiler's clock, and host-event hooks.

The program marks its own work on the same timeline as the device:

* :func:`span` — a host span (``jax.profiler.TraceAnnotation``) with
  dotted names whose first part is the layer: ``sched.*`` (the serving
  scheduler), ``freeze.*`` (the Algorithm-2 phase swap), ``data.*`` (batch
  drawing and placement), ``host.*`` (garbage collection).  With the
  profiler off a span costs one TraceMe check and two clock reads; its
  ``dur_s`` is set either way, so an event-log span
  (:meth:`repro.obs.EventLog.span`) takes its duration from the same span.
* :func:`scope` — a ``jax.named_scope`` for the model's parts, from the one
  tuple :data:`SCOPES`.  Scopes add metadata only (the compiled program's
  ``op_name``), never an equation, so a device trace's fusions can be
  attributed to the part of the model they came from.
* :func:`install_host_hooks` — ``host.gc`` spans from a ``gc.callbacks``
  hook, with the counters ``host_gc_pauses{generation}`` and
  ``host_gc_s``; installed only while tracing is wanted.
* :func:`watch_compiles` — the counters ``jit_compiles`` and
  ``jit_compile_s`` from a ``jax.monitoring`` listener on the backend
  compile event (a persistent-cache fetch counts too).  Always on: it fires
  only when a program is compiled.  The runtime's own
  ``PJRT_Client_Compile`` span marks each compile on the trace.

JAX is imported lazily, so the registry and the event schema stay
stdlib-only.
"""

from __future__ import annotations

import gc
import time
from typing import Optional

from repro.obs.registry import default_registry

#: Every model scope the program opens (DESIGN.md §12), innermost wins.
SCOPES = ("embed", "layers", "attn_proj", "attn_core", "kv_gather",
          "kv_write", "ffn", "norm", "head", "loss", "optim")

#: jax.monitoring's event for a backend compile (or a persistent-cache fetch)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_TraceMe = None


def _trace_me():
    global _TraceMe
    if _TraceMe is None:
        from jax.profiler import TraceAnnotation
        _TraceMe = TraceAnnotation
    return _TraceMe


class Span:
    """A host span on the profiler's trace; ``dur_s`` after it closes.

    ``args`` become the trace event's metadata (``rid``, ``fed_len``...).
    Nothing is recorded anywhere but the profiler's own trace."""

    __slots__ = ("name", "args", "dur_s", "_t0", "_tm")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self.dur_s: Optional[float] = None
        self._tm = None

    def __enter__(self) -> "Span":
        tm = _trace_me()
        if tm.is_enabled():
            self._tm = tm(self.name, **self.args)
            self._tm.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_s = time.perf_counter() - self._t0
        if self._tm is not None:
            self._tm.__exit__(*exc)
            self._tm = None
        return False


def span(name: str, **args) -> Span:
    """Context manager: a host span called ``name`` on the trace clock."""
    return Span(name, args)


def scope(name: str):
    """``jax.named_scope(name)`` for one of :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"unknown model scope {name!r}; add it to "
                         f"repro.obs.SCOPES")
    import jax
    return jax.named_scope(name)


# --------------------------------------------------------------------------
# host events
# --------------------------------------------------------------------------

class _GcHook:
    """``gc.callbacks`` entry: a ``host.gc`` span per collection."""

    def __init__(self):
        self._span: Optional[Span] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._span = span("host.gc", generation=info["generation"])
            self._span.__enter__()
        elif self._span is not None:
            s, self._span = self._span, None
            s.__exit__(None, None, None)
            reg = default_registry()
            reg.counter("host_gc_pauses").inc(generation=info["generation"])
            reg.counter("host_gc_s").inc(s.dur_s)


_gc_hook: Optional[_GcHook] = None
_watching_compiles = False


def install_host_hooks() -> None:
    """Mark garbage collections on the trace (``host.gc``) and count them;
    also makes sure compiles are counted.  Idempotent; undo with
    :func:`uninstall_host_hooks`."""
    global _gc_hook
    watch_compiles()
    if _gc_hook is None:
        _gc_hook = _GcHook()
        gc.callbacks.append(_gc_hook)


def uninstall_host_hooks() -> None:
    """Remove the garbage-collection hook (the compile counters stay)."""
    global _gc_hook
    if _gc_hook is not None:
        if _gc_hook in gc.callbacks:
            gc.callbacks.remove(_gc_hook)
        _gc_hook = None


def _on_duration(event: str, duration: float, **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
        reg = default_registry()
        reg.counter("jit_compiles").inc()
        reg.counter("jit_compile_s").inc(duration)


def watch_compiles() -> None:
    """Count every backend compile in ``jit_compiles`` / ``jit_compile_s``
    (idempotent)."""
    global _watching_compiles
    if not _watching_compiles:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        _watching_compiles = True
