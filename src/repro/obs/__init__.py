"""Telemetry subsystem: metrics registry, structured events, spans.

Zero-dependency (stdlib only) observability layer threaded through
training (``launch/train.py``), serving (``serving/scheduler.py``) and
the kernel dispatchers (``kernels/ops.py``, ``kernels/autotune.py``) —
DESIGN.md §12.

Three complementary pipes:

* :mod:`repro.obs.registry` — in-process counters / gauges / histograms
  with labels, snapshotted on demand (kernel fallbacks, autotune
  hit/miss, compiles, garbage-collection pauses).
* :mod:`repro.obs.events` — schema-versioned JSONL event log
  (:mod:`repro.obs.schema`) appended to the run directory, consumed by
  ``analysis/obs_report.py`` for per-phase speedup attribution.
* :mod:`repro.obs.spans` — host spans and model scopes on the profiler's
  own trace clock (``span``, ``scope``, ``SCOPES``), and the host hooks
  that mark garbage collections and count compiles.
"""

from repro.obs.events import NULL_LOG, EventLog, render_text
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                default_registry, set_default_registry)
from repro.obs.schema import (SCHEMA_VERSION, validate_event, validate_file,
                              validate_lines)
from repro.obs.spans import (SCOPES, install_host_hooks, scope, span,
                             uninstall_host_hooks, watch_compiles)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "default_registry", "set_default_registry",
    "EventLog", "NULL_LOG", "render_text",
    "SCHEMA_VERSION", "validate_event", "validate_file", "validate_lines",
    "SCOPES", "span", "scope", "install_host_hooks",
    "uninstall_host_hooks", "watch_compiles",
]
