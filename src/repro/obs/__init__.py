"""repro.obs — unified tracing, metrics, and the drift ledger (DESIGN.md §11).

Three planes, one ambient context:

* :class:`Tracer` (``trace.py``) — typed span/event records over the
  execution taxonomy (plan/compile/dispatch/chunk/barrier/collective/
  lane/cache/measure), injectable clock, JSON-lines + Chrome trace-event
  exporters (Perfetto-loadable, one track per tier/lane group). Every
  span, the default :class:`NullTracer`'s too, is also the profiler span
  ``repro.<cat>``; recording in memory is off by default.
* :class:`MetricsRegistry` (``metrics.py``) — counters/gauges/histograms
  behind the services' ``stats()`` views and the executor-level counters
  (barriers, fused steps per pass, bytes cached vs streamed, collective
  rounds, retraces), with Prometheus text exposition
  (``repro.runtime.server.start_metrics_server``).
* :class:`DriftLedger` (``ledger.py``) — the persisted
  ``(problem, chip, jax) -> plan -> predicted/measured`` tuning database
  ``autotune`` reads to skip re-measurement, ``plan_candidates`` consults
  to re-rank, and :meth:`DriftLedger.drift_report` mines for plans whose
  projection no longer describes reality.

The *ambient context* (``get_tracer``/``use_tracer`` and friends) is how
instrumentation reaches the executor without threading arguments through
every call: the default tracer is a null object that records nothing (its
spans still reach a running profiler session) and the default ledger is
None. Installing a real tracer/registry/ledger (directly or with the
``use_*`` context managers) lights the whole stack up.
"""
from __future__ import annotations

import contextlib
from typing import Optional

from repro.obs.ledger import (
    DEFAULT_DRIFT_THRESHOLD,
    DriftLedger,
    LedgerRecord,
    plan_signature,
    prediction_ratio,
    problem_key,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.trace import (
    CATEGORIES,
    NullTracer,
    TraceEvent,
    Tracer,
)

# -- ambient observability context --------------------------------------------

_NULL_TRACER = NullTracer()
_tracer: Tracer = _NULL_TRACER
_metrics: MetricsRegistry = MetricsRegistry()
_ledger: Optional[DriftLedger] = None


def get_tracer() -> Tracer:
    """The ambient tracer (a no-op :class:`NullTracer` unless installed)."""
    return _tracer


def set_tracer(tracer: Optional[Tracer]) -> Tracer:
    """Install ``tracer`` as the ambient tracer (None restores the null
    tracer); returns the previous one."""
    global _tracer
    prev = _tracer
    _tracer = tracer if tracer is not None else _NULL_TRACER
    return prev


def get_metrics() -> MetricsRegistry:
    """The ambient metrics registry (a real, process-global registry —
    counters are cheap; scope one with :func:`use_metrics` when isolation
    matters, e.g. determinism tests)."""
    return _metrics


def set_metrics(registry: Optional[MetricsRegistry]) -> MetricsRegistry:
    global _metrics
    prev = _metrics
    _metrics = registry if registry is not None else MetricsRegistry()
    return prev


def get_ledger() -> Optional[DriftLedger]:
    """The ambient drift ledger, or None (recording disabled)."""
    return _ledger


def set_ledger(ledger: Optional[DriftLedger]) -> Optional[DriftLedger]:
    global _ledger
    prev = _ledger
    _ledger = ledger
    return prev


@contextlib.contextmanager
def use_tracer(tracer: Tracer):
    """Scope an ambient tracer: ``with use_tracer(tr): execute(...)``."""
    prev = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)


@contextlib.contextmanager
def use_metrics(registry: MetricsRegistry):
    prev = set_metrics(registry)
    try:
        yield registry
    finally:
        set_metrics(prev)


@contextlib.contextmanager
def use_ledger(ledger: DriftLedger):
    prev = set_ledger(ledger)
    try:
        yield ledger
    finally:
        set_ledger(prev)


__all__ = [
    "CATEGORIES",
    "Counter",
    "DEFAULT_DRIFT_THRESHOLD",
    "DriftLedger",
    "Gauge",
    "Histogram",
    "LedgerRecord",
    "MetricsRegistry",
    "NullTracer",
    "TraceEvent",
    "Tracer",
    "get_ledger",
    "get_metrics",
    "get_tracer",
    "plan_signature",
    "prediction_ratio",
    "problem_key",
    "set_ledger",
    "set_metrics",
    "set_tracer",
    "use_ledger",
    "use_metrics",
    "use_tracer",
]
