"""Unified telemetry: typed span tracing + metrics + exporters.

FeDepth's premise is adaptation to *measured* capability, so the system
must be able to observe itself: per-client, per-block, per-link runtime
signals that the ROADMAP's capacity-scheduler feedback loop and the
sim-vs-real calibration both read.  This package is that measurement
substrate (docs/observability.md):

* :mod:`~repro.obs.trace` — typed spans/events with both sim-time and
  wall-clock stamps; :class:`~repro.obs.trace.SysEvent` replaces the
  systime engines' tuple zoo (the legacy ``AsyncEngine.trace`` list is
  a byte-identical projection of it).
* :mod:`~repro.obs.metrics` — process-local counters / gauges /
  histograms (jit-cache hits, codec ratios, EF residual norms, prefix
  buffer events, deadline misses, spill-store churn, ...).
* :mod:`~repro.obs.export` — JSONL (composes with
  ``JsonlHistorySink``), Chrome trace-event format (Perfetto), and a
  Prometheus textfile snapshot.

**Off by default, profiler spans always.**  Both engines take ``obs=``
(default ``None`` = off).  Off means: no tracer, no registry, and every
instrumented call site guarded by one ``active()`` lookup returning
``None`` — histories, aggregated params, and the legacy trace are
bitwise-identical to the pre-telemetry code path (tests/test_obs.py).
The program's spans reach the profiler either way
(:func:`~repro.obs.trace.annotate`, ``repro.<kind>``): with no profiler
running each costs about a microsecond on the host and records nothing
(docs/observability.md §Profiler).

Enablement flows through one contextvar: an engine whose ``obs`` is set
wraps its run in :func:`activate`, and deep sites that never see the
engine (``PrefixCache``, ``SpillStore``, ``CommChannel``, the jit-cache
helpers) read :func:`active`.  Pass one :class:`Obs` to several engines
to pool their capture.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional, Union

from repro.obs.audit import MemoryAuditor  # noqa: F401
from repro.obs.dynamics import DynamicsAnalyzer  # noqa: F401
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry)
from repro.obs.trace import (LEGACY_FIELDS, SYS_EVENT_KINDS,  # noqa: F401
                             Event, Span, SysEvent, Tracer, annotate)


@dataclasses.dataclass
class Obs:
    """One telemetry capture: a tracer + a metrics registry, plus the
    opt-in diagnostics layer — a memory-conformance auditor and a
    learning-dynamics analyzer (both default ``None`` = off, keeping
    the plain-telemetry path bitwise identical)."""
    tracer: Tracer = dataclasses.field(default_factory=Tracer)
    metrics: MetricsRegistry = dataclasses.field(
        default_factory=MetricsRegistry)
    audit: Optional[MemoryAuditor] = None
    dynamics: Optional[DynamicsAnalyzer] = None

    # ---------------------------------------------------------- lifecycle
    def bind(self, ctx) -> "Obs":
        """Attach an experiment context to the diagnostics (engines call
        this at construction; a no-op without audit/dynamics)."""
        if self.audit is not None:
            self.audit.bind(ctx, self.metrics)
        if self.dynamics is not None:
            self.dynamics.bind(self.metrics)
        return self

    def reset(self) -> "Obs":
        """Fresh capture in place: clear spans/metrics/diagnostics so
        back-to-back runs sharing this ``Obs`` don't accumulate stale
        counters (audit keeps its experiment binding)."""
        self.tracer.reset()
        self.metrics.reset()
        if self.audit is not None:
            self.audit.reset()
        if self.dynamics is not None:
            self.dynamics.reset()
        return self

    # ------------------------------------------------------ exporters
    def export_jsonl(self, sink_or_path) -> int:
        from repro.obs.export import to_jsonl
        return to_jsonl(self, sink_or_path)

    def export_chrome_trace(self, path: Optional[str] = None) -> dict:
        from repro.obs.export import to_chrome_trace
        return to_chrome_trace(self, path)

    def export_prometheus(self, path_or_file=None) -> str:
        from repro.obs.export import to_prometheus
        return to_prometheus(self.metrics, path_or_file)


def make_obs(spec: Union[None, bool, str, Obs]) -> Optional[Obs]:
    """Resolve the engines' ``obs=`` knob: ``None``/``False``/``"off"``
    -> disabled (``None``); ``True``/``"on"`` -> a fresh capture;
    ``"full"`` -> a capture with the diagnostics layer (memory auditor +
    dynamics analyzer) enabled; an :class:`Obs` instance passes through
    (sharing one capture across engines)."""
    if spec is None or spec is False or spec == "off":
        return None
    if spec is True or spec == "on":
        return Obs()
    if spec == "full":
        return Obs(audit=MemoryAuditor(), dynamics=DynamicsAnalyzer())
    if isinstance(spec, Obs):
        return spec
    raise ValueError(f"obs must be 'on', 'off', 'full', None, a bool, or "
                     f"an Obs instance, got {spec!r}")


# --------------------------------------------------------------------------
# the active-capture contextvar
# --------------------------------------------------------------------------
_ACTIVE: contextvars.ContextVar[Optional[Obs]] = contextvars.ContextVar(
    "repro_obs_active", default=None)


def active() -> Optional[Obs]:
    """The capture currently activated by an enclosing engine run, or
    ``None`` — THE guard every deep instrumentation site starts with."""
    return _ACTIVE.get()


@contextlib.contextmanager
def activate(obs: Optional[Obs]):
    """Make ``obs`` the active capture for the dynamic extent (nests;
    ``None`` explicitly deactivates)."""
    token = _ACTIVE.set(obs)
    try:
        yield obs
    finally:
        _ACTIVE.reset(token)


def scope(obs: Optional[Obs]):
    """``activate(obs)`` when enabled, a no-op context otherwise — what
    the engines wrap ``run``/``run_round`` in so the disabled path never
    pays for a contextvar set."""
    if obs is None:
        return contextlib.nullcontext()
    return activate(obs)


def span_if(obs: Optional[Obs], kind: str, **attrs):
    """``obs.tracer.span(kind, **attrs)`` when enabled, the profiler
    annotation ``repro.<kind>`` alone otherwise (yielding ``None``) —
    the one path instrumented call sites take."""
    if obs is None:
        return annotate(kind, **attrs)
    return obs.tracer.span(kind, **attrs)


__all__ = [
    "Obs", "make_obs", "active", "activate", "scope", "span_if", "annotate",
    "Tracer", "Span", "Event", "SysEvent", "LEGACY_FIELDS",
    "SYS_EVENT_KINDS",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "MemoryAuditor", "DynamicsAnalyzer",
]
