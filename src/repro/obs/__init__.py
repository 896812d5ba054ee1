"""Runtime observability: tracing spans, metrics, route-dispatch visibility.

Three pieces (docs/ARCHITECTURE.md §Observability):

* :mod:`repro.obs.trace` — process-global tracer with nestable spans on the
  monotonic clock, a bounded ring buffer, and Chrome-trace/Perfetto JSON
  export.  Off by default; the disabled fast path is one global load.
* :mod:`repro.obs.metrics` — per-owner :class:`MetricsRegistry`
  (counters / gauges / exact-percentile histograms) behind the serving and
  training telemetry: TTFT, inter-token latency, tok/s, queue depth,
  page-pool occupancy, prefix-cache hits, step time, stragglers.
* route-dispatch events (:func:`route_event` below) — every trace-time
  kernel routing decision in :mod:`repro.kernels.ops` (fused vs split,
  flash vs xla, pallas vs xla bwd) is counted here and, when tracing,
  marked in the timeline, so a silent fallback to a slow path shows up in
  ``route_counts()`` / the exported trace instead of only in the wall time.

Consumers: ``launch/serve.py --trace/--metrics-json``,
``launch/train.py --trace``, ``benchmarks/run.py --trace``, and
``python -m repro.perf.timeline`` (replay-diff of two exported traces).
"""
from __future__ import annotations

from typing import Dict, Tuple

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               format_serving_line)
from repro.obs.trace import (Tracer, disable, enable, enabled, export,
                             get_tracer, instant, span, verbose)

# (op, route) -> count of trace-time dispatch decisions.  Process-global on
# purpose: routing is a process-level property (backend + env vars), and the
# counters must be live even when no tracer is installed.
_ROUTE_COUNTS: Dict[Tuple[str, str], int] = {}


def route_event(op: str, route: str, **args) -> None:
    """Record one trace-time kernel routing decision (cheap: dict bump +
    optional instant event)."""
    key = (op, route)
    _ROUTE_COUNTS[key] = _ROUTE_COUNTS.get(key, 0) + 1
    instant(f"route:{op}={route}", cat="route", op=op, route=route, **args)


def route_counts() -> Dict[Tuple[str, str], int]:
    """Copy of the dispatch-decision counters ({(op, route): n})."""
    return dict(_ROUTE_COUNTS)


def reset_route_counts() -> None:
    _ROUTE_COUNTS.clear()


def routes_snapshot() -> Dict[str, int]:
    """JSON-ready view of the dispatch counters (``{"op:route": n}``) —
    merged into ``--metrics-json`` exports so a config that silently loses
    a kernel route (e.g. ``ff_tp:tp_fallback`` under TP) is visible in the
    same artifact as the latency percentiles."""
    return {f"{op}:{route}": n
            for (op, route), n in sorted(_ROUTE_COUNTS.items())}


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "format_serving_line",
    "Tracer", "enable", "disable", "enabled", "export", "get_tracer",
    "instant", "span", "verbose",
    "route_event", "route_counts", "reset_route_counts", "routes_snapshot",
]
