"""Counters / gauges / histograms for serving and training telemetry.

A :class:`MetricsRegistry` is a named bag of instruments — unlike the
tracer there is no global singleton: each engine / trainer owns one, so
two engines in one process never share a TTFT histogram.  Instruments are
cheap (plain Python attribute math, no locks — the engines and trainer
mutate them from their own driver thread) and snapshot to plain dicts for
``--metrics-json`` and the periodic one-line reports.

The catalog the serve engines populate (``docs/ARCHITECTURE.md``
§Observability):

* ``ttft_s`` (histogram)         — submit -> first generated token, per request
* ``itl_s`` (histogram)          — mean inter-token latency, per request
* ``decode_step_s`` (histogram)  — one padded-batch decode step
* ``tokens_generated`` (counter), ``requests_finished`` (counter),
  ``admission_rejects`` (counter)
* ``queue_depth`` / ``active_slots`` / ``page_pool_used`` (gauges, with
  high-water marks)
* ``prefix_hits`` / ``prefix_tokens_skipped`` (counters, paged+prefix mode)

The trainer's set: ``step_time_s`` (histogram), ``tokens_per_s`` (gauge),
``loss`` (gauge), ``straggler_count`` (counter).
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Union

Number = Union[int, float]


class Counter:
    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0

    def inc(self, n: Number = 1) -> None:
        self.value += n


class Gauge:
    """Last-set value plus a high-water mark (peak occupancy answers the
    capacity question a last-value gauge can't)."""

    __slots__ = ("value", "max")

    def __init__(self) -> None:
        self.value = 0
        self.max = 0

    def set(self, v: Number) -> None:
        self.value = v
        if v > self.max:
            self.max = v


class Histogram:
    """Exact-sample histogram: serving runs are bounded by the request
    count, so keeping the raw observations (bounded by ``max_samples``)
    buys exact percentiles without bucket-boundary tuning."""

    __slots__ = ("samples", "count", "total", "max_samples")

    def __init__(self, max_samples: int = 100_000) -> None:
        self.samples: List[float] = []
        self.count = 0
        self.total = 0.0
        self.max_samples = max_samples

    def observe(self, v: Number) -> None:
        self.count += 1
        self.total += v
        if len(self.samples) < self.max_samples:
            self.samples.append(float(v))

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, p: float) -> float:
        """Nearest-rank percentile over the retained samples (0 if empty)."""
        if not self.samples:
            return 0.0
        s = sorted(self.samples)
        idx = min(len(s) - 1, max(0, int(round(p / 100.0 * (len(s) - 1)))))
        return s[idx]

    def summary(self) -> Dict[str, float]:
        return {"count": self.count, "mean": self.mean,
                "p50": self.percentile(50), "p90": self.percentile(90),
                "p99": self.percentile(99)}


class MetricsRegistry:
    """Named instruments, created on first touch (``m.counter("x").inc()``)."""

    def __init__(self) -> None:
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._hists: Dict[str, Histogram] = {}
        self.t_start = time.perf_counter()

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            c = self._counters[name] = Counter()
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            g = self._gauges[name] = Gauge()
        return g

    def histogram(self, name: str) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = Histogram()
        return h

    # -- export ---------------------------------------------------------------
    def snapshot(self) -> dict:
        """Plain-dict snapshot (JSON-ready): counters as values, gauges as
        {value, max}, histograms as count/mean/percentiles."""
        return {
            "elapsed_s": time.perf_counter() - self.t_start,
            "counters": {k: v.value for k, v in sorted(self._counters.items())},
            "gauges": {k: {"value": g.value, "max": g.max}
                       for k, g in sorted(self._gauges.items())},
            "histograms": {k: h.summary()
                           for k, h in sorted(self._hists.items())},
        }

    def write_json(self, path: str, **extra) -> str:
        """Dump the snapshot as JSON; ``extra`` top-level sections (e.g.
        ``routes=obs.routes_snapshot()``) ride along in the same artifact."""
        doc = self.snapshot()
        doc.update(extra)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        return path


def _fmt(v: float) -> str:
    if v >= 100:
        return f"{v:.0f}"
    return f"{v:.3g}"


def format_serving_line(m: MetricsRegistry) -> str:
    """The periodic one-line serving report (and the final summary body)."""
    snap = m.snapshot()
    c, g, h = snap["counters"], snap["gauges"], snap["histograms"]
    toks = c.get("tokens_generated", 0)
    el = max(snap["elapsed_s"], 1e-9)
    ttft = h.get("ttft_s", {})
    itl = h.get("itl_s", {})
    pool = g.get("page_pool_used", {"value": 0, "max": 0})
    return (f"reqs={c.get('requests_finished', 0)} tok={toks} "
            f"tok/s={_fmt(toks / el)} "
            f"ttft_ms p50={_fmt(1e3 * ttft.get('p50', 0.0))} "
            f"p99={_fmt(1e3 * ttft.get('p99', 0.0))} "
            f"itl_ms p50={_fmt(1e3 * itl.get('p50', 0.0))} "
            f"queue={g.get('queue_depth', {}).get('value', 0)} "
            f"active={g.get('active_slots', {}).get('value', 0)} "
            f"pages={pool['value']}/{pool['max']}peak "
            f"prefix_hits={c.get('prefix_hits', 0)} "
            f"prefix_tok_skipped={c.get('prefix_tokens_skipped', 0)} "
            f"rejects={c.get('admission_rejects', 0)} "
            f"preempts={c.get('preemptions', 0)} "
            f"faulted={c.get('retired_faulted', 0)}")
