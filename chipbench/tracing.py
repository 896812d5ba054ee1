"""Taking a profiler trace of part of the window, and reducing it to the
numbers the per-layer metrics read.

The reduction works on plain event lists, so that a hand-made trace
(``tests/test_tracing.py``) checks it without a chip:

* ``device``: per device, the operations that ran on it, ``(name, start_ns,
  dur_ns)``, and the program executions (``modules``);
* ``host``: the benchmark's own host spans (``chipbench.*``
  ``TraceAnnotation``), ``(name, start_ns, dur_ns, args)``;
* ``window``: the traced interval, the host span ``chipbench.traced``.

Busy time is the union of a device's operation intervals inside the
window; the idle share is one minus busy over the window.
"""
from __future__ import annotations

import collections
import glob
import os
import shutil
import tempfile

TRACED = "chipbench.traced"
PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _event(e) -> tuple:
    return (e.name, float(e.start_ns), float(e.duration_ns))


def load_xplane(path: str) -> dict:
    """Events of an ``.xplane.pb`` file, in the form :func:`reduce` reads."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = {"device": {}, "host": [], "window": None}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] += [_event(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] += [_event(e) for e in line.events]
            out["device"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == TRACED:
                        out["window"] = (float(e.start_ns),
                                         float(e.duration_ns))
                    elif e.name.startswith(PREFIX):
                        args = {k: v for k, v in e.stats}
                        out["host"].append(_event(e) + (args,))
    return out


class Capture:
    """``start`` / ``stop`` a profiler trace into a private temporary
    directory (under ``TMPDIR``), read it back, and delete it."""

    def __init__(self):
        self.dir = None
        self._span = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation(TRACED)
        self._span.__enter__()

    def stop(self) -> dict:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            return load_xplane(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def clip(ev, lo: float, hi: float):
    s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
    return (s, e) if e > s else None


def union(intervals) -> list:
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(
        c for c in (clip(ev, lo, hi) for ev in ops) if c))


def leaves(ops) -> list:
    """The operations that hold no other operation (the trace lists a
    loop and the operations of its body on the same line)."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = []
    for i, ev in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        inside = (nxt is not None and nxt[1] < ev[1] + ev[2]
                  and nxt[1] + nxt[2] <= ev[1] + ev[2])
        if not inside:
            out.append(ev)
    return out


def short(name: str) -> str:
    """An operation's instruction name and result type, without the
    operands: ``%fusion.3 = f32[8,2048]{...}`` -> ``fusion.3 f32[8,2048]``."""
    head, _, rest = name.partition(" = ")
    typ = rest.split("{")[0].split(" ")[0] if rest else ""
    return f"{head.lstrip('%')} {typ}".strip()[:96]


class Reduced:
    """The numbers of one trace."""

    def __init__(self, trace: dict):
        if trace["window"] is None:
            raise ValueError(f"no {TRACED} span in the trace")
        self.trace = trace
        w0, wd = trace["window"]
        self.lo, self.hi = w0, w0 + wd
        self.window_s = wd * 1e-9
        devs = [d for d in trace["device"].values() if d["ops"]]
        if not devs:
            raise ValueError("no device operations in the trace")
        self.devices = devs
        self.busy_s = sum(busy_ns(d["ops"], self.lo, self.hi)
                          for d in devs) / len(devs) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def host(self, name: str) -> list:
        return [h for h in self.trace["host"] if h[0] == PREFIX + name
                and self.lo <= h[1] and h[1] + h[2] <= self.hi]

    def op_time_s(self, match, intervals=None) -> float:
        """Device seconds of the operations whose name ``match`` accepts,
        inside ``intervals`` (``[(start_ns, end_ns)]``; default: the window),
        averaged over the devices."""
        spans = intervals or [(self.lo, self.hi)]
        total = 0.0
        for d in self.devices:
            for ev in d["ops"]:
                if not match(ev[0]):
                    continue
                for lo, hi in spans:
                    c = clip(ev, lo, hi)
                    if c:
                        total += c[1] - c[0]
        return total / len(self.devices) * 1e-9

    def modules(self, match) -> list:
        """Program executions (first device) whose name ``match`` accepts
        and that lie wholly inside the window, as ``(start, end)``."""
        return [(ev[1], ev[1] + ev[2]) for ev in self.devices[0]["modules"]
                if match(ev[0]) and self.lo <= ev[1]
                and ev[1] + ev[2] <= self.hi]

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (leaf operations: a
        loop that holds others is not counted again), and the longest idle
        gaps, each named by the host span it fell in."""
        per_op = collections.Counter()
        for d in self.devices:
            for ev in leaves(d["ops"]):
                c = clip(ev, self.lo, self.hi)
                if c:
                    per_op[short(ev[0])] += ((c[1] - c[0]) * 1e-9
                                             / len(self.devices))
        d0 = self.devices[0]
        busy = union(c for c in (clip(ev, self.lo, self.hi)
                                 for ev in d0["ops"]) if c)
        edges = [self.lo] + [x for b in busy for x in b] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return {"device_ops": [[n, s] for n, s in per_op.most_common(top)],
                "idle_gaps": [[self.host_at(g), (g[1] - g[0]) * 1e-9]
                              for g in gaps[:top]]}

    def host_at(self, gap) -> str:
        """The innermost benchmark host span overlapping most of ``gap``."""
        best, best_key = "none", (0.0, 0.0)
        for name, s, d, _ in self.trace["host"]:
            ov = min(s + d, gap[1]) - max(s, gap[0])
            key = (ov, -d)
            if ov > 0 and key > best_key:
                best, best_key = name[len(PREFIX):], key
        return best
