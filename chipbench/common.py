"""What every cell shares: finding a cell's files by name, seeds, the chip
check, the compile counter, route checks, percentiles and the result line."""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class Incorrect(Exception):
    """The run did not do what the cell asks (a wrong route, a compile in
    the window, a request that never finished)."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A module of the benchmark found by file name (names may hold dots,
    which ``import`` cannot)."""
    name = "chipbench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(name: str) -> dict:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration file,
    its workload file and the metrics it reports."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])

    def reports(metric):
        return name in metric.get("workloads", [name])

    return {
        "entry": entry,
        "config": load_json(os.path.join(ROOT, conf["file"])),
        "workload": load_json(os.path.join(BENCH, "workloads",
                                           name + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def program_cfg(conf: dict):
    """The program's model configuration for a configuration file, checked
    against the sizes its model module reads from the file."""
    from repro import configs

    from chipbench import models

    p = conf["program"]
    cfg = configs.get(p["arch"], linear=configs.linear_cfg(p["linear"]),
                      **p["overrides"])
    want = models.of(conf).program_sizes(conf)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise SystemExit(f"chipbench: the program's {p['arch']} is {got}, "
                         f"the configuration file says {want}")
    return cfg


def key_data(seed: int):
    """Threefry key data for a seed of up to 64 bits (seeds past 2**31 do
    not fit a signed 32-bit ``PRNGKey``)."""
    import numpy as np

    s = int(seed) % (1 << 64)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def rng(seed: int, *tags: int):
    """A numpy generator for one use of the seed (``tags`` keep the uses
    apart)."""
    import numpy as np

    return np.random.default_rng([int(seed) % (1 << 64), *tags])


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of a non-empty sample."""
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def median(values) -> float:
    s = sorted(values)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts traces, lowerings and backend compiles (cache loads too) from
    JAX's monitoring events while ``armed``: each is a stall that the
    window must not contain."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        self.names: list = []
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1
            if len(self.names) < 8:
                self.names.append(f"{event.rsplit('/', 1)[-1]}:"
                                  f"{kw.get('fun_name', '?')}")

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def check_routes(routes: dict) -> None:
    """No Pallas kernel ran in the interpreter and no route was demoted."""
    bad = [k for k in routes
           if k == "pallas_exec:interpret" or k.startswith("demote:")]
    if bad:
        raise Incorrect(f"routes {bad} taken (all routes: {routes})")


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for dev in devices]
    return int(max(peaks))


def peaks(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"chipbench: device kind {kind!r} is not in "
                         "chipbench/peaks.json")
    return table[kind]


def print_result(result: dict, checks: dict) -> None:
    """Each number compared beside its limit as the last lines of stderr,
    then the result line, ``checks`` last in it, as the last line of
    stdout."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps({**result, "checks": checks}), flush=True)
