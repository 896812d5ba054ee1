#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this machine holds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell ``<name>`` of ``BENCHMARK.json``
names its configuration file, which names its model module under
``chipbench/models/`` (weights, reference and work counts); its workload
file is ``chipbench/workloads/<name>.json``, whose ``runner`` (``train`` or
``serve``) runs it and whose ``traffic.kind`` names the generator under
``chipbench/traffic/``.  Each metric is read by ``chipbench/metrics/<metric
name>.py``.  The run makes its weights and inputs from ``--seed``, warms up
every shape, measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON line last on
stdout.  ``--trace 1`` also traces a few seconds of the window and reports
the per-layer metrics instead of the end-to-end ones.

It exits non-zero without a result when JAX finds no TPU, or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(_BENCH), os.path.join(os.path.dirname(_BENCH),
                                                      "src")]

from chipbench import common  # noqa: E402


def metric_values(cell: dict, names: list, rec: dict) -> dict:
    out = {}
    for m in names:
        mod = common.load_module(os.path.join(common.BENCH, "metrics",
                                              m["name"] + ".py"))
        v = mod.read(rec)
        if v is None:
            common.log(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = common.cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        common.log(f"needs a TPU; JAX found {devices[0].platform} devices")
        return 2
    chips = cell["entry"]["chips"]
    if len(devices) < chips:
        common.log(f"the cell needs {chips} chips, JAX found {len(devices)}")
        return 2
    devices = devices[:chips]
    from repro.launch.cache import enable_compile_cache

    cache = enable_compile_cache()
    peaks = common.peaks(devices[0].device_kind)
    common.log(f"device {devices[0].device_kind} x {len(devices)}; compile "
               f"cache {cache}")
    return finish(cell, args, devices, peaks, *execute(cell, args, devices))


def execute(cell: dict, args, devices):
    runner = common.load_module(os.path.join(
        common.BENCH, cell["workload"]["runner"] + ".py"))
    return runner.run(cell, args, devices, T_PROCESS)


def finish(cell: dict, args, devices, peaks: dict, rec: dict,
           out: dict) -> int:
    """Reduce the trace, read the metrics, and print the result line."""
    rec["peaks"] = peaks
    rec["kind"] = cell["workload"]["runner"]
    device = {**common.device_info(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    common.log(f"peak_bytes_in_use {out['memory_peak_bytes']}")
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        from chipbench import tracing

        red = tracing.Reduced(rec["trace"])
        rec["reduced"] = red
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["metrics"] = metric_values(cell, cell["per_layer"], rec)
        result["breakdown"] = red.breakdown()
        common.log(f"breakdown {result['breakdown']}")
    else:
        result["metrics"] = metric_values(cell, cell["end_to_end"], rec)
    for p in out["problems"]:
        common.log(f"FAILED: {p}")
    result["correct"] = bool(out["ok"] and not out["problems"])
    result["device"] = device
    common.print_result(result, out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
