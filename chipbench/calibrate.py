#!/usr/bin/env python3
"""Read the numbers that a cell's limits are set from, on the chip, in one
process (the benchmark's own runs do not run this):

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \\
        --control-seeds 4,5,6 [--seconds 10]
    python3 chipbench/calibrate.py --workload <serving cell> --rates 0.2,0.4


* the program's readings: the numbers ``run.py`` compares, one line per
  seed (a serving cell runs a short window at the cell's own load);
* the control's: the reference computed with float8 operands put in the
  program's place, which has to read as not correct;
* for a training cell also the planted fault "half of the batch left out,
  the mean taken over the rest" (the reference on the first half of each
  batch put in the program's place).  A step that returns its state
  unchanged reads 1 on ``update_gap`` by definition and needs no run.

``--rates`` instead runs a serving cell's mix at each offered rate, to find
the highest rate it sustains without a growing queue.  Each reading is
printed as one JSON line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

_BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(_BENCH), os.path.join(os.path.dirname(_BENCH),
                                                      "src")]

from chipbench import common  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def train_readings(cell: dict, seeds: list, control_seeds: list) -> None:
    from chipbench import train

    prog = train.Program(cell)
    conf, wl = cell["config"], cell["workload"]
    for seed in seeds:
        state, ring, got = prog.first_steps(seed)
        del state, ring
        gc.collect()
        ref = train.reference_readings(conf, wl, seed)
        emit(kind="program", seed=seed, **train.gaps(got, ref))
    half = list(range(wl["traffic"]["batch"] // 2))
    for seed in control_seeds:
        ref = train.reference_readings(conf, wl, seed)
        emit(kind="control", seed=seed, **train.gaps(
            train.reference_readings(conf, wl, seed, prec="fp8"), ref))
        emit(kind="half_batch", seed=seed, **train.gaps(
            train.reference_readings(conf, wl, seed, rows=half), ref))


def serve_readings(cell: dict, seeds: list, control_seeds: list,
                   seconds: float, devices) -> None:
    from chipbench import models, serve

    conf, wl = cell["config"], cell["workload"]
    m = models.of(conf).dims(conf)
    for seed in seeds:
        args = argparse.Namespace(workload=cell["entry"]["name"], seed=seed,
                                  seconds=seconds, trace=0)
        rec, out = serve.run(cell, args, devices, time.perf_counter())
        emit(kind="program", seed=seed,
             token_gap=out["checks"]["token_gap"]["value"],
             problems=out["problems"], attempted=out["attempted"],
             failed=out["failed"])
        if seed in control_seeds:
            ck = wl["check"]
            recs = serve.sample(rec["reqs"], seed, ck["tokens"],
                                ck["requests"])
            g = serve.token_gaps(conf, m, seed, recs, wl["max_len"],
                                 ck["served"], control=True)
            emit(kind="control", seed=seed, token_gap=max(g))
        del rec, out
        gc.collect()


def sweep(cell: dict, rates: list, seconds: float, devices) -> None:
    """The steady mix at each offered rate: whether the queue grows."""
    import copy

    from chipbench import serve

    for i, rate in enumerate(rates):
        c = copy.deepcopy(cell)
        c["workload"]["traffic"]["rate"] = rate
        args = argparse.Namespace(workload=cell["entry"]["name"], seed=1000 + i,
                                  seconds=seconds, trace=0)
        rec, out = serve.run(c, args, devices, time.perf_counter())
        rec["kind"], rec["peaks"] = "serve", None
        vals = {}
        for name in ("ttft_p95_ms", "itl_p95_ms", "serve_tokens_per_s"):
            mod = common.load_module(f"{common.BENCH}/metrics/{name}.py")
            vals[name] = mod.read(rec)
        w0, w1 = rec["w0"], rec["w1"]
        done = sum(1 for r in rec["reqs"].values()
                   if r["times"] and w0 <= r["times"][-1] <= w1)
        emit(kind="sweep", rate=rate, attempted=out["attempted"],
             finished_in_window=done, queue=rec["queue"], **vals)
        del rec, out
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="",
                    help="a serving cell's offered rates to sweep instead")
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",") if s]
    control = [int(s) for s in a.control_seeds.split(",") if s]
    cell = common.cell(a.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        common.log(f"needs a TPU; JAX found {devices[0].platform} devices")
        return 2
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if a.rates:
        sweep(cell, [float(r) for r in a.rates.split(",")], a.seconds, devices)
    elif cell["workload"]["runner"] == "train":
        train_readings(cell, seeds, control)
    else:
        serve_readings(cell, seeds, control, a.seconds, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
