"""Smoke-size cells for the CPU tests: the published configurations and
workloads of the benchmark, cut to a size the CPU runs in seconds."""
from __future__ import annotations

import argparse
import copy
import os

from chipbench import common, models


def config(name: str, compute: str = None, linear: str = None) -> dict:
    """The configuration file ``name`` cut to the smoke sizes its model
    module gives, with the program's ``compute`` dtype or ``linear`` where
    given."""
    conf = common.load_json(os.path.join(common.BENCH, "configs",
                                         name + ".json"))
    if linear:
        conf["program"]["linear"] = linear
    small, prog = models.of(conf).smoke(conf)
    conf.update(small)
    conf["program"]["overrides"] = {**conf["program"]["overrides"], **prog}
    if compute:
        conf["program"]["overrides"]["compute_dtype"] = compute
    return conf


# the end-to-end metrics of the serving cells that BENCHMARK.json does not
# hold yet (PERF.md, Open questions)
UNLISTED = {
    "qwen3-0.6b.serve-steady": [("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"),
                                ("setup_s", "s")],
    "qwen3-0.6b.serve-longctx": [("itl_p95_ms", "ms"),
                                 ("serve_tokens_per_s", "tokens/s"),
                                 ("setup_s", "s")],
}


def listed(name: str) -> dict:
    """The cell as the harness finds it: from BENCHMARK.json, or from its
    files when the benchmark does not list it."""
    if name not in UNLISTED:
        return common.cell(name)
    conf = name.rsplit(".", 1)[0]
    return {"entry": {"name": name, "config": conf, "chips": 1},
            "config": common.load_json(os.path.join(
                common.BENCH, "configs", conf + ".json")),
            "workload": common.load_json(os.path.join(
                common.BENCH, "workloads", name + ".json")),
            "end_to_end": [{"name": n, "unit": u} for n, u in UNLISTED[name]],
            "per_layer": []}


def cell(name: str, compute: str = None, linear: str = None) -> dict:
    """The benchmark's cell ``name`` with its configuration and workload cut
    to smoke size."""
    c = copy.deepcopy(listed(name))
    c["config"] = config(c["entry"]["config"], compute, linear)
    wl = c["workload"]
    if wl["runner"] == "train":
        wl["traffic"].update(batch=2, seq=32, ring=4)
        wl["log_every"] = 2
        wl["trace_s"] = 0.5
    else:
        t = wl["traffic"]
        t["warm_s"] = 0.5
        for k in ("prompt", "output"):
            t[k] = {"dist": "uniform", "min": 4, "max": 12, "multiple": 4}
        t.update(rate=8.0, clients=2, pool=8)
        wl.update(slots=4, max_len=32, page_size=4, prefill_chunk=8,
                  warm_chunks=[4, 8], trace_s=0.5)
        wl["check"].update(tokens=20, requests=3, served=16)
    return c


def args(name: str, seed: int = 3, seconds: float = 1.0, trace: int = 0):
    return argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=trace)
