"""Smoke-size cells for the CPU tests: the published configurations and
workloads of the benchmark, cut to a size the CPU runs in seconds."""
from __future__ import annotations

import argparse
import copy
import os

from chipbench import common

OPT = {"hidden_size": 64, "num_hidden_layers": 2, "ffn_dim": 128,
       "num_attention_heads": 4, "vocab_size": 256,
       "max_position_embeddings": 128, "word_embed_proj_dim": 64}
OPT_PROGRAM = {"n_layers": 2, "d_model": 64, "vocab_size": 256, "n_heads": 4,
               "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
               "max_position": 128}
QWEN = {"hidden_size": 64, "num_hidden_layers": 2, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256}
QWEN_PROGRAM = {"n_layers": 2, "d_model": 64, "vocab_size": 256,
                "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                "attn_chunk": None}


def config(name: str, compute: str = None) -> dict:
    conf = common.load_json(os.path.join(common.BENCH, "configs",
                                         name + ".json"))
    small, prog = (OPT, OPT_PROGRAM) if name.startswith("opt") else (
        QWEN, QWEN_PROGRAM)
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


def cell(name: str, compute: str = None) -> dict:
    """The benchmark's cell ``name`` with its configuration and workload cut
    to smoke size."""
    c = copy.deepcopy(listed(name))
    c["config"] = config(c["entry"]["config"], compute)
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
