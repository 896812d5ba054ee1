"""Benchmark harness — one registered suite per paper table/figure.

Each suite prints ``name,us_per_call,derived`` CSV (the seed contract) and
writes a machine-readable ``BENCH_<suite>.json`` at the repo root — the
performance trajectory that ``python -m repro.perf.check`` gates against
the last committed baseline.  Mapping to the paper:

* ff_timing        — Tables 1, 5, 10 (ff time, DENSE vs DYAD variants),
                     §3.4.3 (-CAT), plus the fused-kernel autotune cells
* ff_fused         — beyond-paper: the whole-ff megakernel (one Pallas
                     grid, hidden never leaves VMEM) vs the split kernel
                     chain vs DENSE at OPT-125m/350m ff dims
* attention        — beyond-paper: flash prefill/decode kernels vs the
                     XLA sdpa paths at OPT dims (4k/32k), decode-step
                     latency for both serve engines
* quality          — Tables 2, 3 (quality parity; offline stand-in stream)
* memory           — Table 11 (params / checkpoint / in-training memory)
* width_sweep      — Figure 6 (speedup vs model width)
* mnist            — §3.4.5 (vision probe on CPU)
* quant            — beyond-paper: int8/fp8 quantized weight streams
                     (in-kernel dequant) vs the fp megakernel at a
                     decode-shaped batch, int8 paged-KV capacity, and
                     end-to-end greedy token match vs the fp routes
* serve_throughput — beyond-paper: end-to-end serving tokens/sec
* train_step       — §1 headline (training speed): full fwd+bwd+AdamW step
                     on DYAD vs DENSE ff blocks, einsum-VJP vs fused bwd
* smoke            — tiny CI suite (< 1 min): dense-vs-dyad ff + train-step
                     cells plus an autotune cache exercise

Roofline terms (EXPERIMENTS §Roofline) come from the dry-run
(``python -m repro.launch.dryrun``), which needs the 512-device env and is
therefore not run from here; per-record FLOP/byte counts are attached by
the suites via ``repro.perf.record.hlo_metrics``.

    python benchmarks/run.py --suite ff_timing
    python benchmarks/run.py                       # every suite
    python -m repro.perf.check                     # gate vs committed JSON
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# allow `python benchmarks/run.py` from the repo root (the documented form):
# the `benchmarks` package lives next to this file's parent directory.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def main(argv=None) -> int:
    from repro import obs
    from repro.launch.cache import enable_compile_cache
    from repro.perf import registry

    enable_compile_cache()

    # importing the suite modules registers them (repro.perf.register)
    from benchmarks import (bench_attention, bench_ff_fused,  # noqa: F401
                            bench_ff_timing, bench_memory, bench_mnist,
                            bench_quality, bench_quant,
                            bench_serve_throughput, bench_smoke,
                            bench_tp_scaling, bench_train_step,
                            bench_width_sweep)

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--suite", action="append", default=None,
                   help="suite to run (repeatable; default: all)")
    p.add_argument("--out-dir", default=_ROOT,
                   help="where BENCH_<suite>.json is written "
                        "(default: repo root)")
    p.add_argument("--no-json", action="store_true",
                   help="print CSV only, skip BENCH_<suite>.json")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="record suite/autotune spans while benchmarking and "
                        "export Chrome-trace JSON here (diff two runs with "
                        "python -m repro.perf.timeline)")
    p.add_argument("--list", action="store_true",
                   help="list registered suites and exit")
    p.add_argument("legacy_suites", nargs="*",
                   help="positional suite names (seed-compatible form)")
    args = p.parse_args(argv)

    if args.list:
        print("\n".join(registry.available_suites()))
        return 0

    if args.trace:
        obs.enable()

    wanted = (args.suite or []) + args.legacy_suites
    wanted = wanted or registry.available_suites()
    print("name,us_per_call,derived")
    for name in wanted:
        t0 = time.time()
        with obs.span(f"suite:{name}", cat="bench"):
            rec = registry.run_suite(name, out_dir=args.out_dir,
                                     write=not args.no_json)
        note = "" if args.no_json else f" -> {rec.path}"
        print(f"# suite {name} done in {time.time() - t0:.1f}s"
              f" ({len(rec.results)} records){note}", file=sys.stderr)

    if args.trace:
        obs.export(args.trace)
        print(f"# trace -> {args.trace}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
