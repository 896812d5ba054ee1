"""Serving launcher: initialize (or restore) a model and run batched
generation — the interactive counterpart of the decode_* dry-run cells.

Two engines (``--engine``):

* ``batch`` (default) — :class:`repro.serve.Engine`: one jitted single-pass
  prefill for the whole (B, S) int32 prompt batch, then one jitted
  ``lax.scan`` for the whole decode loop.  Output: (B, new_tokens) int32.
* ``continuous`` — :class:`repro.serve.ContinuousBatchingEngine`: submits
  ``--requests`` prompts with heterogeneous lengths into ``--slots`` cache
  slots; finished sequences retire at EOS/length and queued requests
  back-fill freed slots, all through one jitted padded-batch step.

The KV/SSM cache is allocated once at ``prompt_len + new_tokens`` (fp32 by
default; see ``Engine(cache_dtype=...)``) and persists across the decode.

Example:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b --smoke \
        --batch 4 --prompt-len 16 --new-tokens 32
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b --smoke \
        --engine continuous --requests 12 --slots 4
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp

from repro import configs, faults, obs
from repro.checkpoint import CheckpointManager
from repro.launch.cache import enable_compile_cache
from repro.models import model
from repro.serve import ContinuousBatchingEngine, Engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--linear", default=None)
    ap.add_argument("--engine", choices=("batch", "continuous"),
                    default="batch")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--requests", type=int, default=12,
                    help="continuous engine: number of submitted requests")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous engine: cache slots (padded batch)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="continuous engine: retire sequences at this token")
    ap.add_argument("--page-size", type=int, default=None,
                    help="continuous engine: paged KV cache with this many "
                         "tokens per page (default: dense per-slot rings)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="paged mode: physical pages in the pool incl. "
                         "scratch (default: full-capacity slots)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="paged mode: prefill prompts in chunks of this "
                         "many tokens, interleaved with decode steps")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged mode: share full prompt-prefix pages "
                         "between requests (skips re-prefill)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record runtime spans (admission/prefill/decode/"
                         "sync/retire) and export Chrome-trace JSON here — "
                         "open in ui.perfetto.dev, diff two runs with "
                         "python -m repro.perf.timeline")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the final serving metrics snapshot (TTFT/"
                         "ITL percentiles, tok/s, queue depth, page-pool "
                         "occupancy, prefix hits) as JSON")
    ap.add_argument("--report-every", type=float, default=None,
                    metavar="SECONDS",
                    help="continuous engine: periodic one-line stats report")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="fault-injection schedule, e.g. 'page_exhaustion:"
                         "p=0.05;nan_logits:at_step=3;slow_step:ms=50' "
                         "(overrides REPRO_FAULT; see repro.faults)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="continuous engine: per-request wall-clock budget; "
                         "expired requests retire with reason=deadline "
                         "keeping their partial output")
    ap.add_argument("--tp", type=int, default=1,
                    help="shard the model axis over this many devices: "
                         "dispatches the shard_map TP kernels "
                         "(kernels/tp.py) when d_ff / KV heads divide, "
                         "einsum fallback (visible in --metrics-json "
                         "routes) otherwise.  On CPU force devices first: "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel axis size (dp * tp must equal the "
                         "visible device count when either exceeds 1)")
    ap.add_argument("--quant-weights", choices=("int8", "fp8"), default=None,
                    help="quantize the DYAD ff weights offline "
                         "(repro.quant.quantize_params sidecars) and stream "
                         "them through the in-kernel-dequant bodies; "
                         "requires a kernel-routed linear spec.  "
                         "REPRO_KERNEL_QUANT=off restores fp32 routes")
    ap.add_argument("--quant-kv", choices=("int8",), default=None,
                    help="paged mode: int8 KV page pools with per-token-row "
                         "fp32 scale pools, dequantized in-kernel at decode "
                         "(~2-4x more tokens per HBM byte)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune", action="store_true",
                    help="pre-tune Pallas kernel tiles for this model's "
                         "dyad shapes before compiling (repro.perf); only "
                         "meaningful with a kernel-routed linear spec, "
                         "e.g. --linear dyad_it_4_kernel")
    args = ap.parse_args()

    enable_compile_cache()
    if args.trace:
        obs.enable()
    if args.faults:
        faults.configure(args.faults, seed=args.fault_seed)

    # engines capture the ambient mesh at construction (per-shard autotune
    # keys) and the layer dispatch consults it at trace time, so the whole
    # run sits inside one activation-sharding context
    mesh_ctx = contextlib.nullcontext()
    if args.tp > 1 or args.dp > 1:
        from repro.launch.mesh import make_mesh
        from repro.sharding import ctx as shard_ctx
        mesh = make_mesh((args.dp, args.tp))
        mesh_ctx = shard_ctx.activation_sharding(mesh, dp=("data",),
                                                 model="model")
        print(f"[serve] mesh: data={args.dp} model={args.tp}")
    with mesh_ctx:
        _run(args)


def _run(args):
    linear = configs.linear_cfg(args.linear) if args.linear else None
    cfg = configs.get(args.arch, smoke=args.smoke, linear=linear)
    if args.quant_weights:
        cfg = cfg.replace(linear=cfg.linear.replace(quant=args.quant_weights))
    if args.quant_kv:
        if args.engine != "continuous" or args.page_size is None:
            raise SystemExit("--quant-kv requires --engine continuous with "
                             "--page-size (the quantized layout is the "
                             "paged pool)")
        cfg = cfg.replace(kv_quant=args.quant_kv)
    key = jax.random.PRNGKey(args.seed)
    params = model.init_params(cfg, key)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        if mgr.latest_step() is not None:
            step, state = mgr.restore({"params": params})
            params = state["params"]
            print(f"[serve] restored checkpoint step {step}")
    if args.quant_weights:
        from repro import quant
        params = quant.quantize_params(params, args.quant_weights)
        print(f"[serve] quantized DYAD weight sidecars: {args.quant_weights}")

    max_len = args.prompt_len + args.new_tokens

    if args.engine == "continuous":
        engine = ContinuousBatchingEngine(
            cfg, params, n_slots=args.slots, max_len=max_len,
            eos_id=args.eos_id, temperature=args.temperature, seed=args.seed,
            autotune=args.autotune, page_size=args.page_size,
            n_pages=args.n_pages, prefill_chunk=args.prefill_chunk,
            prefix_cache=args.prefix_cache,
            report_every_s=args.report_every)
        lengths = [max(1, args.prompt_len - (i % 4)) for i in range(args.requests)]
        prompts = [
            jax.random.randint(jax.random.fold_in(key, i), (lengths[i],), 0,
                               cfg.vocab_size)
            for i in range(args.requests)]
        t0 = time.perf_counter()
        uids = [engine.submit(p, args.new_tokens,
                              deadline_s=args.deadline_s) for p in prompts]
        results = engine.run()
        dt = time.perf_counter() - t0
        total = sum(len(results[u]) for u in uids)
        print(f"[serve] continuous: {args.requests} requests over "
              f"{args.slots} slots, {total} tokens in {dt:.2f}s "
              f"({total / dt:.1f} tok/s)")
        if engine.paged:
            print(f"[serve] paged: {engine.stats}")
        if faults.active():
            print(f"[serve] faults: {faults.snapshot()}")
        if engine.demoted:
            # the NaN guard moved the engine onto weaker kernel routes:
            # the run finished, but not on the routes it was asked for
            print(f"[serve] WARNING: demoted kernel routes: {engine.demoted}")
        print({u: results[u][:8] for u in uids[:4]})
        print(f"[serve] summary: {engine.format_summary()}")
        _finish(args, engine.metrics)
        return

    engine = Engine(cfg, params, max_len=max_len, autotune=args.autotune)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    frames = None
    if cfg.family == "encdec":
        frames = jax.random.normal(
            key, (args.batch, cfg.n_frames, cfg.frontend_dim), cfg.cdtype)

    t0 = time.perf_counter()
    out = engine.generate(prompts, args.new_tokens,
                          temperature=args.temperature, key=key,
                          frames=frames)
    dt = time.perf_counter() - t0
    tps = args.batch * args.new_tokens / dt
    print(f"[serve] generated {out.shape} in {dt:.2f}s ({tps:.1f} tok/s)")
    print(out[:, :16])
    print(f"[serve] summary: {obs.format_serving_line(engine.metrics)}")
    _finish(args, engine.metrics)


def _finish(args, metrics):
    """Export the trace / metrics snapshot requested on the CLI."""
    if args.metrics_json:
        # route-dispatch counters ride along: ff_tp/attn_tp tp_fused vs
        # tp_fallback make a silently lost kernel route visible here.
        metrics.write_json(args.metrics_json, routes=obs.routes_snapshot(),
                           faults=faults.snapshot())
        print(f"[serve] metrics: {args.metrics_json}")
    if args.trace:
        t = obs.get_tracer()
        n = len(t) if t else 0
        obs.export(args.trace)
        print(f"[serve] trace: {args.trace} ({n} events) — open in "
              f"ui.perfetto.dev")


if __name__ == "__main__":
    main()
