"""Training launcher.

Single-host (this container) it runs real steps on the local device(s); on a
real cluster the same entrypoint runs under ``jax.distributed.initialize()``
(multi-host: one process per host, the data pipeline shards by process index,
and the mesh comes from ``mesh.make_production_mesh``).

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch opt125m --smoke \
        --steps 100 --linear dyad_it_4
    PYTHONPATH=src python -m repro.launch.train --arch qwen3_0_6b --smoke \
        --steps 50 --linear dense --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import signal

import jax

from repro import configs, faults, obs
from repro.data import SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.optim import AdamW, Compressor, schedule
from repro.train import Trainer, init_train_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--linear", default=None,
                    help="dense | dyad_<variant>_<n>[_cat]")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record train_step/checkpoint/autotune spans and "
                         "export Chrome-trace JSON here (ui.perfetto.dev)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the final training metrics snapshot as JSON")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="fault-injection schedule, e.g. "
                         "'nan_loss:at_step=5;ckpt_io:p=0.3;slow_step:ms=20' "
                         "(overrides REPRO_FAULT; see repro.faults)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--nan-strikes", type=int, default=3,
                    help="consecutive non-finite steps before rolling back "
                         "to the last checkpoint")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune", action="store_true",
                    help="pre-tune Pallas kernel tiles (forward AND the "
                         "dgrad/wgrad backward ops) for this model's dyad "
                         "shapes before the train step compiles "
                         "(repro.perf); only meaningful with a "
                         "kernel-routed linear spec, e.g. "
                         "--linear dyad_it_4_kernel")
    args = ap.parse_args()

    enable_compile_cache()
    if args.trace:
        obs.enable()
    if args.faults:
        faults.configure(args.faults, seed=args.fault_seed)

    linear = configs.linear_cfg(args.linear) if args.linear else None
    cfg = configs.get(args.arch, smoke=args.smoke, linear=linear)
    print(f"[train] arch={cfg.name} family={cfg.family} "
          f"linear={cfg.linear.impl}({cfg.linear.variant},n={cfg.linear.n_dyad})")

    if args.autotune:
        # tune BEFORE the first jit trace: the train step's value_and_grad
        # resolves fwd + dgrad/wgrad tiles at trace time (batch*seq rows).
        from repro.perf.autotune import ensure_tuned_for_model

        # seq_len additionally covers the flash_prefill tiles the training
        # forward resolves for flash_attn configs
        tuned = ensure_tuned_for_model(cfg, tokens=args.batch * args.seq_len,
                                       include_bwd=True,
                                       seq_len=args.seq_len)
        print(f"[train] autotuned {len(tuned)} kernel-shape entries")

    opt = AdamW(lr=schedule.warmup_cosine(args.lr, args.steps // 10 + 1,
                                          args.steps))
    comp = Compressor(codec=args.compress)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                       global_batch=args.batch, seed=args.seed,
                       shard=jax.process_index(),
                       num_shards=jax.process_count())
    state = init_train_state(cfg, opt, jax.random.PRNGKey(args.seed),
                             compressor=comp)
    step = jax.jit(make_train_step(cfg, opt, compressor=comp),
                   donate_argnums=0)

    trainer = Trainer(step, state, data, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, log_every=10,
                      nan_strikes=args.nan_strikes)
    # SIGTERM (spot reclaim / scheduler) AND SIGINT (operator ctrl-C) both
    # end the run through the same path: finish the in-flight step, write a
    # final blocking checkpoint, exit 0 — the next launch auto-resumes.
    trainer.install_preemption_handler(
        signals=(signal.SIGTERM, signal.SIGINT))
    _, metrics = trainer.run(args.steps)
    if trainer._preempted:
        print(f"[train] preempted at step {trainer.step}: checkpoint saved, "
              "relaunch to resume")
    loss = float(metrics["loss"]) if "loss" in metrics else float("nan")
    print(f"[train] done at step {trainer.step}: loss={loss:.4f} "
          f"stragglers={len(trainer.straggler_events)}")
    snap = trainer.metrics.snapshot()
    h = snap["histograms"].get("step_time_s")
    if h:
        print(f"[train] summary: steps={h['count']} "
              f"step_ms p50={h['p50'] * 1e3:.1f} p99={h['p99'] * 1e3:.1f} "
              f"tok/s={snap['gauges'].get('tokens_per_s', {}).get('value', 0):.0f} "
              f"stragglers={snap['counters'].get('straggler_count', 0)}")
    if args.metrics_json:
        trainer.metrics.write_json(args.metrics_json,
                                   faults=faults.snapshot())
        print(f"[train] metrics: {args.metrics_json}")
    if args.trace:
        obs.export(args.trace)
        print(f"[train] trace: {args.trace} — open in ui.perfetto.dev")


if __name__ == "__main__":
    main()
