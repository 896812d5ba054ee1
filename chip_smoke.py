#!/usr/bin/env python3
"""Drive DYAD training and serving once on a TPU, at published widths.

    python3 chip_smoke.py              # one chip: the train and serve phases
    python3 chip_smoke.py --chips 4    # four chips: Qwen3-0.6B served at tp=4,
                                       # compared with the same model at tp=1

Run it from the root of a checkout on a machine with a TPU.  It uses the
same entry points as ``repro.launch.train`` / ``repro.launch.serve``, with
random weights and data made from ``--seed``:

* train: OPT-125m (the paper's model) with DYAD-IT n=4 on the fused Pallas
  kernels, batch 8 x 512 tokens, a few AdamW steps through ``Trainer``;
  every loss must be finite;
* serve: Qwen3-0.6B with the DYAD ff megakernel, the continuous-batching
  engine on a paged KV cache (16-token pages, 4 slots), 8 requests of
  128-512 prompt tokens and 32 new tokens each; every request must retire
  at EOS or its token budget.

Each phase also checks that the kernels ran compiled (never the Pallas
interpreter), that no route was silently demoted, and that one forward on
the kernel route agrees with the einsum route within a bound derived from
bf16 rounding and the longest contraction (:func:`logit_bound`).  It exits
non-zero on any failure, and when JAX finds no TPU.  The last line of
stdout is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))

TRAIN_ARCH, TRAIN_LINEAR = "opt125m", "dyad_it_4_kernel"
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 5
SERVE_ARCH, SERVE_LINEAR = "qwen3_0_6b", "dyad_it_4_kernel_ffused"
SERVE_SLOTS, SERVE_PAGE, SERVE_NEW = 4, 16, 32
SERVE_PROMPTS = (128, 512, 256, 384, 512, 128, 384, 256)
TP = 4

# bf16 unit roundoff: bf16 keeps an 8-bit significand
BF16_U = 2.0 ** -8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def env(**kv):
    """Set environment variables for the block (route switches are read at
    trace time), then restore them."""
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update(kv)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def longest_contraction(cfg) -> int:
    """Longest dot product of one forward: a DYAD projection sums its two
    components over 2 * d/n inputs; the attention output projection over
    n_heads * head_dim; the unembedding over d_model."""
    n = cfg.linear.n_dyad
    return max(2 * cfg.d_ff // n, 2 * cfg.d_model // n,
               cfg.n_heads * cfg.hd, cfg.d_model)


def logit_bound(cfg, ref_max: float) -> float:
    """Largest accepted |kernel - einsum| logit difference.

    Both routes feed the MXU bf16 operands but round to bf16 at different
    points (the kernels keep pre-activations and partial sums in fp32, the
    einsum route rounds each projection's output).  A rounding moves one
    product of a length-K dot by at most u * |product|; K such errors of
    random sign add up to about u * sqrt(K) times the largest product, and
    no product of the final contraction exceeds the largest logit by much.
    So the bound is u * sqrt(K_max) * max|logit|, with K_max from
    :func:`longest_contraction`.  A wrong tile, index or permutation
    changes logits by O(max|logit|), well above it."""
    return BF16_U * math.sqrt(longest_contraction(cfg)) * ref_max


def compare_logits(cfg, logits_k, logits_ref, what: str) -> dict:
    import jax.numpy as jnp

    diff = float(jnp.max(jnp.abs(logits_k - logits_ref)))
    ref_max = float(jnp.max(jnp.abs(logits_ref)))
    bound = logit_bound(cfg, ref_max)
    finite = bool(jnp.all(jnp.isfinite(logits_k)))
    log(f"{what}: max|logit diff| {diff!r} bound {bound!r} "
        f"(u_bf16 * sqrt(K={longest_contraction(cfg)}) * max|logit| "
        f"{ref_max!r}), ratio {diff / bound!r}")
    check(finite, f"{what}: kernel-route logits are not finite")
    check(diff <= bound, f"{what}: logit diff {diff} exceeds bound {bound}")
    return {"max_abs_diff": diff, "bound": bound, "ref_max_abs": ref_max}


def einsum_twin(lin):
    """The einsum route computing the same function as kernel route
    ``lin``.  The ff megakernel runs the mixed-variant ff (up IT, down OT),
    whose einsum twin is the ``fuse_mlp`` dataflow (spec ``dyad_it_4_fused``);
    per-projection kernels mirror the plain DYAD linears (``dyad_it_4``)."""
    return lin.replace(use_kernel=False, fuse_ff_kernel=False,
                       fuse_mlp=lin.fuse_ff_kernel)


def reference_logits(cfg, params, tokens):
    """The same forward on the einsum route: jnp DYAD linears and the XLA
    attention paths."""
    import jax

    from repro.models import model

    cfg_ref = cfg.replace(linear=einsum_twin(cfg.linear))
    with env(REPRO_KERNEL_ATTN="xla"):
        fwd = jax.jit(lambda p, t: model.forward(cfg_ref, p, {"tokens": t})[0])
        return fwd(params, tokens)


def check_routes(routes: dict, want: dict, what: str) -> None:
    """Each op in ``want`` took exactly the wanted route (and at least
    once); no demotion ladder rung fired."""
    log(f"{what} routes: {routes}")
    for op, route in want.items():
        taken = {k: n for k, n in routes.items() if k.split(":")[0] == op}
        check(set(taken) == {f"{op}:{route}"},
              f"{what}: {op} must take only route {route!r}, took {taken}")
    demotions = [k for k in routes if k.startswith("demote:")]
    check(not demotions, f"{what}: routes demoted: {demotions}")


# -- phases -------------------------------------------------------------------


def train_phase(cfg, *, batch: int, seq: int, steps: int, seed: int) -> dict:
    """A few optimizer steps through ``Trainer``, then one forward on the
    kernel route against the einsum route."""
    import jax
    import numpy as np

    from repro import obs
    from repro.data import SyntheticLM
    from repro.models import model
    from repro.optim import AdamW, schedule
    from repro.train import Trainer, init_train_state, make_train_step

    obs.reset_route_counts()
    opt = AdamW(lr=schedule.warmup_cosine(1e-3, 1, steps))
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                       global_batch=batch, seed=seed)
    state = init_train_state(cfg, opt, jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=0).lower(
        state, data.batch(0)).compile()
    compile_s = time.perf_counter() - t0
    log(f"train: {cfg.name} linear={TRAIN_LINEAR} batch {batch} x seq {seq}:"
        f" train step compiled in {compile_s!r} s")

    losses, skipped, step_s = [], [], []

    def timed_step(state, b):
        t = time.perf_counter()
        state, m = step(state, b)
        jax.block_until_ready(m["loss"])
        step_s.append(time.perf_counter() - t)
        losses.append(float(m["loss"]))
        skipped.append(float(m["nonfinite"]))
        return state, m

    trainer = Trainer(timed_step, state, data, log_every=steps)
    trainer.run(steps)
    tok_s = [batch * seq / s for s in step_s]
    log(f"train: losses {losses}")
    log(f"train: step seconds {step_s} tokens/s {tok_s}")
    check(len(losses) == steps, f"train: ran {len(losses)} of {steps} steps")
    check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    check(not any(skipped), f"train: steps skipped as non-finite {skipped}")

    params = trainer.state["params"]
    tokens = data.batch(0)["tokens"]
    logits = jax.jit(lambda p, t: model.forward(cfg, p, {"tokens": t})[0])(
        params, tokens)
    routes = obs.routes_snapshot()
    cmp = compare_logits(cfg, logits, reference_logits(cfg, params, tokens),
                         "train forward kernel vs einsum")
    return {"compile_s": compile_s, "step_s": step_s, "losses": losses,
            "routes": routes, "logits": cmp}


def serve_requests(engine, prompts, new_tokens: int):
    """Submit every prompt, step the engine until it drains; returns the
    retired requests and the wall seconds."""
    t0 = time.perf_counter()
    for p in prompts:
        engine.submit(p, new_tokens)
    done = []
    while engine.slots.active or engine.queue:
        done += engine.step()
    return done, time.perf_counter() - t0


def make_prompts(cfg, lengths, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
            for n in lengths]


def serve_phase(cfg, *, prompt_lens, new_tokens: int, slots: int, page: int,
                seed: int, mesh=None) -> dict:
    """Serve the requests twice through one continuous-batching engine (the
    first pass compiles, the second runs warm), then compare one prompt's
    forward on the kernel route with the einsum route."""
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.models import model
    from repro.serve import ContinuousBatchingEngine
    from repro.serve.engine import RetireReason
    from repro.sharding import ctx as shard_ctx

    tag = "serve" if mesh is None else f"serve tp={mesh.shape['model']}"
    obs.reset_route_counts()
    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = make_prompts(cfg, prompt_lens, seed)
    max_len = max(prompt_lens) + new_tokens
    mesh_ctx = (contextlib.nullcontext() if mesh is None
                else shard_ctx.activation_sharding(mesh, dp=("data",),
                                                   model="model"))
    with mesh_ctx:
        engine = ContinuousBatchingEngine(
            cfg, params, n_slots=slots, max_len=max_len, page_size=page,
            seed=seed)
        passes = []
        for name in ("cold", "warm"):
            done, wall = serve_requests(engine, prompts, new_tokens)
            n_tok = sum(len(r.tokens) for r in done)
            passes.append({"pass": name, "wall_s": wall, "tokens": n_tok,
                           "requests": len(done)})
            log(f"{tag}: {name} pass: {len(done)} requests, {n_tok} tokens "
                f"in {wall!r} s ({n_tok / wall!r} tok/s)")
            check(len(done) == len(prompts),
                  f"{tag}: {len(done)} of {len(prompts)} requests retired")
            bad = [(r.uid, r.retire_reason) for r in done
                   if r.retire_reason not in (RetireReason.EOS,
                                              RetireReason.MAX_NEW)]
            check(not bad, f"{tag}: requests retired abnormally: {bad}")
        check(engine.demoted == [], f"{tag}: engine demoted {engine.demoted}")
        log(f"{tag}: compile estimate (cold - warm) "
            f"{passes[0]['wall_s'] - passes[1]['wall_s']!r} s; "
            f"{engine.format_summary()}")
        tokens = jnp.asarray(prompts[prompt_lens.index(max(prompt_lens))])[None]
        logits = jax.jit(
            lambda p, t: model.forward(cfg, p, {"tokens": t})[0])(params,
                                                                  tokens)
    routes = obs.routes_snapshot()
    return {"passes": passes, "routes": routes, "params": params,
            "tokens": tokens, "logits": logits}


# -- main ---------------------------------------------------------------------


def one_chip(args) -> None:
    from repro import configs

    cfg = configs.get(TRAIN_ARCH, smoke=False,
                      linear=configs.linear_cfg(TRAIN_LINEAR))
    res = train_phase(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                      steps=TRAIN_STEPS, seed=args.seed)
    check_routes(res["routes"], {"pallas_exec": "compiled", "attn": "flash",
                                 "kernel_bwd": "pallas"}, "train")

    cfg = configs.get(SERVE_ARCH, smoke=False,
                      linear=configs.linear_cfg(SERVE_LINEAR))
    res = serve_phase(cfg, prompt_lens=list(SERVE_PROMPTS),
                      new_tokens=SERVE_NEW, slots=SERVE_SLOTS,
                      page=SERVE_PAGE, seed=args.seed)
    check_routes(res["routes"], {"pallas_exec": "compiled", "ff": "fused",
                                 "attn": "flash"}, "serve")
    compare_logits(cfg, res["logits"],
                   reference_logits(cfg, res["params"], res["tokens"]),
                   "serve forward kernel vs einsum")


def tp_phase(cfg, mesh, *, prompt_lens, new_tokens: int, slots: int,
             page: int, seed: int) -> None:
    """Serve under a (1, tp) mesh through the shard_map kernels
    (``kernels/tp.py``), then compare one forward on that mesh with the
    same forward unsharded on one chip."""
    import jax

    from repro.models import model

    tp = mesh.shape["model"]
    ids = {d.id for d in mesh.devices.flat}
    log(f"mesh {dict(mesh.shape)} over devices {sorted(ids)}")
    check(mesh.devices.size == tp and len(ids) == tp,
          f"mesh spans {len(ids)} devices, not {tp}")
    res = serve_phase(cfg, prompt_lens=prompt_lens, new_tokens=new_tokens,
                      slots=slots, page=page, seed=seed, mesh=mesh)
    check_routes(res["routes"], {"pallas_exec": "compiled", "ff": "fused",
                                 "attn": "flash", "ff_tp": "tp_fused",
                                 "attn_tp": "tp_fused"}, f"serve tp={tp}")
    logits1 = jax.jit(lambda p, t: model.forward(cfg, p, {"tokens": t})[0])(
        res["params"], res["tokens"])
    compare_logits(cfg, res["logits"], logits1,
                   f"serve forward tp={tp} vs tp=1")


def four_chips(args) -> None:
    """Qwen3-0.6B served at tp=4, compared with tp=1."""
    from repro import configs
    from repro.launch.mesh import make_mesh

    cfg = configs.get(SERVE_ARCH, smoke=False,
                      linear=configs.linear_cfg(SERVE_LINEAR))
    tp_phase(cfg, make_mesh((1, TP)), prompt_lens=list(SERVE_PROMPTS),
             new_tokens=SERVE_NEW, slots=SERVE_SLOTS, page=SERVE_PAGE,
             seed=args.seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, TP), default=1,
                    help=f"1: train + serve phases on one chip; {TP}: only "
                         f"the tp={TP} serve path and its tp=1 comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} devices",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" found {len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compile_cache
    from repro.perf import autotune

    cache_dir = enable_compile_cache()
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"device {dev.device_kind} x {len(devices)}; compile cache "
        f"{cache_dir} ({warm} entries at start)")

    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == TP else one_chip)(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        # no --autotune here: these are the default or cached tiles
        for key, blocks in sorted(autotune.resolved_blocks().items()):
            log(f"tiles {key}: {blocks}")
    log(f"all phases passed in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
