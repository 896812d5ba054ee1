"""quant suite: quantized DYAD serving — int8/fp8 weight streams through
the in-kernel-dequant megakernel, int8 paged KV capacity, and end-to-end
greedy quality vs the fp routes.

Decode batches are weight-bound: the ff cell times the quantized
megakernel (``ops.dyad_ff_quant``) against the fp megakernel at a
decode-shaped batch and attaches the roofline-modeled per-device times
(constants from ``launch.roofline``, bf16 serving compute) where the ONLY
difference is the weight-stream bytes — payload + fp32 scale sidecars vs
bf16 tensors.  ``bound_speedup`` (fp bound / quant bound) is the
deliverable and must exceed 1.5x at these dims.  On CPU both routes
execute the Pallas interpreter, so (as everywhere in this repo) the
absolute wall-clock is NOT a TPU number.

The KV cell doesn't model anything: it allocates the real paged pools
(``init_paged_kv_cache``) both ways and reports bytes/token from leaf
``nbytes`` — ``capacity_ratio`` (tokens per HBM byte, >= 1.8x required)
is exact arithmetic on the layouts.

The quality cell runs the continuous engine twice on the real smoke model
— fp routes vs int8 weights + int8 paged KV (flash decode) — and reports
the greedy token match fraction, which must be >= 0.99.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, force_attn_route, time_fn
from repro import configs, obs, perf, quant
from repro.kernels import ops as kops
from repro.launch.roofline import HBM_BW, PEAK_FLOPS
from repro.layers import attention as attn_lib
from repro.layers import mlp
from repro.models import model
from repro.perf.autotune import autotune_dyad

TOKENS = 32                 # decode-shaped batch: weight-bound regime
D, DFF = 768, 3072          # opt125m ff dims
N_DYAD = 4
ACT = "gelu"

KV_HEADS, HEAD_DIM, PAGE, N_PAGES = 8, 64, 16, 32


def _ff_bound_us(w_bytes_per_elem: float, scales: bool) -> float:
    """Roofline per-device microseconds for one decode-shaped ff call:
    bf16 activations either way; only the weight stream changes."""
    act = 2                                      # bf16 serving compute
    flops = 8 * TOKENS * D * DFF / N_DYAD
    w_elems = 4 * D * DFF / N_DYAD               # up x2 + down x2
    w_bytes = w_elems * w_bytes_per_elem
    if scales:                                   # fp32 (block, out_row)
        w_bytes += 2 * (DFF + D) * 4
    hbm = TOKENS * D * act * 2 + w_bytes         # x in + y out + weights
    return max(flops / PEAK_FLOPS, hbm / HBM_BW) * 1e6


def _pretune(qdt: str):
    n = N_DYAD
    k, j = D // n, DFF // n
    autotune_dyad("dyad_ff_fused", TOKENS, n, k, k, d_mid=j, act=ACT,
                  iters=1)
    autotune_dyad("dyad_ff_fused_w8", TOKENS, n, k, k, qdt, d_mid=j,
                  act=ACT, iters=1)


def _ff_cells():
    lin = configs.linear_cfg("dyad_it_4_kernel_ffused_w8")
    params = mlp.init_mlp(jax.random.PRNGKey(0), D, DFF, lin, act=ACT)
    x = jax.random.normal(jax.random.PRNGKey(1), (TOKENS, D))
    shape = (TOKENS, D, DFF)
    w_mb = round(4 * D * DFF / N_DYAD * 4 / 2 ** 20, 2)

    t_fp = time_fn(jax.jit(lambda p, x: kops.dyad_ff(p, x, act=ACT)),
                   params, x, iters=3, warmup=1)
    b_fp = _ff_bound_us(2, scales=False)
    emit("quant_ff_fp", t_fp, shape=shape, weight_mb=w_mb,
         bound_us=round(b_fp, 3))

    for qdt in ["int8", "fp8"]:
        _pretune("float8_e4m3fn" if qdt == "fp8" else qdt)
        pq = quant.quantize_params(params, qdt)
        obs.reset_route_counts()
        t_q = time_fn(jax.jit(lambda p, x: kops.dyad_ff_quant(p, x,
                                                              act=ACT)),
                      pq, x, iters=3, warmup=1)
        b_q = _ff_bound_us(1, scales=True)
        emit(f"quant_ff_{qdt}", t_q, shape=shape, weight_mb=round(w_mb / 4, 2),
             bound_us=round(b_q, 3),
             bound_speedup=round(b_fp / b_q, 3),
             wall_vs_fp=round(t_fp / t_q, 3))


def _kv_cells():
    for name, dtype in (("fp32", np.float32), ("bf16", jax.numpy.bfloat16)):
        full = attn_lib.init_paged_kv_cache(
            2, 64, KV_HEADS, HEAD_DIM, dtype, page_size=PAGE,
            n_pages=N_PAGES)
        q = attn_lib.init_paged_kv_cache(
            2, 64, KV_HEADS, HEAD_DIM, dtype, page_size=PAGE,
            n_pages=N_PAGES, quant="int8")
        pools = ("pages_k", "pages_v", "scales_k", "scales_v")
        slots = N_PAGES * PAGE
        bt_full = sum(full[nm].nbytes for nm in pools if nm in full) / slots
        bt_q = sum(q[nm].nbytes for nm in pools if nm in q) / slots
        emit(f"quant_kv_capacity_{name}", 0.0,
             shape=(N_PAGES, PAGE, KV_HEADS, HEAD_DIM),
             bytes_per_token_fp=int(bt_full), bytes_per_token_int8=int(bt_q),
             capacity_ratio=round(bt_full / bt_q, 3))


def _engine_tokens(cfg, params, prompts, new_tokens):
    from repro.serve import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=24,
                                   page_size=4)
    uids = [eng.submit(p, new_tokens) for p in prompts]
    t0 = time.perf_counter()
    out = eng.run()
    dt = time.perf_counter() - t0
    toks = [out[u] for u in uids]
    return toks, dt, sum(len(t) for t in toks)


def _quality_cell():
    cfg = configs.get("qwen3_0_6b", smoke=True)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=(s,)) for s in (11, 7, 9)]

    with force_attn_route("flash"):
        want, _, _ = _engine_tokens(cfg, params, prompts, 6)
        qcfg = cfg.replace(
            linear=configs.linear_cfg("dyad_it_4_kernel_ffused_w8"),
            kv_quant="int8")
        obs.reset_route_counts()
        got, dt, n_tok = _engine_tokens(
            qcfg, quant.quantize_params(params), prompts, 6)
    routes = obs.routes_snapshot()
    matched = sum(int(a == b) for w, g in zip(want, got)
                  for a, b in zip(w, g))
    total = sum(len(w) for w in want)
    emit("quant_quality_greedy", dt / max(n_tok, 1) * 1e6,
         shape=(len(prompts), 6),
         token_match=round(matched / max(total, 1), 4),
         ff_quant_events=routes.get("ff_quant:int8", 0),
         kv_quant_events=routes.get("kv_quant:int8", 0))


@perf.register("quant")
def run():
    _ff_cells()
    _kv_cells()
    _quality_cell()


if __name__ == "__main__":
    run()
