"""jit'd differentiable wrappers around the fused DYAD Pallas kernels.

Two public ops: ``dyad_mm`` (one DYAD linear) and ``dyad_ff`` (the whole
ff module — up [+ SwiGLU gate], activation, down — through the one-grid
megakernel; see the ff section at the bottom of this file).

``dyad_mm(x, w1, w2, variant=...)``:

* forward — builds the two strided block views (pure re-views, folded into the
  operands' layouts by XLA) and calls the fused forward kernel;
* backward — custom VJP routed through the fused backward dataflow
  (``use_kernel_bwd=True``, the default): on TPU the Pallas kernels
  (:func:`repro.kernels.dyad_mm.dyad_mm_dgrad` / ``dyad_mm_dgrad_two`` for
  the input cotangent, ``dyad_mm_wgrad`` for both weight cotangents, all
  with fp32 accumulator tiles); on other backends a compiled XLA lowering
  of the SAME dataflow (:func:`_bwd_direct`) — it contracts directly in the
  permuted layouts so none of the strided views (``x2``, ``z2bar``) or the
  ``dx2`` un-view are ever materialized, and accumulates in fp32 exactly
  like the kernel.  The Pallas interpreter is NOT on the non-TPU hot path:
  its grid loop re-carries every operand per step, which is right for
  bit-level validation (tests pass ``interpret=True`` explicitly) and wrong
  for throughput.  Set ``REPRO_KERNEL_BWD=pallas`` to force the Pallas
  route off-TPU (validation/timing of the true kernels), or
  ``REPRO_KERNEL_BWD=xla`` to force the compiled fallback on TPU.

The pre-kernel einsum backward survives as the oracle
(:func:`repro.kernels.ref.dyad_mm_bwd_ref`), selectable with
``use_kernel_bwd=False`` — gradient-equivalence tests pin every route
against it to fp32 tolerance.

Variant dataflow in the backward (the permutations are bijective, so the
cotangent "un-views" are exact inverses of the forward views):

* ``ot`` — both dx components land block-contiguous, so ONE fused
  accumulator computes ``dx = z1bar.w1 + z2bar.w2`` in-kernel;
* ``it``/``dt`` — component 2's dx lives in the permuted layout, so the
  kernel emits both products and the zero-copy un-view + add happens here
  (the XLA fallback instead writes component 2 directly into the permuted
  layout: ``bgo,goi->big``).

On non-TPU backends the forward kernel runs in ``interpret=True`` mode,
which executes the kernel body in Python for bit-correct validation on CPU.

Tile sizes: the kernel calls below pass no explicit ``block_*``, so the
wrappers resolve tiles from the autotune cache per (op, shape, dtype,
backend) — see :mod:`repro.perf.autotune`.  Run the tuner
(``launch/train.py --autotune``, ``launch/serve.py --autotune``, or
``ensure_tuned_for_model``) BEFORE the first trace of a jitted caller: the
resolved tiles are baked into the trace, including the ``value_and_grad``
trace of a train step.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro import faults, obs
from repro.kernels import dyad_gmm, flash_attn, ref
from repro.kernels.dyad_mm import (_block_major, dyad_ff_fused,
                                   dyad_ff_fused_q,
                                   dyad_mm_blocks, dyad_mm_blocks_q,
                                   dyad_mm_blocks_two, dyad_mm_blocks_two_q,
                                   dyad_mm_dgrad, dyad_mm_dgrad_two,
                                   dyad_mm_wgrad)


@functools.lru_cache(maxsize=None)
def _backend_is_tpu() -> bool:
    """The backend never changes within a process — resolve the (relatively
    expensive) jax backend query once instead of on every trace of every
    call site.  Env-var escape hatches stay dynamic (plain dict lookups):
    tests and benchmarks flip them between traces."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Single source of truth for the kernel execution mode — the autotuner
    and benchmarks reuse this so tuned tiles are measured the same way the
    serving and training hot paths run them."""
    interpret = not _backend_is_tpu()
    obs.route_event("pallas_exec", "interpret" if interpret else "compiled")
    return interpret


def _use_pallas_bwd() -> bool:
    """Route the backward through the Pallas kernels?  TPU: yes (that is
    the hot path they exist for).  Elsewhere: only when forced with
    ``REPRO_KERNEL_BWD=pallas`` — the default is the compiled XLA lowering
    of the same dataflow (:func:`_bwd_direct`).  Checked at trace time."""
    forced = os.environ.get("REPRO_KERNEL_BWD", "").lower()
    if forced == "pallas":
        use = True
    elif forced == "xla":
        use = False
    else:
        use = _backend_is_tpu()
    # trace-time decision, recorded so a silent fall-off from the Pallas
    # kernels shows up in obs.route_counts() / the exported timeline
    obs.route_event("kernel_bwd", "pallas" if use else "xla",
                    forced=bool(forced))
    return use


def _ff_route() -> str:
    """Which forward route does ``dyad_ff`` take?  ``fused`` (the default:
    the one-grid megakernel) or ``split`` (up [+ gate] kernel dispatch, XLA
    activation, down kernel dispatch — the pre-megakernel dataflow, with
    the hidden round-tripping through HBM).  ``REPRO_KERNEL_FF=fused|split``
    forces either; checked at trace time."""
    forced = os.environ.get("REPRO_KERNEL_FF", "").lower()
    route = forced if forced in ("fused", "split") else "fused"
    obs.route_event("ff", route, forced=route == forced)
    return route


def attn_route() -> str:
    """Which route does attention take when the config opts into flash
    (``cfg.flash_attn``)?  ``flash`` (the Pallas kernels) on TPU, ``xla``
    (the existing chunked/naive einsum paths) elsewhere — off-TPU the
    kernels would run the interpreter, which is validation-grade, not a
    hot path.  ``REPRO_KERNEL_ATTN=flash|xla`` forces either; checked at
    trace time."""
    forced = os.environ.get("REPRO_KERNEL_ATTN", "").lower()
    route = (forced if forced in ("flash", "xla")
             else "flash" if _backend_is_tpu() else "xla")
    obs.route_event("attn", route, forced=route == forced)
    return route


def _einsum32(spec, a, b):
    """fp32 contraction of possibly-bf16 operands, for the compiled
    non-TPU lowerings below.  Upcasting first is exact — the product of
    two bf16 values fits an fp32 mantissa, so this equals a bf16 x bf16
    dot accumulated in fp32 — and it sidesteps CPU dot kernels that have
    no bf16 x bf16 -> fp32 path."""
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


def _bwd_direct(x2d, w1, w2, g2d, variant: str):
    """Compiled non-TPU lowering of the fused kernel backward.

    Mirrors dgrad/wgrad kernel semantics — fp32 accumulation, component
    fusion — but expressed as direct-layout contractions: the BLOCKTRANS
    operand is read through the free ``(B, d, n)`` reshape (``big`` /
    ``bog`` subscripts) and component 2's dx is PRODUCED in the permuted
    layout, so unlike the einsum oracle no ``x2`` / ``z2bar`` / un-view
    copy is ever materialized.
    """
    B, f_in = x2d.shape
    n, d_out, d_in = w1.shape
    x1 = x2d.reshape(B, n, d_in)
    xr = x2d.reshape(B, d_in, n)          # x2[b,g,i] == xr[b,i,g]
    z1 = g2d.reshape(B, n, d_out)
    gr = g2d.reshape(B, d_out, n)         # z2bar[b,g,o] == gr[b,o,g]

    dw1 = _einsum32("bgi,bgo->goi", x1, z1)
    dx1 = _einsum32("bgo,goi->bgi", z1, w1)
    if variant == "it":
        dw2 = _einsum32("big,bgo->goi", xr, z1)
        dx2r = _einsum32("bgo,goi->big", z1, w2)
        dx = dx1.reshape(B, f_in) + dx2r.reshape(B, f_in)
    elif variant == "ot":
        dw2 = _einsum32("bgi,bog->goi", x1, gr)
        dx2 = _einsum32("bog,goi->bgi", gr, w2)
        dx = (dx1 + dx2).reshape(B, f_in)
    else:  # "dt"
        dw2 = _einsum32("big,bog->goi", xr, gr)
        dx2r = _einsum32("bog,goi->big", gr, w2)
        dx = dx1.reshape(B, f_in) + dx2r.reshape(B, f_in)
    return dx, dw1, dw2


@functools.lru_cache(maxsize=None)
def _make_dyad_mm(variant: str, use_kernel_bwd: bool = True):
    @jax.custom_vjp
    def op(x, w1, w2):
        n, d_out, _ = w1.shape
        lead = x.shape[:-1]
        x2d = x.reshape(-1, x.shape[-1])
        x1, x2 = ref.block_views(x2d, n, variant)
        w1c, w2c = w1.astype(x.dtype), w2.astype(x.dtype)
        if variant == "it":
            # IT: both components share the block-contiguous OUTPUT layout,
            # so one fused accumulator suffices (the "super--CAT" path).
            z = dyad_mm_blocks(x1, x2, w1c, w2c, interpret=_interpret())
            y = z.reshape(-1, n * d_out)
        else:
            # OT/DT: component 2 writes a strided output layout; the kernel
            # emits both products and the re-view happens here (zero-copy).
            z1, z2 = dyad_mm_blocks_two(x1, x2, w1c, w2c, interpret=_interpret())
            y = ref.combine(z1, z2, variant)
        return y.reshape(*lead, n * d_out)

    def fwd(x, w1, w2):
        return op(x, w1, w2), (x, w1, w2)

    def bwd_einsum(resids, g):
        x, w1, w2 = resids
        return ref.dyad_mm_bwd_ref(x, w1, w2, g, variant=variant)

    def bwd_kernel(resids, g):
        x, w1, w2 = resids
        n = w1.shape[0]
        lead = x.shape[:-1]
        f_in = x.shape[-1]
        x2d = x.reshape(-1, f_in)
        g2d = g.reshape(-1, g.shape[-1]).astype(x.dtype)
        w1c, w2c = w1.astype(x.dtype), w2.astype(x.dtype)

        if not _use_pallas_bwd():
            dx, dw1, dw2 = _bwd_direct(x2d, w1c, w2c, g2d, variant)
            return (dx.reshape(*lead, f_in).astype(x.dtype),
                    dw1.astype(w1.dtype), dw2.astype(w2.dtype))

        x1, x2 = ref.block_views(x2d, n, variant)
        z1bar, z2bar = ref.split_cotangent(g2d, n, variant)
        interpret = _interpret()
        if variant == "ot":
            # both dx components are block-contiguous: fused single-tile
            # accumulate in-kernel (the add the einsum oracle does in jnp).
            dx3 = dyad_mm_dgrad(z1bar, z2bar, w1c, w2c, interpret=interpret)
            dx = dx3.reshape(-1, f_in)
        else:
            dx1, dx2 = dyad_mm_dgrad_two(z1bar, z2bar, w1c, w2c,
                                         interpret=interpret)
            dx = ref.unview(dx1, dx2, variant)
        dw1, dw2 = dyad_mm_wgrad(x1, x2, z1bar, z2bar, out_dtype=w1.dtype,
                                 interpret=interpret)
        return (dx.reshape(*lead, f_in).astype(x.dtype), dw1,
                dw2.astype(w2.dtype))

    op.defvjp(fwd, bwd_kernel if use_kernel_bwd else bwd_einsum)
    return op


def dyad_mm(x, w1, w2, *, variant: str = "it", use_kernel_bwd: bool = True):
    """Fused DYAD matmul: (..., f_in) -> (..., f_out), no bias.

    ``use_kernel_bwd=False`` swaps the backward to the pure-einsum oracle
    (``ref.dyad_mm_bwd_ref``) — the escape hatch for debugging gradients or
    backends where the fused backward underperforms.
    """
    return _make_dyad_mm(variant, use_kernel_bwd)(x, w1, w2)


# -- the ff megakernel op -----------------------------------------------------
#
# ``dyad_ff`` is the whole transformer ff module as one differentiable op:
# up = IT (strided view on the replicated input), activation, down = OT
# (strided view on the reduced output) — the mixed-variant dataflow of
# ``layers.mlp._fused_dyad_mlp``, but executed by ONE Pallas grid
# (:func:`repro.kernels.dyad_mm.dyad_ff_fused`) so the ``(..., n, d_ff/n)``
# hidden never exists in HBM.
#
# Backward: the fused VJP REMATERIALIZES the hidden (the forward deliberately
# never stored it) with one up-kernel dispatch, then composes the existing
# fused backward kernels: ``dyad_mm_dgrad`` for the down input cotangent (OT:
# both dx components share the block layout — one fused accumulator),
# ``dyad_mm_wgrad`` for both down weight grads, the activation VJP
# elementwise in XLA, then ``dyad_mm_wgrad`` + ``dyad_mm_dgrad_two`` for the
# up (and gate) side.  Off-TPU the same dataflow lowers to compiled XLA
# einsums in direct layouts (:func:`_ff_bwd_direct`), exactly like
# :func:`_bwd_direct` for the single matmul; ``REPRO_KERNEL_BWD`` applies.


def _ff_act_fwd(act, g_pre, u_pre):
    """(h, residuals) for the activation epilogue in BLOCK layout."""
    if act == "swiglu":
        return jax.vjp(lambda g, u: jax.nn.silu(g) * u, g_pre, u_pre)
    return jax.vjp(ref.ACTS[act], u_pre)


def _ff_forward(x, wg, wu, wd, act):
    """Shared forward: returns flat (..., f_out).  wg is None when ungated."""
    n, _, _ = wu[0].shape
    d_out = wd[0].shape[1]
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    dt = x.dtype
    wu1, wu2 = (w.astype(dt) for w in wu)
    wd1, wd2 = (w.astype(dt) for w in wd)
    x1, x2 = ref.block_views(x2d, n, "it")
    interpret = _interpret()
    route = _ff_route()
    if route == "fused":
        wg1, wg2 = (w.astype(dt) for w in wg) if wg is not None else (None,
                                                                      None)
        z1, z2 = dyad_ff_fused(x1, x2, wu1, wu2, wd1, wd2, wg1=wg1, wg2=wg2,
                               act=act, interpret=interpret)
    else:
        u = dyad_mm_blocks(x1, x2, wu1, wu2, interpret=interpret)
        if wg is not None:
            g_pre = dyad_mm_blocks(x1, x2, wg[0].astype(dt),
                                   wg[1].astype(dt), interpret=interpret)
            h = jax.nn.silu(g_pre) * u
        else:
            h = ref.ACTS[act](u)
        z1, z2 = dyad_mm_blocks_two(h, h, wd1, wd2, interpret=interpret)
    y = ref.combine(z1, z2, "ot")
    # chaos hook: ``kernel_nan`` with route=ff_fused / ff_split simulates a
    # numerically-broken kernel on the active route (trace-time; no-op
    # unless a fault schedule is armed)
    y = faults.poison(y, "kernel_nan", route=f"ff_{route}")
    return y.reshape(*lead, n * d_out)


def _ff_bwd_kernel(x, wg, wu, wd, g, act):
    """Pallas-kernel backward: rematerialized hidden + fused dgrad/wgrad."""
    n = wu[0].shape[0]
    lead = x.shape[:-1]
    f_in = x.shape[-1]
    dt = x.dtype
    x2d = x.reshape(-1, f_in)
    g2d = g.reshape(-1, g.shape[-1]).astype(dt)
    x1, x2 = ref.block_views(x2d, n, "it")
    wu1, wu2 = (w.astype(dt) for w in wu)
    wd1, wd2 = (w.astype(dt) for w in wd)
    interpret = _interpret()

    u_pre = dyad_mm_blocks(x1, x2, wu1, wu2, interpret=interpret)
    if wg is not None:
        g_pre = dyad_mm_blocks(x1, x2, wg[0].astype(dt), wg[1].astype(dt),
                               interpret=interpret)
        h, act_vjp = _ff_act_fwd(act, g_pre, u_pre)
    else:
        h, act_vjp = _ff_act_fwd(act, None, u_pre)

    z1bar, z2bar = ref.split_cotangent(g2d, n, "ot")
    dwd1, dwd2 = dyad_mm_wgrad(h, h, z1bar, z2bar, out_dtype=wd[0].dtype,
                               interpret=interpret)
    # OT down: both dh components share the block layout -> ONE fused tile.
    dh = dyad_mm_dgrad(z1bar, z2bar, wd1, wd2, interpret=interpret)

    if wg is not None:
        dg_pre, du_pre = act_vjp(dh)
        dg_pre = dg_pre.astype(dt)
    else:
        (du_pre,) = act_vjp(dh)
    du_pre = du_pre.astype(dt)

    dwu1, dwu2 = dyad_mm_wgrad(x1, x2, du_pre, du_pre,
                               out_dtype=wu[0].dtype, interpret=interpret)
    dx1, dx2 = dyad_mm_dgrad_two(du_pre, du_pre, wu1, wu2,
                                 interpret=interpret)
    dx = ref.unview(dx1, dx2, "it")
    dgs = ()
    if wg is not None:
        dwg1, dwg2 = dyad_mm_wgrad(x1, x2, dg_pre, dg_pre,
                                   out_dtype=wg[0].dtype, interpret=interpret)
        dxg1, dxg2 = dyad_mm_dgrad_two(dg_pre, dg_pre, wg[0].astype(dt),
                                       wg[1].astype(dt), interpret=interpret)
        dx = dx + ref.unview(dxg1, dxg2, "it")
        dgs = (dwg1, dwg2.astype(wg[1].dtype))
    return (dx.reshape(*lead, f_in).astype(x.dtype), *dgs,
            dwu1, dwu2.astype(wu[1].dtype),
            dwd1, dwd2.astype(wd[1].dtype))


def _ff_bwd_direct(x, wg, wu, wd, g, act):
    """Compiled non-TPU lowering of the megakernel backward: direct-layout
    contractions (the BLOCKTRANS operands are read through the free
    ``(B, d, n)`` reshapes), fp32 accumulation, rematerialized hidden —
    no strided view, hidden store, or dx un-view is ever materialized."""
    n, d_ffb, d_in = wu[0].shape
    d_out = wd[0].shape[1]
    lead = x.shape[:-1]
    f_in = x.shape[-1]
    dt = x.dtype
    x2d = x.reshape(-1, f_in)
    B = x2d.shape[0]
    g2d = g.reshape(-1, g.shape[-1]).astype(dt)
    x1 = x2d.reshape(B, n, d_in)
    xr = x2d.reshape(B, d_in, n)              # x2[b,g,k] == xr[b,k,g]
    z1 = g2d.reshape(B, n, d_out)
    gr = g2d.reshape(B, d_out, n)             # z2bar[b,g,o] == gr[b,o,g]
    wu1, wu2 = (w.astype(dt) for w in wu)
    wd1, wd2 = (w.astype(dt) for w in wd)

    def up(w1, w2):
        pre = (_einsum32("bgk,gjk->bgj", x1, w1)
               + _einsum32("bkg,gjk->bgj", xr, w2))
        return pre.astype(dt)

    u_pre = up(wu1, wu2)
    if wg is not None:
        wg1, wg2 = (w.astype(dt) for w in wg)
        h, act_vjp = _ff_act_fwd(act, up(wg1, wg2), u_pre)
    else:
        h, act_vjp = _ff_act_fwd(act, None, u_pre)

    dwd1 = _einsum32("bgj,bgo->goj", h, z1)
    dwd2 = _einsum32("bgj,bog->goj", h, gr)
    dh = (_einsum32("bgo,goj->bgj", z1, wd1)
          + _einsum32("bog,goj->bgj", gr, wd2)).astype(dt)

    if wg is not None:
        dg_pre, du_pre = act_vjp(dh)
    else:
        (du_pre,) = act_vjp(dh)

    def down_grads(du, w1, w2):
        dw1 = _einsum32("bgk,bgj->gjk", x1, du)
        dw2 = _einsum32("bkg,bgj->gjk", xr, du)
        # component 2's dx is PRODUCED in the permuted layout (bkg): the
        # un-view is a free reshape, never a copy.
        dx = (_einsum32("bgj,gjk->bgk", du, w1).reshape(B, f_in)
              + _einsum32("bgj,gjk->bkg", du, w2).reshape(B, f_in))
        return dw1, dw2, dx

    dwu1, dwu2, dx = down_grads(du_pre, wu1, wu2)
    dgs = ()
    if wg is not None:
        dwg1, dwg2, dxg = down_grads(dg_pre, wg1, wg2)
        dx = dx + dxg
        dgs = (dwg1.astype(wg[0].dtype), dwg2.astype(wg[1].dtype))
    return (dx.reshape(*lead, f_in).astype(x.dtype), *dgs,
            dwu1.astype(wu[0].dtype), dwu2.astype(wu[1].dtype),
            dwd1.astype(wd[0].dtype), dwd2.astype(wd[1].dtype))


@functools.lru_cache(maxsize=None)
def _make_dyad_ff(act: str, use_kernel_bwd: bool = True):
    gated = act == "swiglu"

    def bwd(resids, g):
        if gated:
            x, wg1, wg2, wu1, wu2, wd1, wd2 = resids
            wg = (wg1, wg2)
        else:
            x, wu1, wu2, wd1, wd2 = resids
            wg = None
        if not use_kernel_bwd:
            # pure-einsum oracle: autodiff of the reference forward.
            args = (x, wu1, wu2, wd1, wd2) + ((wg1, wg2) if gated else ())
            if gated:
                f = lambda x, wu1, wu2, wd1, wd2, wg1, wg2: ref.dyad_ff_ref(
                    x, wu1, wu2, wd1, wd2, wg1, wg2, act=act)
            else:
                f = lambda x, wu1, wu2, wd1, wd2: ref.dyad_ff_ref(
                    x, wu1, wu2, wd1, wd2, act=act)
            _, vjp = jax.vjp(f, *args)
            grads = vjp(g)
            if gated:
                dx, dwu1, dwu2, dwd1, dwd2, dwg1, dwg2 = grads
                return (dx, dwg1, dwg2, dwu1, dwu2, dwd1, dwd2)
            return grads
        route = _ff_bwd_kernel if _use_pallas_bwd() else _ff_bwd_direct
        return route(x, wg, (wu1, wu2), (wd1, wd2), g, act)

    if gated:
        @jax.custom_vjp
        def op(x, wg1, wg2, wu1, wu2, wd1, wd2):
            return _ff_forward(x, (wg1, wg2), (wu1, wu2), (wd1, wd2), act)

        def fwd(x, wg1, wg2, wu1, wu2, wd1, wd2):
            return (op(x, wg1, wg2, wu1, wu2, wd1, wd2),
                    (x, wg1, wg2, wu1, wu2, wd1, wd2))
    else:
        @jax.custom_vjp
        def op(x, wu1, wu2, wd1, wd2):
            return _ff_forward(x, None, (wu1, wu2), (wd1, wd2), act)

        def fwd(x, wu1, wu2, wd1, wd2):
            return op(x, wu1, wu2, wd1, wd2), (x, wu1, wu2, wd1, wd2)

    op.defvjp(fwd, bwd)
    return op


def dyad_ff(params, x, *, act: str = "gelu", use_kernel_bwd: bool = True):
    """The whole DYAD ff module as one differentiable op (bias-free).

    ``params`` is the ``layers.mlp`` param dict: ``{"up", "down"}`` (+
    ``"gate"`` for ``act="swiglu"``), each holding DYAD ``w1``/``w2``.
    Forward runs the one-grid Pallas megakernel (``REPRO_KERNEL_FF=split``
    falls back to the two/three-dispatch kernel chain); backward composes
    the fused dgrad/wgrad kernels on TPU and compiled direct-layout XLA
    elsewhere.  ``use_kernel_bwd=False`` swaps the backward to autodiff of
    the einsum oracle (``ref.dyad_ff_ref``).
    """
    op = _make_dyad_ff(act, use_kernel_bwd)
    if act == "swiglu":
        return op(x, params["gate"]["w1"], params["gate"]["w2"],
                  params["up"]["w1"], params["up"]["w2"],
                  params["down"]["w1"], params["down"]["w2"])
    return op(x, params["up"]["w1"], params["up"]["w2"],
              params["down"]["w1"], params["down"]["w2"])


# -- quantized forward routes -------------------------------------------------
#
# Serving-only: the quantized weights are a frozen snapshot, so these are
# plain forward functions OUTSIDE the custom-VJP machinery — dispatch sites
# (``layers.mlp``, ``core.factory``) route here only when not differentiating.
# They stream the int8/fp8 SIDECAR leaves (``w*_q``/``w*_s`` from
# ``repro.quant.quantize_params``) and never touch the retained fp32
# originals — in particular there is no ``w.astype(x.dtype)`` cast: the
# payload reaches the kernel in its quantized dtype and is dequantized at
# the VMEM load (scale into the fp32 accumulator epilogue).


def dyad_mm_quant(x, w1q, w2q, s1, s2, *, variant: str = "it"):
    """Forward-only :func:`dyad_mm` streaming quantized weight sidecars.

    w1q/w2q: (n, d_out, d_in) int8/fp8 payloads; s1/s2: (n, d_out) fp32
    per-(block, out_row) scales."""
    n, d_out, _ = w1q.shape
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    x1, x2 = ref.block_views(x2d, n, variant)
    interpret = _interpret()
    if variant == "it":
        z = dyad_mm_blocks_q(x1, x2, w1q, w2q, s1, s2, interpret=interpret)
        y = z.reshape(-1, n * d_out)
    else:
        z1, z2 = dyad_mm_blocks_two_q(x1, x2, w1q, w2q, s1, s2,
                                      interpret=interpret)
        y = ref.combine(z1, z2, variant)
    return y.reshape(*lead, n * d_out)


def dyad_ff_quant(params, x, *, act: str = "gelu"):
    """Forward-only :func:`dyad_ff` streaming quantized weight sidecars.

    ``params`` is the ``layers.mlp`` param dict AFTER
    ``repro.quant.quantize_params`` (every projection carries
    ``w1_q``/``w1_s``/``w2_q``/``w2_s``).  The fused route runs the
    quantized megakernel (:func:`repro.kernels.dyad_mm.dyad_ff_fused_q`);
    ``REPRO_KERNEL_FF=split`` composes the quantized mm kernels instead
    (up [+ gate], XLA activation, down) — the same escape hatch surface as
    the unquantized op."""
    up, down = params["up"], params["down"]
    gated = act == "swiglu"
    n = up["w1_q"].shape[0]
    d_out = down["w1_q"].shape[1]
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    x1, x2 = ref.block_views(x2d, n, "it")
    interpret = _interpret()
    if _ff_route() == "fused":
        gate_kw = {}
        if gated:
            g = params["gate"]
            gate_kw = dict(wg1=g["w1_q"], wg2=g["w2_q"],
                           sg1=g["w1_s"], sg2=g["w2_s"])
        z1, z2 = dyad_ff_fused_q(
            x1, x2, up["w1_q"], up["w2_q"], down["w1_q"], down["w2_q"],
            up["w1_s"], up["w2_s"], down["w1_s"], down["w2_s"],
            act=act, interpret=interpret, **gate_kw)
    else:
        u = dyad_mm_blocks_q(x1, x2, up["w1_q"], up["w2_q"],
                             up["w1_s"], up["w2_s"], interpret=interpret)
        if gated:
            g = params["gate"]
            g_pre = dyad_mm_blocks_q(x1, x2, g["w1_q"], g["w2_q"],
                                     g["w1_s"], g["w2_s"],
                                     interpret=interpret)
            h = jax.nn.silu(g_pre) * u
        else:
            h = ref.ACTS[act](u)
        z1, z2 = dyad_mm_blocks_two_q(h, h, down["w1_q"], down["w2_q"],
                                      down["w1_s"], down["w2_s"],
                                      interpret=interpret)
    y = ref.combine(z1, z2, "ot")
    return y.reshape(*lead, n * d_out)


# -- the grouped expert op ----------------------------------------------------
#
# ``dyad_gmm`` is a DYAD-IT projection of rows sorted by expert, each row
# through its own expert's weights (:mod:`repro.kernels.dyad_gmm`), as one
# differentiable op.  The backward always runs the grouped Pallas kernels
# (in the interpreter off-TPU): there is no XLA lowering of it to choose.


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _dyad_gmm(x, w1, w2, starts, ends, row_tile):
    out, _ = _dyad_gmm_fwd(x, w1, w2, starts, ends, row_tile)
    return out


def _dyad_gmm_fwd(x, w1, w2, starts, ends, row_tile):
    n = w1.shape[1]
    x1, x2 = (_block_major(v) for v in ref.block_views(x, n, "it"))
    z = dyad_gmm.gmm(x1, x2, w1.astype(x.dtype), w2.astype(x.dtype),
                     starts, ends, row_tile=row_tile,
                     interpret=_interpret())
    out = _block_major(z).reshape(x.shape[0], -1)
    return out, (x1, x2, w1, w2, starts, ends)


def _dyad_gmm_bwd(row_tile, resids, g):
    x1, x2, w1, w2, starts, ends = resids
    n, M, _ = x1.shape
    interpret = _interpret()
    z = _block_major(g.astype(x1.dtype).reshape(M, n, -1))
    dx1, dx2 = dyad_gmm.gmm_dgrad(z, w1.astype(x1.dtype),
                                  w2.astype(x1.dtype), starts, ends,
                                  row_tile=row_tile, interpret=interpret)
    dx = ref.unview(_block_major(dx1), _block_major(dx2), "it")
    dw1, dw2 = dyad_gmm.gmm_wgrad(x1, x2, z, starts, ends,
                                  row_tile=row_tile, interpret=interpret)
    return (dx, dw1.astype(w1.dtype), dw2.astype(w2.dtype),
            _int_zero(starts), _int_zero(ends))


_dyad_gmm.defvjp(_dyad_gmm_fwd, _dyad_gmm_bwd)


def dyad_gmm_it(x, w1, w2, starts, ends, *,
                row_tile: int = dyad_gmm.ROW_TILE):
    """DYAD-IT over rows sorted by expert: ``x (M, f_in)`` -> ``(M, f_out)``,
    row ``r`` of group ``e`` (``starts[e] <= r < ends[e]``, each
    ``starts[e]`` a multiple of ``row_tile``) through expert ``e``'s
    ``w1[e]``, ``w2[e]`` (``(E, n, d_out, d_in)``); rows outside every
    group come out undefined and take no part in the weights' gradient."""
    return _dyad_gmm(x, w1, w2, starts, ends, row_tile)


# -- the flash-attention ops --------------------------------------------------
#
# ``flash_attention`` wraps the fused prefill kernel
# (:func:`repro.kernels.flash_attn.flash_prefill`) in a custom VJP:
#
# * forward — one Pallas grid, online softmax in VMEM (the fwd primal saves
#   nothing; under differentiation the fwd rule additionally emits the
#   per-row log-sum-exp residual);
# * backward — on TPU the flash backward kernels
#   (:func:`flash_attn.flash_prefill_grads`: dq on the forward grid, dk/dv
#   on the transposed grid, probabilities RECOMPUTED per tile from the
#   saved lse); off-TPU a compiled XLA lowering of the same recompute
#   dataflow (:func:`_flash_bwd_direct`).  ``REPRO_KERNEL_BWD`` forces
#   either route, exactly like the DYAD ops.
#
# The einsum VJP survives as the oracle: ``use_kernel_bwd=False`` swaps the
# backward to autodiff of :func:`repro.kernels.ref.sdpa_ref`.
#
# Positions are ``q_off + arange(S)`` / ``k_off + arange(T)`` (scalars or
# per-batch vectors) — the contiguous-position contract every dispatch site
# in ``layers.attention`` satisfies (no-cache forward: k_off = 0;
# fresh-stream cache prefill: q_off = k_off = idx).


def _attn_positions(q_off, k_off, B: int, S: int, T: int):
    qo = jnp.asarray(q_off, jnp.int32).reshape(-1)[:, None]    # (B?|1, 1)
    ko = jnp.asarray(k_off, jnp.int32).reshape(-1)[:, None]
    return qo + jnp.arange(S), ko + jnp.arange(T)              # (B?|1, S/T)


def _flash_bwd_direct(q, k, v, o, lse, do, q_off, k_off, causal, window):
    """Compiled non-TPU lowering of the flash backward: the same
    recomputed-probability dataflow (p from the saved lse, fp32
    accumulation) as direct einsum contractions.  Materializes the score
    tensor — fine for the compiled fallback, wrong for VMEM-bound TPU."""
    f32 = jnp.float32
    B, S, K, G, h = q.shape
    T = k.shape[1]
    scale = 1.0 / float(h) ** 0.5
    s = _einsum32("bskgh,btkh->bskgt", q, k) * scale
    qp, kp = _attn_positions(q_off, k_off, B, S, T)
    m = jnp.ones((max(qp.shape[0], kp.shape[0]), S, T), bool)
    if causal:
        m = m & (kp[:, None, :] <= qp[..., :, None])
    if window is not None:
        m = m & (qp[..., :, None] - kp[:, None, :] < window)
    m = m[:, :, None, None, :]
    # lse rides in the kernel layout (B, K, S*G) -> (B, S, K, G)
    lse = lse.reshape(B, K, S, G).transpose(0, 2, 1, 3)
    p = jnp.where(m, jnp.exp(s - lse[..., None]), 0.0)
    do32 = do.astype(f32)
    delta = jnp.sum(do32 * o.astype(f32), axis=-1)             # (B,S,K,G)
    dv = _einsum32("bskgt,bskgh->btkh", p, do32)
    dp = _einsum32("bskgh,btkh->bskgt", do32, v)
    ds = p * (dp - delta[..., None]) * scale
    dq = _einsum32("bskgt,btkh->bskgh", ds, k)
    dk = _einsum32("bskgt,bskgh->btkh", ds, q)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _int_zero(x):
    """float0 cotangent for the integer offset inputs of the flash op."""
    import numpy as np
    return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _make_flash_attention(causal: bool, window, use_kernel_bwd: bool):
    @jax.custom_vjp
    def op(q, k, v, q_off, k_off):
        out, _ = flash_attn.flash_prefill(
            q, k, v, q_off, k_off, causal=causal, window=window,
            interpret=_interpret())
        return out

    def fwd(q, k, v, q_off, k_off):
        out, lse = flash_attn.flash_prefill(
            q, k, v, q_off, k_off, causal=causal, window=window,
            save_lse=True, interpret=_interpret())
        return out, (q, k, v, out, lse, q_off, k_off)

    def bwd(resids, g):
        q, k, v, o, lse, q_off, k_off = resids
        if not use_kernel_bwd:
            # einsum-VJP oracle: autodiff of the reference forward
            qp, kp = _attn_positions(q_off, k_off, q.shape[0], q.shape[1],
                                     k.shape[1])
            qp = qp if qp.shape[0] > 1 else qp[0]
            kp = kp if kp.shape[0] > 1 else kp[0]
            _, vjp = jax.vjp(
                lambda q, k, v: ref.sdpa_ref(q, k, v, qp, kp, causal=causal,
                                             window=window), q, k, v)
            dq, dk, dv = vjp(g.astype(q.dtype))
        elif _use_pallas_bwd():
            dq, dk, dv = flash_attn.flash_prefill_grads(
                q, k, v, o, lse, g.astype(q.dtype), q_off, k_off,
                causal=causal, window=window, interpret=_interpret())
        else:
            dq, dk, dv = _flash_bwd_direct(q, k, v, o, lse,
                                           g.astype(q.dtype), q_off, k_off,
                                           causal, window)
        return dq, dk, dv, _int_zero(q_off), _int_zero(k_off)

    op.defvjp(fwd, bwd)
    return op


def flash_attention(q, k, v, q_off=0, k_off=0, *, causal: bool = True,
                    window=None, use_kernel_bwd: bool = True):
    """Fused flash attention: (B,S,K,G,h) x (B,T,K,h) -> (B,S,K,G,h).

    Query/key positions are ``q_off + arange(S)`` / ``k_off + arange(T)``
    (scalar or per-batch (B,) offsets).  ``use_kernel_bwd=False`` swaps
    the backward to autodiff of the einsum oracle (``ref.sdpa_ref``)."""
    q_off = jnp.asarray(q_off, jnp.int32)
    k_off = jnp.asarray(k_off, jnp.int32)
    return _make_flash_attention(causal, window, use_kernel_bwd)(
        q, k, v, q_off, k_off)


def flash_decode(q, k, v, idx, *, window=None):
    """One-token ring-cache decode attention (inference only, no VJP).

    q: (B,1,K,G,h) or (B,K,G,h); k/v: the (B,L,K,h) post-write cache;
    ``idx``: the current token's write index (scalar or per-slot (B,)).
    See :func:`repro.kernels.flash_attn.flash_decode`."""
    return flash_attn.flash_decode(q, k, v, idx, window=window,
                                   interpret=_interpret())


def flash_decode_paged(q, pages_k, pages_v, block_table, idx, *,
                       l_real=None, window=None, scales_k=None,
                       scales_v=None):
    """One-token paged-cache decode attention (inference only, no VJP).

    q: (B,1,K,G,h) or (B,K,G,h); pages_k/pages_v: the (n_pages,P,K,h)
    shared page pool; ``block_table``: (B, n_blocks) int32 page ids (dead
    entries must point at the reserved scratch page 0); ``idx``: per-slot
    (B,) write index of the current token.  ``l_real`` bounds the logical
    length when the block-table capacity overshoots it.  ``scales_k``/
    ``scales_v`` (``(n_pages, P, K)`` fp32, together) mark the pools as
    int8-quantized; the kernel dequantizes tiles in-VMEM after the
    block-table gather.
    See :func:`repro.kernels.flash_attn.flash_decode_paged`."""
    return flash_attn.flash_decode_paged(
        q, pages_k, pages_v, block_table, idx, l_real=l_real, window=window,
        scales_k=scales_k, scales_v=scales_v, interpret=_interpret())
