"""Tensor-parallel dispatch of the fused Pallas kernels via shard_map.

Under an activation-sharding context the fused ops used to be abandoned:
``layers/mlp.py`` and ``layers/attention.py`` fell back to the einsum
routes the moment a mesh was active, so the large TP configs never touched
a kernel.  This module runs the EXISTING per-device grids on per-shard
operands instead — DYAD's block tensors ``(n, d_out, d_in)`` shard along
the feature-per-block axes with zero resharding, exactly the layout
``sharding/rules.py`` already places:

* ``dyad_ff_tp`` — the ff megakernel per-shard.  Up/gate weights split
  their ``d_out`` axis over ``model`` (the ``constrain_ff_hidden`` hidden
  layout), the down weight splits ``d_in``; each device runs the one-grid
  megakernel on its ``d_ff/tp`` hidden slice and holds a PARTIAL flat
  output (the OT combine is linear, so summing flat outputs is exact).
  The cross-shard reduce is a ``psum_scatter`` over the feature dim when
  it divides — a ring reduce-scatter whose first hops overlap the last
  grid steps, with the re-gather left to GSPMD at the next consumer —
  falling back to a plain ``psum`` otherwise.

* ``flash_attention_tp`` / ``flash_decode_tp`` / ``flash_decode_paged_tp``
  — the flash kernels per-shard over the KV-head axis.  GQA groups ride
  with their KV head, so each device keeps the full scalar-prefetched
  index / block-table machinery and needs NO body collective: heads are
  independent.

Every wrapper invokes its shard_map under ``autotune.tp_shards(tp)`` so
the trace-time block lookups inside the body resolve the per-shard
``|tp{N}`` cache keys, not the global-shape entries.

``REPRO_KERNEL_TP=off`` is the escape hatch back to the einsum fallbacks
(the pre-TP behavior); non-divisible shards fall back per-site and are
counted by the ``ff_tp``/``attn_tp`` route events in :mod:`repro.obs`.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kops
from repro.perf import autotune


def tp_enabled() -> bool:
    """``REPRO_KERNEL_TP=off`` keeps the einsum fallbacks under TP."""
    return os.environ.get("REPRO_KERNEL_TP", "").lower() != "off"


def _tp(ctx) -> int:
    return ctx.axis_size(ctx.model)


def _batch_axes(ctx, dim: int):
    """dp spec for a batch/row dim, or None when it doesn't divide."""
    return ctx.dp_spec if dim % ctx.axis_size(ctx.dp) == 0 else None


# -- ff megakernel ------------------------------------------------------------


def ff_tp_ready(params, ctx) -> bool:
    """Can the ff megakernel run per-shard under this context?  The hidden
    width per block (up's ``d_out``) must split over the model axis — the
    same divisibility ``sharding/rules.py`` requires to place the weights
    and ``constrain_ff_hidden`` requires for the hidden layout."""
    if not tp_enabled():
        return False
    tp = _tp(ctx)
    return tp == 1 or params["up"]["w1"].shape[1] % tp == 0


def dyad_ff_tp(params, x, *, act: str = "gelu", use_kernel_bwd: bool = True,
               ctx):
    """``kops.dyad_ff`` under tensor parallelism: per-shard megakernel +
    overlapped cross-shard reduce.  Differentiable — grads flow through
    shard_map to the per-shard custom VJPs (the transpose of the replicated
    row input inserts the matching psum automatically)."""
    tp = _tp(ctx)
    if tp == 1:
        return kops.dyad_ff(params, x, act=act, use_kernel_bwd=use_kernel_bwd)
    lead, f_in = x.shape[:-1], x.shape[-1]
    x2d = x.reshape(-1, f_in)
    rows = _batch_axes(ctx, x2d.shape[0])
    n, d_out = params["down"]["w1"].shape[0], params["down"]["w1"].shape[1]
    f_out = n * d_out
    scatter = f_out % tp == 0
    model = ctx.model

    # weight specs mirror sharding/rules.py: up-type (n, d_out, d_in)
    # shards axis 1 over model, down-type shards axis 2.
    names = ("gate", "up", "down") if act == "swiglu" else ("up", "down")
    weights, in_specs = [], [P(rows, None)]
    for nm in names:
        spec = P(None, None, model) if nm == "down" else P(None, model, None)
        weights += [params[nm]["w1"], params[nm]["w2"]]
        in_specs += [spec, spec]

    def body(xs, *ws):
        it = iter(ws)
        ps = {nm: {"w1": next(it), "w2": next(it)} for nm in names}
        y = kops.dyad_ff(ps, xs, act=act, use_kernel_bwd=use_kernel_bwd)
        if scatter:
            return jax.lax.psum_scatter(y, model, scatter_dimension=1,
                                        tiled=True)
        return jax.lax.psum(y, model)

    with autotune.tp_shards(tp):
        y = jax.shard_map(
            body, mesh=ctx.mesh, in_specs=tuple(in_specs),
            out_specs=P(rows, model if scatter else None),
            check_vma=False)(x2d, *weights)
    return y.reshape(*lead, f_out)


def dyad_ff_quant_tp(params, x, *, act: str = "gelu", ctx):
    """``kops.dyad_ff_quant`` under tensor parallelism: the quantized
    weight-stream megakernel per-shard.  The int8/fp8 payload sidecars
    shard exactly like their fp32 originals (up/gate ``d_out`` over model,
    down ``d_in``); the per-(block, out_row) scale sidecars follow the
    payload's OUT axis — up/gate scales ``(n, d_mid)`` split over model,
    down scales ``(n, d_out)`` replicate (the down's out rows are whole
    per shard, only its contraction is split).  Forward-only, same
    overlapped psum_scatter epilogue as :func:`dyad_ff_tp`."""
    tp = _tp(ctx)
    if tp == 1:
        return kops.dyad_ff_quant(params, x, act=act)
    lead, f_in = x.shape[:-1], x.shape[-1]
    x2d = x.reshape(-1, f_in)
    rows = _batch_axes(ctx, x2d.shape[0])
    n, d_out = params["down"]["w1"].shape[0], params["down"]["w1"].shape[1]
    f_out = n * d_out
    scatter = f_out % tp == 0
    model = ctx.model

    names = ("gate", "up", "down") if act == "swiglu" else ("up", "down")
    weights, in_specs = [], [P(rows, None)]
    for nm in names:
        if nm == "down":
            w_spec, s_spec = P(None, None, model), P(None, None)
        else:
            w_spec, s_spec = P(None, model, None), P(None, model)
        weights += [params[nm]["w1_q"], params[nm]["w2_q"],
                    params[nm]["w1_s"], params[nm]["w2_s"]]
        in_specs += [w_spec, w_spec, s_spec, s_spec]

    def body(xs, *ws):
        it = iter(ws)
        ps = {nm: {"w1_q": next(it), "w2_q": next(it),
                   "w1_s": next(it), "w2_s": next(it)} for nm in names}
        y = kops.dyad_ff_quant(ps, xs, act=act)
        if scatter:
            return jax.lax.psum_scatter(y, model, scatter_dimension=1,
                                        tiled=True)
        return jax.lax.psum(y, model)

    with autotune.tp_shards(tp):
        y = jax.shard_map(
            body, mesh=ctx.mesh, in_specs=tuple(in_specs),
            out_specs=P(rows, model if scatter else None),
            check_vma=False)(x2d, *weights)
    return y.reshape(*lead, f_out)


# -- flash attention ----------------------------------------------------------


def attn_tp_ready(n_kv_heads: int, ctx) -> bool:
    """Can the flash kernels run per-shard?  KV heads must split over the
    model axis (GQA groups stay whole per shard)."""
    if not tp_enabled():
        return False
    tp = _tp(ctx)
    return tp == 1 or n_kv_heads % tp == 0


def _off_spec(off, rows):
    """Spec for a scalar-or-(B,) offset/index operand."""
    return P() if off.ndim == 0 else P(rows)


def flash_attention_tp(q, k, v, q_off=0, k_off=0, *, causal: bool = True,
                       window=None, use_kernel_bwd: bool = True, ctx):
    """``kops.flash_attention`` sharded over KV heads (q axis 2, k/v axis
    2); no body collective.  q: (B,S,K,G,h); k/v: (B,T,K,h)."""
    tp = _tp(ctx)
    if tp == 1:
        return kops.flash_attention(q, k, v, q_off, k_off, causal=causal,
                                    window=window,
                                    use_kernel_bwd=use_kernel_bwd)
    q_off = jnp.asarray(q_off, jnp.int32)
    k_off = jnp.asarray(k_off, jnp.int32)
    rows = _batch_axes(ctx, q.shape[0])
    model = ctx.model
    q_spec = P(rows, None, model, None, None)
    kv_spec = P(rows, None, model, None)

    def body(qs, ks, vs, qo, ko):
        return kops.flash_attention(qs, ks, vs, qo, ko, causal=causal,
                                    window=window,
                                    use_kernel_bwd=use_kernel_bwd)

    with autotune.tp_shards(tp):
        return jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(q_spec, kv_spec, kv_spec, _off_spec(q_off, rows),
                      _off_spec(k_off, rows)),
            out_specs=q_spec, check_vma=False)(q, k, v, q_off, k_off)


def flash_decode_tp(q, k, v, idx, *, window=None, ctx):
    """``kops.flash_decode`` sharded over KV heads.  q: (B,1,K,G,h) or
    (B,K,G,h); k/v: the (B,L,K,h) post-write ring cache."""
    tp = _tp(ctx)
    if tp == 1:
        return kops.flash_decode(q, k, v, idx, window=window)
    idx = jnp.asarray(idx, jnp.int32)
    rows = _batch_axes(ctx, q.shape[0])
    model = ctx.model
    q_spec = (P(rows, None, model, None, None) if q.ndim == 5
              else P(rows, model, None, None))
    kv_spec = P(rows, None, model, None)

    def body(qs, ks, vs, i):
        return kops.flash_decode(qs, ks, vs, i, window=window)

    with autotune.tp_shards(tp):
        return jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(q_spec, kv_spec, kv_spec, _off_spec(idx, rows)),
            out_specs=q_spec, check_vma=False)(q, k, v, idx)


def flash_decode_paged_tp(q, pages_k, pages_v, block_table, idx, *,
                          l_real=None, window=None, scales_k=None,
                          scales_v=None, ctx):
    """``kops.flash_decode_paged`` sharded over KV heads: each device holds
    a head-slice of the WHOLE page pool (page ids are global, so the pool
    axis stays unsharded — see ``sharding/rules.cache_shardings``) and its
    full block table / scalar-prefetch machinery.  q: (B,1,K,G,h) or
    (B,K,G,h); pages: (n_pages, P, K, h); block_table: (B, n_blocks).
    Quantized pools ship ``scales_k``/``scales_v`` ``(n_pages, P, K)``
    scale pools sharded over the same KV-head axis."""
    tp = _tp(ctx)
    if tp == 1:
        return kops.flash_decode_paged(q, pages_k, pages_v, block_table,
                                       idx, l_real=l_real, window=window,
                                       scales_k=scales_k, scales_v=scales_v)
    idx = jnp.asarray(idx, jnp.int32)
    rows = _batch_axes(ctx, q.shape[0])
    model = ctx.model
    q_spec = (P(rows, None, model, None, None) if q.ndim == 5
              else P(rows, model, None, None))
    pool_spec = P(None, None, model, None)
    quant = scales_k is not None

    if quant:
        def body(qs, pk, pv, bt, i, sk, sv):
            return kops.flash_decode_paged(qs, pk, pv, bt, i, l_real=l_real,
                                           window=window, scales_k=sk,
                                           scales_v=sv)

        with autotune.tp_shards(tp):
            return jax.shard_map(
                body, mesh=ctx.mesh,
                in_specs=(q_spec, pool_spec, pool_spec, P(rows, None),
                          _off_spec(idx, rows), P(None, None, model),
                          P(None, None, model)),
                out_specs=q_spec, check_vma=False)(
                    q, pages_k, pages_v, block_table, idx, scales_k,
                    scales_v)

    def body(qs, pk, pv, bt, i):
        return kops.flash_decode_paged(qs, pk, pv, bt, i, l_real=l_real,
                                       window=window)

    with autotune.tp_shards(tp):
        return jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(q_spec, pool_spec, pool_spec, P(rows, None),
                      _off_spec(idx, rows)),
            out_specs=q_spec, check_vma=False)(
                q, pages_k, pages_v, block_table, idx)
