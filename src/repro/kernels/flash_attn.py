"""Flash-attention Pallas TPU kernels: fused prefill + ring-cache decode.

Two kernels move the attention hot path onto the same tuned-tile footing
as the DYAD matmul/ff kernels (:mod:`repro.kernels.dyad_mm`):

* :func:`flash_prefill` — ONE grid ``(B, K, S/bQ, T/bK)`` with the key axis
  sequential-innermost.  Online-softmax state (m, l, acc) lives in fp32
  VMEM scratch and is revisited across key tiles, so the ``(S, T)`` score
  matrix never exists — each ``(bQ·G, bK)`` score tile is consumed in
  VMEM by the softmax update and the P·V dot on the same grid step.  GQA
  is handled by folding the G query heads that share a KV head into the
  q-tile rows: one streamed K/V tile serves all G heads.  Each grid step
  classes its score tile from the scalar-prefetched offsets, the tile
  indices and the static sizes (:func:`_tile_class`):

  - *empty* — outside the causal / sliding-window band.  The key-tile
    index map clamps it onto an in-band neighbour (no DMA is issued for a
    revisited block) and ``pl.when`` skips its compute, so it costs
    neither bandwidth nor FLOPs;
  - *interior* — every (row, column) is valid (wholly below the diagonal,
    inside the window, no padded column);
  - *edge* — every other in-band tile.

  A masked tile body builds its mask as one compare per condition of a
  column-minus-row iota against a scalar (:func:`_tile_mask`).  The
  forward masks every in-band tile: on a v5e an unmasked interior body
  makes it no faster (1.4% slower at the OPT-125m training shape).

* :func:`flash_decode` — the S=1 ring-buffer cache path.  q is broadcast
  across key tiles of the ``(B, L, K, h)`` cache; the per-slot key
  position is computed IN-KERNEL from the scalar-prefetched write index
  ``idx`` (``pos[j] = idx - (idx - j) mod L`` — the ring layout of
  ``layers.attention``), so both the homogeneous ``Engine`` (scalar idx)
  and the per-slot ``ContinuousBatchingEngine`` (vector idx) decode steps
  hit the same kernel.  Key tiles wholly beyond ``idx`` (unwrapped cache)
  are skipped with ``pl.when``.

Backward (:func:`flash_prefill_grads`): the standard two-kernel flash
backward — probabilities are RECOMPUTED per tile from the saved
log-sum-exp (``lse = m + log l``), never stored.  ``dq`` runs on the
forward grid (key axis innermost, one fp32 dq accumulator per q tile);
``dk``/``dv`` run the transposed grid (q axis innermost, two fp32
accumulators per key tile).  Both skip empty tiles like the forward.
The dq kernel alone runs its interior tiles with no mask (3% faster on a
v5e at the training shape; the dk/dv kernel gains nothing from it, so it
masks every in-band tile).  At trace time each
:func:`flash_prefill_grads` call records one
``obs.route_event("flash_tiles", ...)`` with the class counts of one
(batch, kv-head) grid at zero offset, the training path's: how often the
unmasked dq body runs there.

Masking contract (shared with ``layers.attention``): query row ``r`` of
tile ``qi`` sits at absolute position ``q_off + qi*bQ + r//G``; key
column ``c`` at ``k_off + c``.  ``q_off``/``k_off`` are scalar-prefetched
per-batch vectors, which covers the no-cache forward (``k_off = 0``) and
the fresh-stream cache prefill (``q_off = k_off = idx``) with one kernel.
Masked probabilities are zeroed EXPLICITLY (``where(mask, e, 0)``), so a
fully-masked row yields output 0 (l = 0 guard), exactly like the XLA
paths after their ``jnp.maximum(l, 1e-30)`` guard.

Tile selection: ``block_q`` (query positions per tile) and ``block_k``
(keys per tile) resolve from the autotune cache under the
``flash_prefill`` / ``flash_decode`` op keys (``repro.perf.autotune``;
``block_b`` in the cache dict tiles q positions, ``block_k`` tiles keys,
``block_o`` is unused — the head dim is never tiled).  Degenerate (odd /
prime) S, T pad up to tile units exactly like ``plan_tiles``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels.dyad_mm import _largest_divisor, _plan_axis

NEG_INF = -1e30
_TINY = 1e-30

# minimal healthy tiles: q positions are sublane-like (unit 8); keys are
# the lane axis of the score tile (unit 128)
_UNIT_Q = 8
_UNIT_K = 128
# lanes carried by the m/l softmax-state scratch (all lanes hold the same
# value; 128 matches the fp32 native tile so no partial-lane relayouts)
_STATE_LANES = 128

# A device trace names a Pallas call after the innermost name scope around
# it: its ``name=`` if it has one, else the jitted wrapper that holds it.
# The benchmark's readers (chipbench/metrics/) find the prefill, dq, dkv and
# paged decode kernels by their wrappers, so those calls carry no ``name=``;
# the other decode kernels carry a fixed one (tests/test_kernel_names.py).


def resolve_attn_blocks(op: str, rows: int, n_kv: int, h: int, kv_len: int,
                        dtype, g: int, block_q=None, block_k=None,
                        page: Optional[int] = None):
    """Fill unspecified flash tile sizes from the autotune cache (explicit
    arguments always win).  ``block_b`` in the cached dict tiles q
    positions, ``block_k`` tiles keys; the GQA ratio ``g`` rides in the
    key as ``d_mid`` (it scales the resident q/acc rows ``bQ*G``) and the
    page size rides as ``d_page`` for the paged decode op — a key tile can
    never span a page boundary, so tiles tuned for one page size must not
    collide with another."""
    if block_q is None or block_k is None:
        from repro.perf.autotune import get_tuned_blocks

        tuned = get_tuned_blocks(op, rows, n_kv, h, kv_len,
                                 str(jnp.dtype(dtype)), d_mid=g,
                                 d_page=page)
        block_q = tuned["block_b"] if block_q is None else block_q
        block_k = tuned["block_k"] if block_k is None else block_k
    return block_q, block_k


def _as_offsets(off, B: int):
    """Normalize a scalar / (B,)-vector offset to an int32 (B,) vector."""
    off = jnp.asarray(off, jnp.int32).reshape(-1)
    return jnp.broadcast_to(off, (B,))


def _fold_gqa(q):
    """(B, S, K, G, h) -> (B, K, S*G, h): row r = s*G + g, so the G query
    heads sharing a KV head are adjacent rows of one q tile."""
    B, S, K, G, h = q.shape
    return q.transpose(0, 2, 1, 3, 4).reshape(B, K, S * G, h)


def _unfold_gqa(o, S: int, G: int):
    B, K, SG, h = o.shape
    return o.reshape(B, K, SG // G, G, h).transpose(0, 2, 1, 3, 4)[:, :S]


def _tile_class(causal: bool, window: Optional[int], d, qi, ki, bQ: int,
                bT: int, t_real: int):
    """Classify score tile (``qi``, ``ki``) of one (batch, kv-head) grid,
    with ``d = q_off - k_off``.  Returns ``(band, interior)``:

    * ``band`` — some (row, column) of the tile may be valid; a tile outside
      the (causal, window) band is *empty* and is skipped;
    * ``interior`` — every (row, column) is valid: the last key lies at or
      before the first query, no column is padding, and the farthest pair
      is inside the window.  Such a tile needs no mask.

    An in-band tile that is not interior is an *edge* tile.  Either value
    is the constant ``True`` where nothing constrains it.  Built from
    comparisons and ``&`` only, so it takes Python or NumPy integers (the
    trace-time tile counter) as well as traced scalars (the kernels)."""
    band = interior = True
    if causal:
        band = band & (ki * bT <= d + (qi + 1) * bQ - 1)
        interior = interior & ((ki + 1) * bT - 1 <= d + qi * bQ)
    if window is not None:
        band = band & ((ki + 1) * bT - 1 >= d + qi * bQ - window + 1)
        interior = interior & (d + (qi + 1) * bQ - 1 - ki * bT < window)
    if t_real % bT:
        interior = interior & ((ki + 1) * bT <= t_real)
    return band, interior


def _by_tile_class(body, band, interior, *, split: bool) -> None:
    """Run ``body(masked)`` for one grid step, and nothing on empty tiles.
    Where ``interior`` is the constant ``True`` no tile holds an invalid
    pair and ``body(False)`` runs.  Else, with ``split``, interior tiles
    run ``body(False)`` and edge tiles ``body(True)``; without it, every
    in-band tile runs ``body(True)``."""
    if interior is True:
        body(False)
        return
    if split:
        pl.when(interior)(functools.partial(body, False))
        edge = jnp.logical_not(interior)
        band = edge if band is True else jnp.logical_and(band, edge)
    if band is True:
        body(True)
    else:
        pl.when(band)(functools.partial(body, True))


def _tile_counts(nq: int, nt: int, bQ: int, bT: int, t_real: int,
                 causal: bool, window: Optional[int]):
    """(empty, interior, edge) tiles of one (batch, kv-head) grid at d = 0."""
    qi = np.arange(nq)[:, None]
    ki = np.arange(nt)[None, :]
    band, interior = _tile_class(causal, window, 0, qi, ki, bQ, bT, t_real)
    n_band = int(np.broadcast_to(band, (nq, nt)).sum())
    n_interior = int(np.broadcast_to(interior, (nq, nt)).sum())
    return nq * nt - n_band, n_interior, n_band - n_interior


def _record_tiles(nq: int, nt: int, bQ: int, bT: int, t_real: int,
                  causal: bool, window: Optional[int]) -> None:
    """Trace-time ``flash_tiles`` route event: how many tiles of each class
    one (batch, kv-head) grid holds at d = 0, i.e. how often the unmasked
    dq body runs on the training path.  Callers with other offsets get
    other counts."""
    empty, interior, edge = _tile_counts(nq, nt, bQ, bT, t_real, causal,
                                         window)
    obs.route_event("flash_tiles", f"empty{empty}_interior{interior}"
                    f"_edge{edge}", empty=empty, interior=interior,
                    edge=edge)


def _kv_index_map(causal: bool, window: Optional[int], bQ: int, bT: int,
                  nt: int):
    """Key/value index map with static band clamping: out-of-band grid
    steps re-request the nearest in-band tile, so Pallas issues no DMA for
    them (same-block revisit) and ``pl.when`` skips their compute."""

    def index(b, kh, qi, ki, qoff_ref, koff_ref):
        if not causal and window is None:
            return (b, kh, ki, 0)
        d = qoff_ref[b] - koff_ref[b]
        ki_eff = ki
        if causal:
            last = jnp.maximum((d + (qi + 1) * bQ - 1) // bT, 0)
            ki_eff = jnp.minimum(ki_eff, last)
        if window is not None:
            first = jnp.clip((d + qi * bQ - window + 1) // bT, 0, nt - 1)
            ki_eff = jnp.maximum(ki_eff, first)
        return (b, kh, ki_eff, 0)

    return index


def _tile_mask(d, qi, ki, bQ: int, bT: int, G: int, t_real: int,
               causal: bool, window: Optional[int]):
    """(bQ*G, bT) boolean validity mask for one in-band tile.  Row ``r`` sits
    at ``q_off + qi*bQ + r//G`` and column ``c`` at ``k_off + ki*bT + c``;
    with ``lim = d + qi*bQ - ki*bT`` the pair is causal when
    ``c - r//G <= lim`` and inside the window when ``c - r//G > lim -
    window``: one compare of the column-minus-row iota per condition."""
    shape = (bQ * G, bT)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if G > 1:
        rows = rows // G
    diff = cols - rows
    lim = d + qi * bQ - ki * bT
    conds = []
    if causal:
        conds.append(diff <= lim)
    if window is not None:
        conds.append(diff > lim - window)
    if t_real % bT:
        conds.append(cols < t_real - ki * bT)
    return functools.reduce(jnp.logical_and, conds)


# -- forward ------------------------------------------------------------------


def _prefill_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                    G: int, bQ: int, bT: int, t_real: int, causal: bool,
                    window: Optional[int], scale: float, save_lse: bool):
    if save_lse:
        lse_ref, m_s, l_s, acc = rest
    else:
        m_s, l_s, acc = rest
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nt = pl.num_programs(3)
    d = qoff_ref[b] - koff_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    def compute(masked: bool):
        # the cache-prefill path streams K/V in the cache dtype, which may
        # differ from the query's compute dtype: promote per-tile in VMEM
        ct = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        q = q_ref[0, 0].astype(ct)                       # (bQ*G, h)
        k = k_ref[0, 0].astype(ct)                       # (bT, h)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bQ*G, bT)
        if masked:
            mask = _tile_mask(d, qi, ki, bQ, bT, G, t_real, causal, window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[...]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)             # (bQ*G, 128)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        if masked:
            # explicit zeroing: fully-masked rows keep l == 0 -> output 0
            p = jnp.where(mask, p, 0.0)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_next
        acc[...] = acc[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    band, interior = _tile_class(causal, window, d, qi, ki, bQ, bT, t_real)
    _by_tile_class(compute, band, interior, split=False)

    @pl.when(ki == nt - 1)
    def _flush():
        l = l_s[:, :1]
        o_ref[0, 0] = (acc[...] / jnp.maximum(l, _TINY)).astype(o_ref.dtype)
        if save_lse:
            lse_ref[0, 0] = (m_s[:, :1]
                             + jnp.log(jnp.maximum(l_s[:, :1], _TINY)))


@functools.partial(
    jax.jit, static_argnames=("bQ", "bT", "G", "causal", "window", "t_real",
                              "save_lse", "interpret")
)
def _prefill_impl(q, k, v, qoff, koff, *, bQ, bT, G, causal, window, t_real,
                  save_lse, interpret):
    B, K, SG, h = q.shape
    Tp = k.shape[2]
    nq, nt = SG // (bQ * G), Tp // bT
    grid = (B, K, nq, nt)
    bQG = bQ * G

    q_spec = pl.BlockSpec((1, 1, bQG, h),
                          lambda b, kh, qi, ki, qo, ko: (b, kh, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bT, h),
                           _kv_index_map(causal, window, bQ, bT, nt))
    o_spec = pl.BlockSpec((1, 1, bQG, h),
                          lambda b, kh, qi, ki, qo, ko: (b, kh, qi, 0))
    out_shape = jax.ShapeDtypeStruct((B, K, SG, h), q.dtype)
    out_specs, out_shapes = [o_spec], [out_shape]
    if save_lse:
        out_specs.append(pl.BlockSpec(
            (1, 1, bQG, 1), lambda b, kh, qi, ki, qo, ko: (b, kh, qi, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((B, K, SG, 1), jnp.float32))

    scale = 1.0 / float(h) ** 0.5
    body = functools.partial(
        _prefill_kernel, G=G, bQ=bQ, bT=bT, t_real=t_real, causal=causal,
        window=window, scale=scale, save_lse=save_lse)
    out = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((bQG, _STATE_LANES), jnp.float32),
                pltpu.VMEM((bQG, _STATE_LANES), jnp.float32),
                pltpu.VMEM((bQG, h), jnp.float32),
            ],
        ),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(qoff, koff, q, k, v)
    return (out[0], out[1][..., 0]) if save_lse else (out[0], None)


def _plan_attn(S: int, T: int, block_q: int, block_k: int):
    bQ, Sp = _plan_axis(S, block_q, _UNIT_Q)
    bT, Tp = _plan_axis(T, block_k, _UNIT_K)
    return bQ, Sp, bT, Tp


def _pad_axis1(x, to: int):
    d = to - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, d)) + ((0, 0),) * (x.ndim - 2)) if d else x


def flash_prefill(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_off=0,
    k_off=0,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    save_lse: bool = False,
    block_q: int = None,
    block_k: int = None,
    interpret: bool = False,
):
    """Fused flash attention forward.

    q: (B, S, K, G, h); k, v: (B, T, K, h) — the layer-natural GQA layout.
    Query position ``s`` sits at ``q_off + s``; key ``t`` at ``k_off + t``
    (scalars or (B,) vectors — positions must be CONTIGUOUS from the
    offset, which every dispatch site guarantees).  Returns
    ``(out (B,S,K,G,h), lse)`` where ``lse`` is the (B, K, S*G) fp32
    log-sum-exp when ``save_lse`` (the backward residual), else None.
    """
    B, S, K, G, h = q.shape
    T = k.shape[1]
    bq, bk = resolve_attn_blocks("flash_prefill", S, K, h, T, q.dtype, G,
                                 block_q, block_k)
    bQ, Sp, bT, Tp = _plan_attn(S, T, bq, bk)
    q = _fold_gqa(_pad_axis1(q, Sp))
    k = _pad_axis1(k, Tp).transpose(0, 2, 1, 3)
    v = _pad_axis1(v, Tp).transpose(0, 2, 1, 3)
    o, lse = _prefill_impl(
        q, k, v, _as_offsets(q_off, B), _as_offsets(k_off, B),
        bQ=bQ, bT=bT, G=G, causal=causal, window=window, t_real=T,
        save_lse=save_lse, interpret=interpret)
    o = _unfold_gqa(o, S, G)
    if lse is not None and Sp != S:
        lse = lse.reshape(B, K, Sp, G)[:, :, :S].reshape(B, K, S * G)
    return o, lse


# -- backward: dq -------------------------------------------------------------
#
# Same grid as the forward (key axis innermost); probabilities recomputed
# per tile from the saved lse, one fp32 (bQ*G, h) dq accumulator.


def _dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, acc, *, G: int, bQ: int, bT: int,
               t_real: int, causal: bool, window: Optional[int],
               scale: float):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nt = pl.num_programs(3)
    d = qoff_ref[b] - koff_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    def compute(masked: bool):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0, 0])                   # (bQ*G, 1) rows
        if masked:
            p = jnp.where(_tile_mask(d, qi, ki, bQ, bT, G, t_real, causal,
                                     window), p, 0.0)
        dp = jax.lax.dot_general(
            do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    band, interior = _tile_class(causal, window, d, qi, ki, bQ, bT, t_real)
    # the one kernel that an unmasked interior body makes faster on a v5e
    _by_tile_class(compute, band, interior, split=True)

    @pl.when(ki == nt - 1)
    def _flush():
        dq_ref[0, 0] = acc[...].astype(dq_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bQ", "bT", "G", "causal", "window", "t_real",
                              "interpret")
)
def _dq_impl(q, k, v, do, lse, delta, qoff, koff, *, bQ, bT, G, causal,
             window, t_real, interpret):
    B, K, SG, h = q.shape
    Tp = k.shape[2]
    nq, nt = SG // (bQ * G), Tp // bT
    bQG = bQ * G

    q_spec = pl.BlockSpec((1, 1, bQG, h),
                          lambda b, kh, qi, ki, qo, ko: (b, kh, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bT, h),
                           _kv_index_map(causal, window, bQ, bT, nt))
    row_spec = pl.BlockSpec((1, 1, bQG, 1),
                            lambda b, kh, qi, ki, qo, ko: (b, kh, qi, 0))
    scale = 1.0 / float(h) ** 0.5
    body = functools.partial(_dq_kernel, G=G, bQ=bQ, bT=bT, t_real=t_real,
                             causal=causal, window=window, scale=scale)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K, nq, nt),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[q_spec],
            scratch_shapes=[pltpu.VMEM((bQG, h), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, K, SG, h), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(qoff, koff, q, k, v, do, lse, delta)[0]


# -- backward: dk / dv --------------------------------------------------------
#
# Transposed grid ``(B, K, T/bK, S/bQ)`` — the q axis is the reduction,
# innermost, so the two (bT, h) fp32 accumulators are revisited per key
# tile.  The q-side index map clamps out-of-band q tiles symmetrically.


def _q_index_map(causal: bool, window: Optional[int], bQ: int, bT: int,
                 nq: int):
    def index(b, kh, ki, qi, qoff_ref, koff_ref):
        if not causal and window is None:
            return (b, kh, qi, 0)
        d = qoff_ref[b] - koff_ref[b]
        qi_eff = qi
        if causal:
            # rows qrow >= kcol_min: qi >= (ki*bT - d) // bQ
            first = jnp.clip((ki * bT - d) // bQ, 0, nq - 1)
            qi_eff = jnp.maximum(qi_eff, first)
        if window is not None:
            last = jnp.maximum(
                ((ki + 1) * bT - 1 + window - 1 - d) // bQ, 0)
            qi_eff = jnp.minimum(qi_eff, last)
        return (b, kh, qi_eff, 0)

    return index


def _dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, kacc, vacc, *, G: int, bQ: int,
                bT: int, t_real: int, causal: bool, window: Optional[int],
                scale: float):
    b, ki, qi = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    d = qoff_ref[b] - koff_ref[b]

    @pl.when(qi == 0)
    def _init():
        kacc[...] = jnp.zeros_like(kacc)
        vacc[...] = jnp.zeros_like(vacc)

    def compute(masked: bool):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0, 0])
        if masked:
            p = jnp.where(_tile_mask(d, qi, ki, bQ, bT, G, t_real, causal,
                                     window), p, 0.0)
        do = do_ref[0, 0]
        # dv += P^T · dO  — contract the q rows
        vacc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        kacc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    band, interior = _tile_class(causal, window, d, qi, ki, bQ, bT, t_real)
    _by_tile_class(compute, band, interior, split=False)

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[0, 0] = kacc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = vacc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bQ", "bT", "G", "causal", "window", "t_real",
                              "interpret")
)
def _dkv_impl(q, k, v, do, lse, delta, qoff, koff, *, bQ, bT, G, causal,
              window, t_real, interpret):
    B, K, SG, h = q.shape
    Tp = k.shape[2]
    nq, nt = SG // (bQ * G), Tp // bT
    bQG = bQ * G

    q_spec = pl.BlockSpec((1, 1, bQG, h),
                          _q_index_map(causal, window, bQ, bT, nq))
    kv_spec = pl.BlockSpec((1, 1, bT, h),
                           lambda b, kh, ki, qi, qo, ko: (b, kh, ki, 0))
    row_spec = pl.BlockSpec((1, 1, bQG, 1),
                            _q_index_map(causal, window, bQ, bT, nq))
    scale = 1.0 / float(h) ** 0.5
    body = functools.partial(_dkv_kernel, G=G, bQ=bQ, bT=bT, t_real=t_real,
                             causal=causal, window=window, scale=scale)
    out_sds = jax.ShapeDtypeStruct((B, K, Tp, h), k.dtype)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K, nt, nq),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[kv_spec, kv_spec],
            scratch_shapes=[pltpu.VMEM((bT, h), jnp.float32),
                            pltpu.VMEM((bT, h), jnp.float32)],
        ),
        out_shape=[out_sds, out_sds],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(qoff, koff, q, k, v, do, lse, delta)


def flash_prefill_grads(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    o: jax.Array,
    lse: jax.Array,
    do: jax.Array,
    q_off=0,
    k_off=0,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = None,
    block_k: int = None,
    interpret: bool = False,
):
    """Flash backward: (dq, dk, dv) at the layer-natural layouts.

    ``lse`` is the (B, K, S*G) residual from ``flash_prefill(...,
    save_lse=True)``; probabilities are recomputed per tile from it —
    the ``(S, T)`` score matrix is never materialized here either.
    """
    B, S, K, G, h = q.shape
    T = k.shape[1]
    bq, bk = resolve_attn_blocks("flash_prefill", S, K, h, T, q.dtype, G,
                                 block_q, block_k)
    bQ, Sp, bT, Tp = _plan_attn(S, T, bq, bk)
    qf = _fold_gqa(_pad_axis1(q, Sp))
    dof = _fold_gqa(_pad_axis1(do.astype(q.dtype), Sp))
    kf = _pad_axis1(k, Tp).transpose(0, 2, 1, 3)
    vf = _pad_axis1(v, Tp).transpose(0, 2, 1, 3)
    of = _fold_gqa(_pad_axis1(o, Sp))
    delta = jnp.sum(of.astype(jnp.float32) * dof.astype(jnp.float32),
                    axis=-1)                                   # (B, K, SG)
    if Sp != S:
        # pad with a LARGE lse so recomputed p = exp(s - lse) underflows to
        # exactly 0 on the padded rows (NEG_INF would overflow to inf)
        lse = jnp.pad(lse.reshape(B, K, S, G),
                      ((0, 0), (0, 0), (0, Sp - S), (0, 0)),
                      constant_values=-NEG_INF).reshape(B, K, Sp * G)
    qoff, koff = _as_offsets(q_off, B), _as_offsets(k_off, B)
    # the kernels read lse/delta as (B, K, S*G, 1) row columns
    lse, delta = lse[..., None], delta[..., None]
    _record_tiles(Sp // bQ, Tp // bT, bQ, bT, T, causal, window)
    kw = dict(bQ=bQ, bT=bT, G=G, causal=causal, window=window, t_real=T,
              interpret=interpret)
    dq = _dq_impl(qf, kf, vf, dof, lse, delta, qoff, koff, **kw)
    dk, dv = _dkv_impl(qf, kf, vf, dof, lse, delta, qoff, koff, **kw)
    dq = _unfold_gqa(dq, S, G)
    dk = dk.transpose(0, 2, 1, 3)[:, :T]
    dv = dv.transpose(0, 2, 1, 3)[:, :T]
    return dq, dk, dv


# -- decode: the S=1 ring-cache step ------------------------------------------


def _decode_kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc, *,
                   bT: int, l_real: int, window: Optional[int],
                   scale: float):
    b, t = pl.program_id(0), pl.program_id(2)
    nt = pl.num_programs(2)
    idx = idx_ref[b]

    @pl.when(t == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    # slots wholly beyond the write index (unwrapped cache) hold nothing:
    # skip their tiles entirely.  A wrapped ring (idx >= L) keeps every
    # tile active since t*bT < L <= idx.
    @pl.when(t * bT <= idx)
    def _compute():
        G = q_ref.shape[2]
        # the cache may hold a different dtype than the query (bf16 KV
        # under fp32 compute or vice versa): promote per-tile in VMEM
        ct = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        q = q_ref[0, 0].astype(ct)                        # (G, h)
        k = k_ref[0, 0].astype(ct)                        # (bT, h)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, bT)
        j = jax.lax.broadcasted_iota(jnp.int32, (G, bT), 1) + t * bT
        # ring layout: slot j holds absolute position idx - (idx - j) % L
        pos = idx - jnp.remainder(idx - j, l_real)
        mask = jnp.logical_and(pos >= 0, j < l_real)
        if window is not None:
            mask = jnp.logical_and(mask, idx - pos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[...]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.where(mask, jnp.exp(s - m_next[:, :1]), 0.0)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_next
        acc[...] = acc[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _flush():
        l = l_s[:, :1]
        o_ref[0, 0] = (acc[...] / jnp.maximum(l, _TINY)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bT", "l_real", "window", "interpret")
)
def _decode_impl(q, k, v, idx, *, bT, l_real, window, interpret):
    B, K, G, h = q.shape
    Lp = k.shape[2]
    nt = Lp // bT

    q_spec = pl.BlockSpec((1, 1, G, h), lambda b, kh, t, i: (b, kh, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, bT, h), lambda b, kh, t, i: (b, kh, t, 0))
    scale = 1.0 / float(h) ** 0.5
    body = functools.partial(_decode_kernel, bT=bT, l_real=l_real,
                             window=window, scale=scale)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, nt),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec],
            scratch_shapes=[
                pltpu.VMEM((G, _STATE_LANES), jnp.float32),
                pltpu.VMEM((G, _STATE_LANES), jnp.float32),
                pltpu.VMEM((G, h), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, K, G, h), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode",
    )(idx, q, k, v)[0]


def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    idx,
    *,
    window: Optional[int] = None,
    block_k: int = None,
    interpret: bool = False,
):
    """One-token decode attention over a ring-buffer KV cache.

    q: (B, 1, K, G, h) or (B, K, G, h) — the single new (roped) query.
    k, v: (B, L, K, h) — the POST-WRITE cache.  ``idx`` is the cache
    write index of the current token (scalar, or (B,) per-slot vector
    from the continuous-batching engine); each slot's absolute position
    is derived from it in-kernel, so wrapped rings, bounded-window
    caches, and heterogeneous per-slot positions all resolve exactly.
    Returns (B, 1, K, G, h) / (B, K, G, h) matching the q rank.
    """
    squeeze = q.ndim == 5
    if squeeze:
        q = q[:, 0]
    B, K, G, h = q.shape
    L = k.shape[1]
    _, bk = resolve_attn_blocks("flash_decode", B, K, h, L, q.dtype, G,
                                None, block_k)
    bT, Lp = _plan_axis(L, bk, _UNIT_K)
    k = _pad_axis1(k, Lp).transpose(0, 2, 1, 3)
    v = _pad_axis1(v, Lp).transpose(0, 2, 1, 3)
    o = _decode_impl(q, k, v, _as_offsets(idx, B), bT=bT, l_real=L,
                     window=window, interpret=interpret)
    return o[:, None] if squeeze else o


# -- paged decode: gather K/V tiles through a block table ---------------------
#
# The paged-KV variant of :func:`flash_decode`.  The cache is a PAGE POOL
# ``(n_pages, P, K, h)`` shared by every slot; each slot owns an ordered
# block table row mapping its logical block ``j // P`` to a physical page.
# Both the block table and the per-slot write indices are scalar-prefetched,
# so the K/V index map can route every grid step's DMA to the right page
# BEFORE the kernel body runs — the gather costs an index computation, not
# a materialized per-slot cache copy.  The key-tile size is clamped to a
# divisor of the page size (a tile never spans a page boundary), and tiles
# wholly beyond a slot's write index are clamped onto the last live tile
# (revisited block = no DMA) with ``pl.when`` skipping their compute, so
# short sequences in a long-capacity table cost neither bandwidth nor
# FLOPs.  Unallocated block-table entries MUST still hold a valid page id
# (the engine points them at the reserved scratch page 0).


def _decode_paged_kernel(idx_ref, bt_ref, q_ref, k_ref, v_ref, o_ref, m_s,
                         l_s, acc, *, bT: int, l_real: int,
                         window: Optional[int], scale: float):
    b, t = pl.program_id(0), pl.program_id(2)
    nt = pl.num_programs(2)
    idx = idx_ref[b]

    @pl.when(t == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    # tiles wholly beyond the write index hold nothing: skip their compute
    # (their DMA was already clamped onto a live tile by the index map).
    @pl.when(t * bT <= idx)
    def _compute():
        G = q_ref.shape[2]
        ct = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        q = q_ref[0, 0].astype(ct)                        # (G, h)
        k = k_ref[0, 0].astype(ct)                        # (bT, h)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, bT)
        # logical position IS the tile coordinate: the block table is
        # ordered, pages never wrap (no ring arithmetic).
        j = jax.lax.broadcasted_iota(jnp.int32, (G, bT), 1) + t * bT
        mask = jnp.logical_and(j <= idx, j < l_real)
        if window is not None:
            mask = jnp.logical_and(mask, idx - j < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.where(mask, jnp.exp(s - m_next[:, :1]), 0.0)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_next
        acc[...] = acc[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _flush():
        l = l_s[:, :1]
        o_ref[0, 0] = (acc[...] / jnp.maximum(l, _TINY)).astype(o_ref.dtype)


def _paged_kv_index_map(bT: int, tiles_per_page: int):
    """Route grid step ``t`` of slot ``b`` to page ``bt[b, t*bT // P]``.
    Dead tiles (beyond the write index) re-request the last live tile so
    Pallas issues no DMA for them (same-block revisit)."""

    def index(b, kh, t, idx_ref, bt_ref):
        t_eff = jnp.minimum(t, jnp.maximum(idx_ref[b], 0) // bT)
        blk = t_eff // tiles_per_page
        return (bt_ref[b, blk], kh, t_eff % tiles_per_page, 0)

    return index


def _paged_scale_index_map(bT: int, tiles_per_page: int):
    """The twin of :func:`_paged_kv_index_map` for the per-token-row scale
    pools, laid out ``(n_pages, K, 1, P)`` so a tile is a ``(1, bT)`` row —
    the SAME block-table gather routes it alongside its quantized K/V
    tile."""

    def index(b, kh, t, idx_ref, bt_ref):
        t_eff = jnp.minimum(t, jnp.maximum(idx_ref[b], 0) // bT)
        blk = t_eff // tiles_per_page
        return (bt_ref[b, blk], kh, 0, t_eff % tiles_per_page)

    return index


def _decode_paged_kernel_q(idx_ref, bt_ref, q_ref, k_ref, v_ref, sk_ref,
                           sv_ref, o_ref, m_s, l_s, acc, *, bT: int,
                           l_real: int, window: Optional[int],
                           scale: float):
    """Quantized-KV twin of :func:`_decode_paged_kernel`: K/V tiles arrive
    as int8 payloads and are dequantized IN-KERNEL with their per-token-row
    fp32 scales.  Each scale is constant along the head dim the dots
    contract, so dequant folds into the score columns (``s * sk[None, :]``)
    and the probability rows (``p * sv[None, :]``) exactly — the payload is
    never expanded to fp in HBM.  Dead page rows hold zero scales (pool
    init), which the position mask already excludes."""
    b, t = pl.program_id(0), pl.program_id(2)
    nt = pl.num_programs(2)
    idx = idx_ref[b]

    @pl.when(t == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    @pl.when(t * bT <= idx)
    def _compute():
        G = q_ref.shape[2]
        q = q_ref[0, 0]                                   # (G, h)
        k = k_ref[0, 0].astype(q.dtype)                   # (bT, h) dequant
        sk = sk_ref[0, 0]                                 # (1, bT) fp32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sk * scale
        j = jax.lax.broadcasted_iota(jnp.int32, (G, bT), 1) + t * bT
        mask = jnp.logical_and(j <= idx, j < l_real)
        if window is not None:
            mask = jnp.logical_and(mask, idx - j < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.where(mask, jnp.exp(s - m_next[:, :1]), 0.0)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_next
        sv = sv_ref[0, 0]                                 # (1, bT) fp32
        acc[...] = acc[...] * alpha[:, :1] + jax.lax.dot_general(
            p * sv, v_ref[0, 0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _flush():
        l = l_s[:, :1]
        o_ref[0, 0] = (acc[...] / jnp.maximum(l, _TINY)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bT", "l_real", "window", "interpret")
)
def _decode_paged_impl(q, k, v, idx, bt, *, bT, l_real, window, interpret):
    B, K, G, h = q.shape
    P = k.shape[2]
    tp = P // bT
    nt = bt.shape[1] * tp

    q_spec = pl.BlockSpec((1, 1, G, h), lambda b, kh, t, i, m: (b, kh, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, bT, h), _paged_kv_index_map(bT, tp))
    scale = 1.0 / float(h) ** 0.5
    body = functools.partial(_decode_paged_kernel, bT=bT, l_real=l_real,
                             window=window, scale=scale)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K, nt),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec],
            scratch_shapes=[
                pltpu.VMEM((G, _STATE_LANES), jnp.float32),
                pltpu.VMEM((G, _STATE_LANES), jnp.float32),
                pltpu.VMEM((G, h), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, K, G, h), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(idx, bt, q, k, v)[0]


@functools.partial(
    jax.jit, static_argnames=("bT", "l_real", "window", "interpret")
)
def _decode_paged_q_impl(q, k, v, sk, sv, idx, bt, *, bT, l_real, window,
                         interpret):
    B, K, G, h = q.shape
    P = k.shape[2]
    tp = P // bT
    nt = bt.shape[1] * tp

    q_spec = pl.BlockSpec((1, 1, G, h), lambda b, kh, t, i, m: (b, kh, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, bT, h), _paged_kv_index_map(bT, tp))
    s_spec = pl.BlockSpec((1, 1, 1, bT), _paged_scale_index_map(bT, tp))
    scale = 1.0 / float(h) ** 0.5
    body = functools.partial(_decode_paged_kernel_q, bT=bT, l_real=l_real,
                             window=window, scale=scale)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K, nt),
            in_specs=[q_spec, kv_spec, kv_spec, s_spec, s_spec],
            out_specs=[q_spec],
            scratch_shapes=[
                pltpu.VMEM((G, _STATE_LANES), jnp.float32),
                pltpu.VMEM((G, _STATE_LANES), jnp.float32),
                pltpu.VMEM((G, h), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, K, G, h), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode_paged_q",
    )(idx, bt, q, k, v, sk, sv)[0]


def flash_decode_paged(
    q: jax.Array,
    pages_k: jax.Array,
    pages_v: jax.Array,
    block_table: jax.Array,
    idx,
    *,
    l_real: Optional[int] = None,
    window: Optional[int] = None,
    block_k: int = None,
    interpret: bool = False,
    scales_k: Optional[jax.Array] = None,
    scales_v: Optional[jax.Array] = None,
):
    """One-token decode attention over a PAGED KV cache.

    q: (B, 1, K, G, h) or (B, K, G, h) — the single new (roped) query.
    pages_k, pages_v: (n_pages, P, K, h) — the shared post-write page pool.
    ``block_table``: (B, n_blocks) int32, slot b's logical block ``j // P``
    lives in physical page ``block_table[b, j // P]`` (unallocated entries
    must point at a valid page — the engine's scratch page 0).  ``idx``:
    (B,) per-slot write index of the current token; logical positions are
    the tile coordinates themselves (ordered block tables, no ring).
    ``l_real`` bounds the logical length when the capacity ``n_blocks * P``
    overshoots it (page sizes that don't divide max_len).

    ``scales_k``/``scales_v`` (together) mark the pools as QUANTIZED:
    int8 payloads with per-token-row fp32 scales ``(n_pages, P, K)``
    (``repro.quant.quantize_kv_rows`` at the write site).  The kernel
    gathers the scale tiles through the same prefetched block table and
    dequantizes in-VMEM — K/V stream 2-4x fewer HBM bytes.
    Returns (B, 1, K, G, h) / (B, K, G, h) matching the q rank.
    """
    if (scales_k is None) != (scales_v is None):
        raise ValueError("scales_k and scales_v must be passed together")
    squeeze = q.ndim == 5
    if squeeze:
        q = q[:, 0]
    B, K, G, h = q.shape
    P = pages_k.shape[1]
    NB = block_table.shape[1]
    cap = NB * P
    if l_real is None:
        l_real = cap
    _, bk = resolve_attn_blocks("flash_decode_paged", B, K, h, cap,
                                pages_k.dtype if scales_k is not None
                                else q.dtype,
                                G, None, block_k, page=P)
    # a key tile must stay inside one page: largest divisor of P under the
    # requested tile (pages are pow2 in practice, so this is a pow2 clamp)
    bT = _largest_divisor(P, max(min(bk, P), 1))
    k = pages_k.transpose(0, 2, 1, 3)                     # (NP, K, P, h)
    v = pages_v.transpose(0, 2, 1, 3)
    if scales_k is not None:
        o = _decode_paged_q_impl(
            q, k, v,
            scales_k.transpose(0, 2, 1)[:, :, None],      # (NP, K, 1, P)
            scales_v.transpose(0, 2, 1)[:, :, None],
            _as_offsets(idx, B), jnp.asarray(block_table, jnp.int32),
            bT=bT, l_real=int(l_real), window=window, interpret=interpret)
    else:
        o = _decode_paged_impl(q, k, v, _as_offsets(idx, B),
                               jnp.asarray(block_table, jnp.int32),
                               bT=bT, l_real=int(l_real), window=window,
                               interpret=interpret)
    return o[:, None] if squeeze else o
