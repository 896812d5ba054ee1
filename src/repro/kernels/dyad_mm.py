"""Fused DYAD matmul Pallas TPU kernels — forward, backward, AND the
whole-ff megakernel.

Forward: one ``pallas_call`` computes BOTH dyad components into a single
VMEM-resident fp32 accumulator:

    out[b, g, o] = sum_k x1[b, g, k] * w1[g, o, k] + x2[b, g, k] * w2[g, o, k]

Megakernel (:func:`dyad_ff_fused`): the transformer ff module — up (and,
for SwiGLU, gate) DYAD matmul, activation epilogue, and the OT
down-projection — in ONE grid.  The ``(..., n, d_ff/n)`` hidden exists only
as an fp32 VMEM accumulator tile: it is activated in-register and consumed
by the down dot on the same grid step, so the three-dispatch split path's
hidden HBM round-trip (write (..., d_ff), read it back) disappears
entirely.  See the "megakernel" section below.

Backward: two more fused kernels keep the whole training hot path on Pallas
tiles (``kernels/ops.py`` routes its custom VJP through them):

* ``dyad_mm_dgrad``      — dx[b, g, i] = sum_o z1[b,g,o]*w1[g,o,i]
                                       + z2[b,g,o]*w2[g,o,i]
  (cotangent x transposed blocks, both components fused into ONE fp32
  accumulator — the add that ``ref.unview`` otherwise does in jnp);
* ``dyad_mm_dgrad_two``  — same contraction but the two components are
  emitted separately (variants whose input views live in different
  layouts: the caller applies the inverse re-view, then adds);
* ``dyad_mm_wgrad``      — dw1[g,o,i] = sum_b z1[b,g,o]*x1[b,g,i] and
  dw2 likewise, both weight grads in one grid with two fp32 accumulator
  tiles (the batch reduction never leaves VMEM).

No kernel ever materializes a transposed weight: the dgrad contraction runs
over the ``o`` axis of the SAME ``(n, d_out, d_in)`` weight tiles the forward
streams, and wgrad contracts the batch axis of the activation/cotangent
tiles directly.

This goes beyond the paper's ``-CAT`` trick: instead of concatenating the two
components into one ``2*n_dyad``-block bmm (which still materializes the
concatenated activations), both partial products accumulate in-register/VMEM
with zero extra HBM traffic.  The feature permutation that defines the
BLOCKTRANS component is handled by the caller as a strided re-view
(``ops.py``).

Layout
------
Mosaic accepts a block whose last two dims are multiples of the hardware
tile (8 sublanes, 128 lanes) or equal to the whole array axis.  The
layer-natural views are ``(B, n, d)``, where a one-dyad-block tile
``(bB, 1, bK)`` breaks that rule on its middle axis.  Every kernel here
therefore streams activations BLOCK-MAJOR, ``(n, B, d)``: the dyad-block
axis leads and is squeezed out of each block, leaving ``(bB, bK)`` tiles.
The public wrappers keep the ``(B, n, d)`` interface and swap the two
leading axes on the way in and out — one XLA transpose per activation
operand and per result, the price of this layout.  Per-row quantization
scales ride as ``(n, 1, d_out)`` so their tiles are ``(1, bO)``.

Grid: ``(n_dyad, B/bB, d_out/bO, d_in/bK)`` — the k axis is innermost so the
accumulator tile is revisited on consecutive steps; block=g, batch and out
tiles are embarrassingly parallel.

Tile selection
--------------
``block_b/block_o/block_k`` default to the autotuned sizes for this
``(shape, dtype, backend)`` key (:func:`repro.perf.autotune.get_tuned_blocks`;
falls back to 256/256/512 when the shape was never tuned).  Tiles are then
*planned* per axis (:func:`_plan_axis`): an axis that fits the requested
block is one whole-axis tile; a longer one is tiled by a multiple of the
hardware tile, zero-padded up to one when needed — zero rows/columns
contribute nothing and are sliced off the output.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ONE activation table for kernel epilogue and oracle — keep them in sync
from repro.kernels.ref import ACTS as _FF_ACTS

# minimal healthy tile per axis: sublane granularity on the batch axis,
# lane granularity on the feature axes (fp32 native tile is (8, 128))
_UNIT_B = 8
_UNIT_FEAT = 128

# the leading dyad-block axis: one block per grid step, squeezed out of
# every tile so the kernel body sees plain 2-D (rows, lanes) refs
_SQ = pl.Squeezed()


def _largest_divisor(dim: int, target: int) -> int:
    d = min(dim, target)
    while dim % d:
        d -= 1
    return d


def _plan_axis(dim: int, block: int, unit: int):
    """(tile, padded_dim) for one grid axis.

    Every tile is legal for Mosaic: the whole axis, or a multiple of
    ``unit`` (8 on a sublane axis, 128 on a lane axis).  An axis no longer
    than the requested block (or the unit) is one whole-axis tile.  A
    longer axis is rounded up to a multiple of ``unit`` (a no-op when it
    already is one — the caller zero-pads otherwise) and tiled by the
    largest multiple of ``unit`` within the block that divides it."""
    block = max(block, unit)
    if dim <= block:
        return dim, dim
    padded = -(-dim // unit) * unit
    tile = block // unit * unit
    while padded % tile:
        tile -= unit
    return tile, padded


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Concrete grid tiling for one fused-kernel invocation."""

    bB: int
    bO: int
    bK: int
    padded_b: int
    padded_o: int
    padded_k: int

    @property
    def grid_steps(self) -> int:
        return ((self.padded_b // self.bB) * (self.padded_o // self.bO)
                * (self.padded_k // self.bK))


def plan_tiles(B: int, d_out: int, d_in: int,
               block_b: int, block_o: int, block_k: int) -> TilePlan:
    bB, pb = _plan_axis(B, block_b, _UNIT_B)
    bO, po = _plan_axis(d_out, block_o, _UNIT_FEAT)
    bK, pk = _plan_axis(d_in, block_k, _UNIT_FEAT)
    return TilePlan(bB=bB, bO=bO, bK=bK,
                    padded_b=pb, padded_o=po, padded_k=pk)


def resolve_blocks(op: str, B: int, n: int, d_in: int, d_out: int, dtype,
                   block_b=None, block_o=None, block_k=None):
    """Fill unspecified block sizes from the autotune cache (explicit
    arguments always win).  Runs at trace time — shapes are concrete."""
    if block_b is None or block_o is None or block_k is None:
        from repro.perf.autotune import get_tuned_blocks

        tuned = get_tuned_blocks(op, B, n, d_in, d_out,
                                 str(jnp.dtype(dtype)))
        block_b = tuned["block_b"] if block_b is None else block_b
        block_o = tuned["block_o"] if block_o is None else block_o
        block_k = tuned["block_k"] if block_k is None else block_k
    return block_b, block_o, block_k


def _block_major(x):
    """``(B, n, d)`` layer view <-> ``(n, B, d)`` kernel layout (the swap
    is its own inverse)."""
    return jnp.swapaxes(x, 0, 1)


def _pad(x, widths):
    """Zero-pad the high end of each axis by ``widths`` (no-op if all 0)."""
    if not any(widths):
        return x
    return jnp.pad(x, [(0, w) for w in widths])


def _compiler_params(n_parallel: int, n_arbitrary: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * n_arbitrary)


# -- forward and dgrad: one body ----------------------------------------------
#
# Both contract a block-major activation tile ``(bB, bK)`` against a weight
# tile and accumulate in fp32 over the innermost grid axis.  The forward
# contracts the weight tile's lanes (``(bO, bK)``: x·wᵀ); dgrad contracts
# its sublanes (``(bK, bO)`` over the SAME ``(n, d_out, d_in)`` weight: the
# transposed-block product without ever transposing the weight).  For the
# dgrad grid ``(n, B/bB, d_in/bI, d_out/bK)`` the tile roles keep the
# layer-natural names in the autotune ``blocks`` dict: ``block_o`` tiles
# the produced feature axis (d_in there), ``block_k`` the contracted one.

# A device trace names a Pallas call after the innermost name scope around
# it: its ``name=`` if it has one, else the jitted wrapper that holds it.
# The benchmark's readers (chipbench/metrics/) find these kernels by their
# wrappers (``_mm_impl``, ``_wgrad_impl``, ``_ff_impl``), so the calls carry
# no ``name=`` and the wrappers keep their names (tests/test_kernel_names.py).

_FWD_DN = (((1,), (1,)), ((), ()))      # (bB, bK) x (bO, bK) -> (bB, bO)
_DGRAD_DN = (((1,), (0,)), ((), ()))    # (bB, bK) x (bK, bO) -> (bB, bO)


def _mm_kernel(*refs, nk: int, two: bool, quant: bool, dn):
    """acc += x1·w1 (*s1) + x2·w2 (*s2) over grid axis 3.

    ``two`` keeps the components in separate accumulators and outputs (the
    OT/DT forward, whose components write different output layouts, and
    the IT/DT dgrad, whose components un-view differently); otherwise both
    partial products land in ONE accumulator.  ``quant`` streams int8/fp8
    weight tiles, cast to the activation dtype in-register, and multiplies
    the per-(block, out_row) fp32 scale into each partial product."""
    x1, x2, w1, w2 = refs[:4]
    scales = refs[4:6] if quant else (None, None)
    rest = refs[6 if quant else 4:]
    n_acc = 2 if two else 1
    outs, accs = rest[:n_acc], rest[n_acc:]
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    for x, w, s, acc in zip((x1, x2), (w1, w2), scales, (accs[0], accs[-1])):
        part = jax.lax.dot_general(x[...], w[...].astype(x.dtype), dn,
                                   preferred_element_type=jnp.float32)
        acc[...] += part if s is None else part * s[...]

    @pl.when(k == nk - 1)
    def _flush():
        for out, acc in zip(outs, accs):
            out[...] = acc[...].astype(out.dtype)


@functools.partial(
    jax.jit, static_argnames=("bB", "bO", "bK", "two", "dgrad", "interpret")
)
def _mm_impl(x1, x2, w1, w2, scales, *, bB: int, bO: int, bK: int,
             two: bool, dgrad: bool, interpret: bool):
    """x1, x2: block-major (n, B, K).  w1, w2: (n, O, K), or (n, K, O) for
    dgrad.  scales: () or (s1, s2) as (n, 1, O).  Returns a tuple of one
    or (``two``) two (n, B, O) outputs in x1's dtype."""
    n, B, K = x1.shape
    O = w1.shape[2] if dgrad else w1.shape[1]
    nk = K // bK

    x_spec = pl.BlockSpec((_SQ, bB, bK), lambda g, b, o, k: (g, b, k))
    if dgrad:
        w_spec = pl.BlockSpec((_SQ, bK, bO), lambda g, b, o, k: (g, k, o))
    else:
        w_spec = pl.BlockSpec((_SQ, bO, bK), lambda g, b, o, k: (g, o, k))
    s_spec = pl.BlockSpec((_SQ, 1, bO), lambda g, b, o, k: (g, 0, o))
    o_spec = pl.BlockSpec((_SQ, bB, bO), lambda g, b, o, k: (g, b, o))
    n_acc = 2 if two else 1
    out_sds = jax.ShapeDtypeStruct((n, B, O), x1.dtype)
    body = functools.partial(_mm_kernel, nk=nk, two=two, quant=bool(scales),
                             dn=_DGRAD_DN if dgrad else _FWD_DN)

    outs = pl.pallas_call(
        body,
        grid=(n, B // bB, O // bO, nk),
        in_specs=[x_spec, x_spec, w_spec, w_spec] + [s_spec] * len(scales),
        out_specs=[o_spec] * n_acc,
        out_shape=[out_sds] * n_acc,
        scratch_shapes=[pltpu.VMEM((bB, bO), jnp.float32)] * n_acc,
        compiler_params=_compiler_params(3, 1),
        interpret=interpret,
    )(x1, x2, w1, w2, *scales)
    return tuple(outs)


def _run_mm(op: str, x1, x2, w1, w2, scales, blocks, *, two: bool,
            dgrad: bool, key_dtype, interpret: bool):
    """Resolve + plan tiles, pad, go block-major, run, come back.

    x1, x2: (B, n, K) layer views; w1, w2 as :func:`_mm_impl`; scales: ()
    or (s1, s2) as (n, O).  ``key_dtype`` is the dtype field of the
    autotune key.  Returns (B, n, O), or a pair of them when ``two``."""
    B, n, K = x1.shape
    O = w1.shape[2] if dgrad else w1.shape[1]
    d_in, d_out = (O, K) if dgrad else (K, O)       # layer-natural key dims
    bb, bo, bk = resolve_blocks(op, B, n, d_in, d_out, key_dtype, *blocks)
    plan = plan_tiles(B, O, K, bb, bo, bk)
    db, do, dk = (plan.padded_b - B, plan.padded_o - O, plan.padded_k - K)
    x1, x2 = (_pad(_block_major(x), (0, db, dk)) for x in (x1, x2))
    w1, w2 = (_pad(w, (0, dk, do) if dgrad else (0, do, dk))
              for w in (w1, w2))
    # padded out rows hold zero weights; their scale value is moot
    scales = tuple(_pad(s[:, None, :], (0, 0, do)) for s in scales)
    outs = _mm_impl(x1, x2, w1, w2, scales, bB=plan.bB, bO=plan.bO,
                    bK=plan.bK, two=two, dgrad=dgrad, interpret=interpret)
    outs = tuple(_block_major(z[:, :B, :O]) for z in outs)
    return outs if two else outs[0]


def dyad_mm_blocks(
    x1: jax.Array,
    x2: jax.Array,
    w1: jax.Array,
    w2: jax.Array,
    *,
    block_b: int = None,
    block_o: int = None,
    block_k: int = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused dual-bmm over per-block views.

    x1, x2: (B, n_dyad, d_in) — block-contiguous / permuted input views.
    w1, w2: (n_dyad, d_out, d_in).
    Returns (B, n_dyad, d_out), dtype of x1.

    Block sizes default to the autotuned tiles for this shape/dtype/backend
    (``repro.perf.autotune``); pass explicit values to override.
    """
    return _run_mm("dyad_mm_blocks", x1, x2, w1, w2, (),
                   (block_b, block_o, block_k), two=False, dgrad=False,
                   key_dtype=x1.dtype, interpret=interpret)


def dyad_mm_blocks_two(
    x1: jax.Array,
    x2: jax.Array,
    w1: jax.Array,
    w2: jax.Array,
    *,
    block_b: int = None,
    block_o: int = None,
    block_k: int = None,
    interpret: bool = False,
):
    """As :func:`dyad_mm_blocks` but returns (z1, z2) separately (OT/DT)."""
    return _run_mm("dyad_mm_blocks_two", x1, x2, w1, w2, (),
                   (block_b, block_o, block_k), two=True, dgrad=False,
                   key_dtype=x1.dtype, interpret=interpret)


def dyad_mm_dgrad(
    z1: jax.Array,
    z2: jax.Array,
    w1: jax.Array,
    w2: jax.Array,
    *,
    block_b: int = None,
    block_o: int = None,
    block_k: int = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused input cotangent: both components accumulate into ONE tile.

    z1, z2: (B, n_dyad, d_out) per-component cotangent views.
    w1, w2: (n_dyad, d_out, d_in).
    Returns dx (B, n_dyad, d_in), dtype of z1.  Valid whenever both dx
    components share a layout (the OT variant's input side).
    """
    return _run_mm("dyad_mm_dgrad", z1, z2, w1, w2, (),
                   (block_b, block_o, block_k), two=False, dgrad=True,
                   key_dtype=z1.dtype, interpret=interpret)


def dyad_mm_dgrad_two(
    z1: jax.Array,
    z2: jax.Array,
    w1: jax.Array,
    w2: jax.Array,
    *,
    block_b: int = None,
    block_o: int = None,
    block_k: int = None,
    interpret: bool = False,
):
    """As :func:`dyad_mm_dgrad` but returns (dx1, dx2) separately (IT/DT)."""
    return _run_mm("dyad_mm_dgrad_two", z1, z2, w1, w2, (),
                   (block_b, block_o, block_k), two=True, dgrad=True,
                   key_dtype=z1.dtype, interpret=interpret)


# -- backward: wgrad (weight cotangents) --------------------------------------
#
# Grid ``(n, d_out/bO, d_in/bI, B/bB)`` — the reduction runs over the batch
# axis, innermost so both (bO, bI) fp32 accumulator tiles are revisited on
# consecutive steps.  One grid produces BOTH dw1 and dw2: the per-step dots
# share scheduling, and neither partial sum ever round-trips to HBM.


def _wgrad_kernel(x1_ref, x2_ref, z1_ref, z2_ref, o1_ref, o2_ref,
                  acc1_ref, acc2_ref, *, nb: int):
    b = pl.program_id(3)

    @pl.when(b == 0)
    def _init():
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        acc2_ref[...] = jnp.zeros_like(acc2_ref)

    # (bB, bO)^T x (bB, bI) -> (bO, bI): contract the batch axes.
    dn = (((0,), (0,)), ((), ()))
    acc1_ref[...] += jax.lax.dot_general(
        z1_ref[...], x1_ref[...], dn, preferred_element_type=jnp.float32)
    acc2_ref[...] += jax.lax.dot_general(
        z2_ref[...], x2_ref[...], dn, preferred_element_type=jnp.float32)

    @pl.when(b == nb - 1)
    def _flush():
        o1_ref[...] = acc1_ref[...].astype(o1_ref.dtype)
        o2_ref[...] = acc2_ref[...].astype(o2_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bB", "bO", "bI", "out_dtype", "interpret")
)
def _wgrad_impl(x1, x2, z1, z2, *, bB: int, bO: int, bI: int,
                out_dtype: str, interpret: bool):
    """x1, x2: block-major (n, B, d_in); z1, z2: (n, B, d_out)."""
    n, B, d_in = x1.shape
    d_out = z1.shape[2]
    nb = B // bB

    x_spec = pl.BlockSpec((_SQ, bB, bI), lambda g, o, i, b: (g, b, i))
    z_spec = pl.BlockSpec((_SQ, bB, bO), lambda g, o, i, b: (g, b, o))
    o_spec = pl.BlockSpec((_SQ, bO, bI), lambda g, o, i, b: (g, o, i))
    out_sds = jax.ShapeDtypeStruct((n, d_out, d_in), jnp.dtype(out_dtype))
    acc = pltpu.VMEM((bO, bI), jnp.float32)

    return pl.pallas_call(
        functools.partial(_wgrad_kernel, nb=nb),
        grid=(n, d_out // bO, d_in // bI, nb),
        in_specs=[x_spec, x_spec, z_spec, z_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[out_sds, out_sds],
        scratch_shapes=[acc, acc],
        compiler_params=_compiler_params(3, 1),
        interpret=interpret,
    )(x1, x2, z1, z2)


def dyad_mm_wgrad(
    x1: jax.Array,
    x2: jax.Array,
    z1: jax.Array,
    z2: jax.Array,
    *,
    out_dtype=None,
    block_b: int = None,
    block_o: int = None,
    block_k: int = None,
    interpret: bool = False,
):
    """Fused weight cotangents with fp32 accumulator tiles.

    x1, x2: (B, n_dyad, d_in) per-component input views (the residuals).
    z1, z2: (B, n_dyad, d_out) per-component cotangent views.
    Returns (dw1, dw2): (n_dyad, d_out, d_in) in ``out_dtype`` (defaults to
    x1's dtype) — the cast happens once, from the fp32 accumulator.
    """
    B, n, d_in = x1.shape
    _, _, d_out = z1.shape
    out_dtype = jnp.dtype(out_dtype if out_dtype is not None else x1.dtype)
    bb, bo, bk = resolve_blocks("dyad_mm_wgrad", B, n, d_in, d_out,
                                x1.dtype, block_b, block_o, block_k)
    plan = plan_tiles(B, d_out, d_in, bb, bo, bk)
    db, do, di = (plan.padded_b - B, plan.padded_o - d_out,
                  plan.padded_k - d_in)
    x1, x2 = (_pad(_block_major(x), (0, db, di)) for x in (x1, x2))
    z1, z2 = (_pad(_block_major(z), (0, db, do)) for z in (z1, z2))
    dw1, dw2 = _wgrad_impl(x1, x2, z1, z2, bB=plan.bB, bO=plan.bO,
                           bI=plan.bK, out_dtype=str(out_dtype),
                           interpret=interpret)
    return dw1[:, :d_out, :d_in], dw2[:, :d_out, :d_in]


# -- megakernel: the whole ff module in one grid ------------------------------
#
# ``dyad_ff_fused`` computes, per dyad block g:
#
#     pre[b,g,j] = sum_k x1[b,g,k]*wu1[g,j,k] + x2[b,g,k]*wu2[g,j,k]   (up, IT)
#     h[b,g,j]   = act(pre)                       (SwiGLU: silu(gate_pre)*pre)
#     z*[b,g,o]  = sum_j h[b,g,j]*wd*[g,o,j]                         (down, OT)
#
# Grid ``(n, B/bB, d_out/bO, d_ff_b/bJ, d_in/bK)``: j (the hidden feature
# axis) and k (the up contraction) are sequential-innermost, everything else
# embarrassingly parallel.  Per (g, b, o) the down accumulators (bB, bO) are
# revisited across (j, k); per (g, b, o, j) the hidden accumulator (bB, bJ)
# is revisited across k, activated in-register at ``k == nk-1``, and fed
# straight into the down dot — the hidden NEVER exists in HBM.  Operand
# streaming (x tiles, up/gate/down weight tiles) overlaps the MXU work via
# the standard Pallas double-buffered pipeline over grid steps.
#
# The o axis revisits recompute the hidden once per output tile; for DYAD ff
# dims the per-block down output d_model/n fits one tile (d_out/bO == 1), so
# in practice the hidden is computed exactly once.


@dataclasses.dataclass(frozen=True)
class FFTilePlan:
    """Concrete 4-axis tiling for one megakernel invocation."""

    bB: int
    bO: int
    bJ: int
    bK: int
    padded_b: int
    padded_o: int
    padded_j: int
    padded_k: int

    @property
    def grid_steps(self) -> int:
        return ((self.padded_b // self.bB) * (self.padded_o // self.bO)
                * (self.padded_j // self.bJ) * (self.padded_k // self.bK))


def plan_ff_tiles(B: int, d_out: int, d_ff: int, d_in: int,
                  block_b: int, block_o: int, block_j: int,
                  block_k: int) -> FFTilePlan:
    """Tile all four megakernel axes, padding degenerate dims exactly like
    :func:`plan_tiles`.  Zero-padding stays exact through the activation:
    padded j columns of the DOWN weights are zero, so whatever act(0) is,
    it contributes nothing to the output."""
    bB, pb = _plan_axis(B, block_b, _UNIT_B)
    bO, po = _plan_axis(d_out, block_o, _UNIT_FEAT)
    bJ, pj = _plan_axis(d_ff, block_j, _UNIT_FEAT)
    bK, pk = _plan_axis(d_in, block_k, _UNIT_FEAT)
    return FFTilePlan(bB=bB, bO=bO, bJ=bJ, bK=bK, padded_b=pb, padded_o=po,
                      padded_j=pj, padded_k=pk)


def resolve_ff_blocks(op: str, B: int, n: int, d_in: int, d_out: int,
                      d_ff: int, dtype, block_b=None, block_o=None,
                      block_k=None, block_j=None):
    """Fill unspecified megakernel block sizes from the autotune cache
    (explicit arguments always win).  The ff key carries the hidden width
    (``d_mid``) on top of the usual dims — three weight tensors share one
    VMEM budget, so tiles tuned for a different d_ff must never collide."""
    if (block_b is None or block_o is None or block_k is None
            or block_j is None):
        from repro.perf.autotune import get_tuned_blocks

        tuned = get_tuned_blocks(op, B, n, d_in, d_out,
                                 str(jnp.dtype(dtype)), d_mid=d_ff)
        block_b = tuned["block_b"] if block_b is None else block_b
        block_o = tuned["block_o"] if block_o is None else block_o
        block_k = tuned["block_k"] if block_k is None else block_k
        block_j = tuned["block_j"] if block_j is None else block_j
    return block_b, block_o, block_k, block_j


def _ff_kernel(*refs, nj: int, nk: int, act: str, quant: bool):
    """Operands: x1, x2, the up weights ((wg1, wg2,) wu1, wu2), wd1, wd2,
    [their scales in the same order when ``quant``], z1, z2, then scratch:
    one hidden accumulator per up projection (gate first), acc1, acc2.
    SwiGLU runs TWO up accumulators over the shared k loop; the gated
    product forms in-register at the k flush."""
    n_up = 4 if act == "swiglu" else 2
    x1, x2 = refs[:2]
    ups = refs[2:2 + n_up]
    wd1, wd2 = refs[2 + n_up:4 + n_up]
    i = 4 + n_up
    if quant:
        s_ups, (sd1, sd2) = refs[i:i + n_up], refs[i + n_up:i + n_up + 2]
        i += n_up + 2
    else:
        s_ups, sd1, sd2 = (None,) * n_up, None, None
    z1, z2 = refs[i:i + 2]
    haccs = refs[i + 2:-2]
    acc1, acc2 = refs[-2:]
    j = pl.program_id(3)
    k = pl.program_id(4)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_down():
        acc1[...] = jnp.zeros_like(acc1)
        acc2[...] = jnp.zeros_like(acc2)

    @pl.when(k == 0)
    def _init_up():
        for h in haccs:
            h[...] = jnp.zeros_like(h)

    dn = (((1,), (1,)), ((), ()))

    def dot(a, w, s):
        # (rows, c) x (out, c)^T -> (rows, out), fp32 on the MXU; a
        # quantized weight tile is cast in-register and its per-row scale
        # applied to the partial product (exact: the scale is c-invariant)
        part = jax.lax.dot_general(a, w[...].astype(a.dtype), dn,
                                   preferred_element_type=jnp.float32)
        return part if s is None else part * s[...]

    for h, p in zip(haccs, range(0, n_up, 2)):
        h[...] += dot(x1[...], ups[p], s_ups[p])
        h[...] += dot(x2[...], ups[p + 1], s_ups[p + 1])

    @pl.when(k == nk - 1)
    def _act_and_down():
        # activation epilogue in-register, then the down dot consumes the
        # hidden tile without it ever leaving VMEM.
        if act == "swiglu":
            hv = jax.nn.silu(haccs[0][...]) * haccs[1][...]
        else:
            hv = _FF_ACTS[act](haccs[0][...])
        hv = hv.astype(x1.dtype)
        acc1[...] += dot(hv, wd1, sd1)
        acc2[...] += dot(hv, wd2, sd2)

    @pl.when(jnp.logical_and(j == nj - 1, k == nk - 1))
    def _flush():
        z1[...] = acc1[...].astype(z1.dtype)
        z2[...] = acc2[...].astype(z2.dtype)


@functools.partial(
    jax.jit, static_argnames=("bB", "bO", "bJ", "bK", "act", "interpret")
)
def _ff_impl(x1, x2, weights, scales, *, bB: int, bO: int, bJ: int,
             bK: int, act: str, interpret: bool):
    """x1, x2: block-major (n, B, d_in).  weights: the up weights
    (n, d_ff_b, d_in) then wd1, wd2 (n, d_out, d_ff_b).  scales: () or one
    (n, 1, rows) sidecar per weight, same order."""
    n, B, d_in = x1.shape
    n_up = 4 if act == "swiglu" else 2
    d_out, d_ffb = weights[-1].shape[1:]
    nj, nk = d_ffb // bJ, d_in // bK

    x_spec = pl.BlockSpec((_SQ, bB, bK), lambda g, b, o, j, k: (g, b, k))
    wu_spec = pl.BlockSpec((_SQ, bJ, bK), lambda g, b, o, j, k: (g, j, k))
    wd_spec = pl.BlockSpec((_SQ, bO, bJ), lambda g, b, o, j, k: (g, o, j))
    su_spec = pl.BlockSpec((_SQ, 1, bJ), lambda g, b, o, j, k: (g, 0, j))
    sd_spec = pl.BlockSpec((_SQ, 1, bO), lambda g, b, o, j, k: (g, 0, o))
    z_spec = pl.BlockSpec((_SQ, bB, bO), lambda g, b, o, j, k: (g, b, o))
    in_specs = [x_spec] * 2 + [wu_spec] * n_up + [wd_spec] * 2
    if scales:
        in_specs += [su_spec] * n_up + [sd_spec] * 2
    out_sds = jax.ShapeDtypeStruct((n, B, d_out), x1.dtype)
    scratch = ([pltpu.VMEM((bB, bJ), jnp.float32)] * (n_up // 2)
               + [pltpu.VMEM((bB, bO), jnp.float32)] * 2)
    body = functools.partial(_ff_kernel, nj=nj, nk=nk, act=act,
                             quant=bool(scales))

    return tuple(pl.pallas_call(
        body,
        grid=(n, B // bB, d_out // bO, nj, nk),
        in_specs=in_specs,
        out_specs=[z_spec, z_spec],
        out_shape=[out_sds, out_sds],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(3, 2),
        interpret=interpret,
    )(x1, x2, *weights, *scales))


def _run_ff(op: str, x1, x2, ups, downs, s_ups, s_downs, act: str, blocks,
            *, key_dtype, interpret: bool):
    """Plan, pad, go block-major, run the megakernel, come back.  ``ups``
    are the (gate then) up weights, ``downs`` (wd1, wd2); the scale tuples
    are empty for the unquantized body."""
    B, n, d_in = x1.shape
    d_ffb = ups[0].shape[1]
    d_out = downs[0].shape[1]
    bb, bo, bk, bj = resolve_ff_blocks(op, B, n, d_in, d_out, d_ffb,
                                       key_dtype, *blocks)
    plan = plan_ff_tiles(B, d_out, d_ffb, d_in, bb, bo, bj, bk)
    db, do = plan.padded_b - B, plan.padded_o - d_out
    dj, dk = plan.padded_j - d_ffb, plan.padded_k - d_in
    x1, x2 = (_pad(_block_major(x), (0, db, dk)) for x in (x1, x2))
    weights = (tuple(_pad(w, (0, dj, dk)) for w in ups)
               + tuple(_pad(w, (0, do, dj)) for w in downs))
    scales = (tuple(_pad(s[:, None, :], (0, 0, dj)) for s in s_ups)
              + tuple(_pad(s[:, None, :], (0, 0, do)) for s in s_downs))
    z1, z2 = _ff_impl(x1, x2, weights, scales, bB=plan.bB, bO=plan.bO,
                      bJ=plan.bJ, bK=plan.bK, act=act, interpret=interpret)
    return tuple(_block_major(z[:, :B, :d_out]) for z in (z1, z2))


def _check_ff_act(act: str, wg1, wg2):
    gated = act == "swiglu"
    if gated != (wg1 is not None) or gated != (wg2 is not None):
        raise ValueError("wg1/wg2 must be passed exactly when act='swiglu'")
    if act not in _FF_ACTS and not gated:
        raise ValueError(f"unsupported megakernel activation {act!r}")
    return gated


def dyad_ff_fused(
    x1: jax.Array,
    x2: jax.Array,
    wu1: jax.Array,
    wu2: jax.Array,
    wd1: jax.Array,
    wd2: jax.Array,
    *,
    wg1: jax.Array = None,
    wg2: jax.Array = None,
    act: str = "gelu",
    block_b: int = None,
    block_o: int = None,
    block_k: int = None,
    block_j: int = None,
    interpret: bool = False,
):
    """The whole DYAD ff module in one Pallas grid; hidden stays in VMEM.

    x1, x2:   (B, n_dyad, d_in) block-contiguous / permuted input views (IT).
    wu1, wu2: (n_dyad, d_ff_b, d_in) up weights; wg1/wg2 likewise for the
              SwiGLU gate (required iff ``act == "swiglu"``).
    wd1, wd2: (n_dyad, d_out, d_ff_b) down weights (OT: consumed from the
              block layout, so both components read the SAME hidden tile).
    Returns (z1, z2): (B, n_dyad, d_out) down-projection components — the
    caller applies the OT output re-view + add (``ref.combine``).

    Tiles default to the autotuned sizes under the ``dyad_ff_fused`` /
    ``dyad_ff_fused_swiglu`` op key (which carries d_ff); explicit
    ``block_*`` arguments override.
    """
    gated = _check_ff_act(act, wg1, wg2)
    ups = (wg1, wg2, wu1, wu2) if gated else (wu1, wu2)
    op = "dyad_ff_fused_swiglu" if gated else "dyad_ff_fused"
    return _run_ff(op, x1, x2, ups, (wd1, wd2), (), (), act,
                   (block_b, block_o, block_k, block_j),
                   key_dtype=x1.dtype, interpret=interpret)


# -- quantized twins: int8/fp8 weight streams, dequant at the VMEM load -------
#
# Weight tiles stream in their QUANTIZED dtype (1 byte/elem — the HBM
# stream the forward is bound on shrinks 2-4x); the per-(block, out_row)
# fp32 scales (``repro.quant.quantize_dyad_weight``) ride as tiny sidecar
# operands.  Because each scale is constant along the contracted axis, the
# dequant is a single epilogue multiply on the fp32 partial product:
#
#     acc += (x_tile @ q_tile^T) * s_tile        (exact: s is k-invariant)
#
# — the integer payload is cast to the activation dtype in-register (int8
# magnitudes <= 127 and every fp8 value are exactly representable in bf16
# and fp32, so the cast is lossless) and never exists dequantized in HBM.
# Activation/hidden dataflow, grids, and tile planning are identical to
# the unquantized kernels (the same bodies with ``quant=True``); the ops
# autotune under ``*_w8`` keys whose dtype field carries the weight
# payload dtype, so quantized tiles never collide with unquantized ones.


def dyad_mm_blocks_q(
    x1: jax.Array,
    x2: jax.Array,
    w1: jax.Array,
    w2: jax.Array,
    s1: jax.Array,
    s2: jax.Array,
    *,
    block_b: int = None,
    block_o: int = None,
    block_k: int = None,
    interpret: bool = False,
) -> jax.Array:
    """:func:`dyad_mm_blocks` with quantized weight streams.

    w1, w2: (n_dyad, d_out, d_in) int8/fp8 payloads; s1, s2: (n_dyad,
    d_out) fp32 per-(block, out_row) scales.  Output in x1's dtype."""
    return _run_mm("dyad_mm_blocks_w8", x1, x2, w1, w2, (s1, s2),
                   (block_b, block_o, block_k), two=False, dgrad=False,
                   key_dtype=w1.dtype, interpret=interpret)


def dyad_mm_blocks_two_q(
    x1: jax.Array,
    x2: jax.Array,
    w1: jax.Array,
    w2: jax.Array,
    s1: jax.Array,
    s2: jax.Array,
    *,
    block_b: int = None,
    block_o: int = None,
    block_k: int = None,
    interpret: bool = False,
):
    """As :func:`dyad_mm_blocks_q` but returns (z1, z2) separately (OT/DT)."""
    return _run_mm("dyad_mm_blocks_two_w8", x1, x2, w1, w2, (s1, s2),
                   (block_b, block_o, block_k), two=True, dgrad=False,
                   key_dtype=w1.dtype, interpret=interpret)


def dyad_ff_fused_q(
    x1: jax.Array,
    x2: jax.Array,
    wu1: jax.Array,
    wu2: jax.Array,
    wd1: jax.Array,
    wd2: jax.Array,
    su1: jax.Array,
    su2: jax.Array,
    sd1: jax.Array,
    sd2: jax.Array,
    *,
    wg1: jax.Array = None,
    wg2: jax.Array = None,
    sg1: jax.Array = None,
    sg2: jax.Array = None,
    act: str = "gelu",
    block_b: int = None,
    block_o: int = None,
    block_k: int = None,
    block_j: int = None,
    interpret: bool = False,
):
    """:func:`dyad_ff_fused` with quantized weight streams.

    wu*/wg*: (n, d_ff_b, d_in) int8/fp8 payloads with su*/sg* (n, d_ff_b)
    fp32 scales; wd*: (n, d_out, d_ff_b) payloads with sd* (n, d_out)
    scales.  Activation/hidden dataflow is IDENTICAL to the unquantized
    megakernel — only the weight streams shrink.  Tiles resolve under the
    ``dyad_ff_fused[_swiglu]_w8`` op keys (dtype field = payload dtype)."""
    gated = _check_ff_act(act, wg1, wg2)
    if gated and (sg1 is None or sg2 is None):
        raise ValueError("sg1/sg2 must be passed when act='swiglu'")
    ups = (wg1, wg2, wu1, wu2) if gated else (wu1, wu2)
    s_ups = (sg1, sg2, su1, su2) if gated else (su1, su2)
    op = "dyad_ff_fused_swiglu_w8" if gated else "dyad_ff_fused_w8"
    return _run_ff(op, x1, x2, ups, (wd1, wd2), s_ups, (sd1, sd2), act,
                   (block_b, block_o, block_k, block_j),
                   key_dtype=wu1.dtype, interpret=interpret)
