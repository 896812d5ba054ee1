"""Grouped DYAD matmul Pallas TPU kernels: the expert products of an expert
layer that holds a share of the experts (``layers.moe.apply_moe_share``).

The rows of every kernel here are token copies sorted by the expert they
were routed to.  Group ``e`` (expert ``e`` of the share) holds rows
``starts[e] .. ends[e]``; each group starts on a row tile (``starts`` are
multiples of ``bM``, ascending), so every row tile belongs to one group.
Rows past a group's end up to the next group's start are padding, and rows
from ``starts[E]`` (the aligned end of the last group) on are not computed
at all: those grid steps clamp their index maps onto the last live tile
(no DMA is issued for a revisited block) and skip their compute.  So the
work follows the rows routed to the share, not the buffer's static size,
which has to hold the worst case of a dropless layer.  ``starts`` and
``ends`` are scalar-prefetched; a tile finds its group by counting the
starts at or before its first row, so empty groups need no special case.

* :func:`gmm` — forward, DYAD-IT over each row's own expert:
  ``z[g, r] = x1[g, r]·w1[e, g]ᵀ + x2[g, r]·w2[e, g]ᵀ``;
* :func:`gmm_dgrad` — both input cotangents over the same weights,
  ``dx1[g, r] = z[g, r]·w1[e, g]`` and ``dx2 = z·w2[e, g]`` (IT's two
  components un-view differently, so they stay apart);
* :func:`gmm_wgrad` — ``dw1[e, g] = Σ_r z[g, r]ᵀ x1[g, r]`` and ``dw2``
  likewise over the rows of group ``e``, accumulated in fp32 in the
  output block, which stays resident across the group's tiles.  Padding
  rows are masked out here, and an empty group's gradient is zero.

Rows outside every group are left undefined by :func:`gmm` and
:func:`gmm_dgrad`; a caller reads only its groups' rows.

Layout: activations stream block-major ``(n, M, d)`` as in
:mod:`repro.kernels.dyad_mm`, weights as ``(E, n, d_out, d_in)``.  The
feature axes are not tiled (the grid is ``(n, M / bM)``): an expert's
block widths fit VMEM whole (Moonlight's 352 x 512 DYAD blocks).

A device trace names these calls after their jitted wrappers,
``_gmm_impl`` and ``_gmm_wgrad_impl``, which neither DYAD reader's
prefixes (``_mm_impl``, ``_wgrad_impl``) match
(tests/test_kernel_names.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows per tile, and so the alignment of every group's first row
ROW_TILE = 256

_SQ = pl.Squeezed()
_FWD_DN = (((1,), (1,)), ((), ()))      # (bM, K) x (O, K) -> (bM, O)
_DGRAD_DN = (((1,), (0,)), ((), ()))    # (bM, O) x (O, K) -> (bM, K)
_WGRAD_DN = (((0,), (0,)), ((), ()))    # (bM, O)ᵀ x (bM, K) -> (O, K)


def group_starts(sizes, row_tile: int = ROW_TILE):
    """``(E + 1,)`` int32: the first row of each group when every group
    starts on a row tile, and the aligned end of the last one."""
    sizes = jnp.asarray(sizes, jnp.int32)
    padded = (sizes + row_tile - 1) // row_tile * row_tile
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(padded).astype(jnp.int32)])


def buffer_rows(max_rows: int, n_groups: int,
                row_tile: int = ROW_TILE) -> int:
    """Static rows that hold ``max_rows`` routed rows in ``n_groups``
    tile-aligned groups, whatever their sizes."""
    rows = max_rows + n_groups * (row_tile - 1)
    return -(-rows // row_tile) * row_tile


def _group_of(row, starts_ref, n_groups: int):
    """The group holding ``row``: how many groups after the first start at
    or before it (an empty group shares its start with the next one)."""
    e = jnp.int32(0)
    for j in range(1, n_groups):
        e = e + (starts_ref[j] <= row).astype(jnp.int32)
    return e


def _live_tile(m, starts_ref, n_groups: int, bM: int):
    """(``m`` clamped onto the last live tile, whether ``m`` is live)."""
    n_live = starts_ref[n_groups] // bM
    return (jnp.minimum(m, jnp.maximum(n_live - 1, 0)), m < n_live)


def _row_maps(n_groups: int, bM: int):
    """Index maps of the ``(g, m)`` grid for an activation block and for a
    weight block of the tile's group."""

    def act(g, m, starts_ref, ends_ref):
        return (g, _live_tile(m, starts_ref, n_groups, bM)[0], 0)

    def weight(g, m, starts_ref, ends_ref):
        mm = _live_tile(m, starts_ref, n_groups, bM)[0]
        return (_group_of(mm * bM, starts_ref, n_groups), g, 0, 0)

    return act, weight


# -- forward and dgrad --------------------------------------------------------


def _gmm_kernel(starts_ref, ends_ref, *refs, pairs, n_x: int, n_out: int,
                n_groups: int, bM: int, dn):
    """For each ``(x, w, out)`` in ``pairs``: out += x·w (``dn``), on live
    tiles only."""
    xs, ws = refs[:n_x], refs[n_x:n_x + 2]
    outs = refs[n_x + 2:n_x + 2 + n_out]
    live = _live_tile(pl.program_id(1), starts_ref, n_groups, bM)[1]

    @pl.when(live)
    def _compute():
        parts = [None] * n_out
        for xi, wi, oi in pairs:
            x = xs[xi][...]
            p = jax.lax.dot_general(x, ws[wi][...].astype(x.dtype), dn,
                                    preferred_element_type=jnp.float32)
            parts[oi] = p if parts[oi] is None else parts[oi] + p
        for out, p in zip(outs, parts):
            out[...] = p.astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("bM", "dgrad", "interpret"))
def _gmm_impl(xs, w1, w2, starts, ends, *, bM: int, dgrad: bool,
              interpret: bool):
    """Forward (``xs = (x1, x2)``, one output) or dgrad (``xs = (z,)``, two
    outputs) over block-major ``(n, M, d)`` rows and ``(E, n, O, K)``
    weights."""
    n, M, _ = xs[0].shape
    E, _, O, K = w1.shape
    width = K if dgrad else O
    act, weight = _row_maps(E, bM)
    x_specs = [pl.BlockSpec((_SQ, bM, x.shape[2]), act) for x in xs]
    w_spec = pl.BlockSpec((_SQ, _SQ, O, K), weight)
    o_spec = pl.BlockSpec((_SQ, bM, width), act)
    if dgrad:
        pairs, n_out = ((0, 0, 0), (0, 1, 1)), 2
    else:
        pairs, n_out = ((0, 0, 0), (1, 1, 0)), 1
    body = functools.partial(_gmm_kernel, pairs=pairs, n_x=len(xs),
                             n_out=n_out, n_groups=E, bM=bM,
                             dn=_DGRAD_DN if dgrad else _FWD_DN)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, M // bM),
            in_specs=x_specs + [w_spec, w_spec],
            out_specs=[o_spec] * n_out,
        ),
        out_shape=[jax.ShapeDtypeStruct((n, M, width), xs[0].dtype)] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(starts, ends, *xs, w1, w2)


def _check_rows(M: int, bM: int) -> None:
    if M % bM:
        raise ValueError(f"{M} rows are not a whole number of {bM}-row "
                         "tiles")


def gmm(x1, x2, w1, w2, starts, ends, *, row_tile: int = ROW_TILE,
        interpret: bool = False):
    """Grouped DYAD-IT forward.  ``x1``, ``x2``: block-major ``(n, M, d_in)``
    views of the sorted rows; ``w1``, ``w2``: ``(E, n, d_out, d_in)``;
    ``starts`` ``(E + 1,)`` (:func:`group_starts`) and ``ends`` ``(E,)``
    int32.  Returns ``(n, M, d_out)`` in x1's dtype."""
    _check_rows(x1.shape[1], row_tile)
    return _gmm_impl((x1, x2), w1, w2, starts, ends, bM=row_tile,
                     dgrad=False, interpret=interpret)[0]


def gmm_dgrad(z, w1, w2, starts, ends, *, row_tile: int = ROW_TILE,
              interpret: bool = False):
    """Grouped DYAD-IT input cotangents: ``z`` ``(n, M, d_out)`` ->
    ``(dx1, dx2)``, each ``(n, M, d_in)`` in z's dtype."""
    _check_rows(z.shape[1], row_tile)
    dx1, dx2 = _gmm_impl((z,), w1, w2, starts, ends, bM=row_tile,
                         dgrad=True, interpret=interpret)
    return dx1, dx2


# -- wgrad --------------------------------------------------------------------


def _gmm_wgrad_kernel(starts_ref, ends_ref, x1_ref, x2_ref, z_ref, o1_ref,
                      o2_ref, *, n_groups: int, bM: int):
    m = pl.program_id(1)
    live = _live_tile(m, starts_ref, n_groups, bM)[1]
    row = m * bM
    e = _group_of(row, starts_ref, n_groups)

    @pl.when(live)
    def _compute():
        # padding rows past the group's end take no part
        keep = (jax.lax.broadcasted_iota(jnp.int32, (bM, 1), 0)
                < ends_ref[e] - row)
        z = jnp.where(keep, z_ref[...], 0)
        for x_ref, o_ref in ((x1_ref, o1_ref), (x2_ref, o2_ref)):
            x = jnp.where(keep, x_ref[...], 0)
            part = jax.lax.dot_general(z, x, _WGRAD_DN,
                                       preferred_element_type=jnp.float32)

            @pl.when(row == starts_ref[e])
            def _first():
                o_ref[...] = part

            @pl.when(row != starts_ref[e])
            def _more():
                o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("n_groups", "bM", "interpret"))
def _gmm_wgrad_impl(x1, x2, z, starts, ends, *, n_groups: int, bM: int,
                    interpret: bool):
    """(dw1, dw2), fp32 ``(E, n, d_out, d_in)``, from block-major rows."""
    n, M, K = x1.shape
    O = z.shape[2]
    act, weight = _row_maps(n_groups, bM)
    body = functools.partial(_gmm_wgrad_kernel, n_groups=n_groups, bM=bM)
    w_spec = pl.BlockSpec((_SQ, _SQ, O, K), weight)
    out_sds = jax.ShapeDtypeStruct((n_groups, n, O, K), jnp.float32)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, M // bM),
            in_specs=[pl.BlockSpec((_SQ, bM, K), act),
                      pl.BlockSpec((_SQ, bM, K), act),
                      pl.BlockSpec((_SQ, bM, O), act)],
            out_specs=[w_spec, w_spec],
        ),
        out_shape=[out_sds, out_sds],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(starts, ends, x1, x2, z)


def gmm_wgrad(x1, x2, z, starts, ends, *, row_tile: int = ROW_TILE,
              interpret: bool = False):
    """Grouped DYAD-IT weight cotangents ``(dw1, dw2)``, fp32
    ``(E, n, d_out, d_in)``, over each group's rows; zero for an empty
    group."""
    _check_rows(x1.shape[1], row_tile)
    E = ends.shape[0]
    dw1, dw2 = _gmm_wgrad_impl(x1, x2, z, starts, ends, n_groups=E,
                               bM=row_tile, interpret=interpret)
    held = (ends > starts[:E])[:, None, None, None]
    return jnp.where(held, dw1, 0.0), jnp.where(held, dw2, 0.0)
