"""The grouped DYAD kernels (``kernels/dyad_gmm.py``, in the Pallas
interpreter) against the einsum oracle: forward, dgrad and wgrad over rows
sorted by expert, with empty groups, every row in one expert, and group
ends that split a row tile."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dyad_gmm, ops, ref

BM = 8          # rows per tile
N, DI, DO = 2, 16, 8

CASES = {
    "empty_groups": [5, 0, 13, 0],
    "one_expert": [0, 0, 24, 0],
    "ends_split_tiles": [3, 11, 9, 1],
    "whole_tiles": [8, 16, 0, 8],
}


def _layout(sizes):
    sizes = np.asarray(sizes, np.int32)
    E = len(sizes)
    starts = dyad_gmm.group_starts(sizes, BM)
    ends = starts[:E] + sizes
    M = dyad_gmm.buffer_rows(int(sizes.sum()), E, BM)
    group = np.full(M, -1)
    for e in range(E):
        group[int(starts[e]):int(ends[e])] = e
    return starts, ends, M, group


def _data(E, M, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((M, N * DI)), jnp.float32)
    w1, w2 = (jnp.asarray(rng.standard_normal((E, N, DO, DI)), jnp.float32)
              for _ in range(2))
    return x, w1, w2


def _oracle(x, w1, w2, group):
    """Each row through its own expert's DYAD-IT einsums; zero outside every
    group."""
    out = []
    for r, e in enumerate(group):
        y = ref.dyad_mm_ref(x[r:r + 1], w1[max(e, 0)], w2[max(e, 0)],
                            variant="it")[0]
        out.append(y if e >= 0 else jnp.zeros_like(y))
    return jnp.stack(out)


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_dgrad_wgrad_match_einsums(case):
    sizes = CASES[case]
    starts, ends, M, group = _layout(sizes)
    x, w1, w2 = _data(len(sizes), M)
    live = jnp.asarray(group >= 0)[:, None]
    g = jnp.asarray(np.random.default_rng(1).standard_normal(
        (M, N * DO)), jnp.float32)

    def program(x, w1, w2):
        y = ops.dyad_gmm_it(x, w1, w2, starts, ends, row_tile=BM)
        # rows outside every group are undefined: read only the groups'
        return jnp.where(live, y, 0.0)

    with jax.default_matmul_precision("highest"):
        y1, vjp1 = jax.vjp(program, x, w1, w2)
        y0, vjp0 = jax.vjp(lambda *a: _oracle(*a, group), x, w1, w2)
        d1, d0 = vjp1(g), vjp0(g)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(jnp.where(live, d1[0], 0), d0[0], rtol=1e-5,
                               atol=1e-5)
    for a, b in zip(d1[1:], d0[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)
    # an empty group's weights get a zero gradient, not what its block held
    for e, s in enumerate(sizes):
        if s == 0:
            assert not np.any(np.asarray(d1[1][e]))


def test_padding_rows_take_no_part_in_wgrad():
    """Rows past a group's end inside its last tile hold whatever the
    buffer held (here NaN): the weight gradient masks them out."""
    sizes = [3, 5]
    starts, ends, M, group = _layout(sizes)
    x, w1, w2 = _data(2, M, seed=3)
    pad = jnp.asarray(group < 0)[:, None]
    x = jnp.where(pad, jnp.nan, x)
    x1, x2 = (jnp.swapaxes(v, 0, 1) for v in ref.block_views(x, N, "it"))
    z = jnp.where(pad, jnp.nan, jnp.ones((M, N * DO)))
    z = jnp.swapaxes(z.reshape(M, N, DO), 0, 1)
    dw1, dw2 = dyad_gmm.gmm_wgrad(x1, x2, z, starts, ends, row_tile=BM,
                                  interpret=True)
    assert np.isfinite(np.asarray(dw1)).all()
    assert np.isfinite(np.asarray(dw2)).all()


def test_group_starts_are_tile_aligned_and_the_buffer_holds_any_split():
    starts = np.asarray(dyad_gmm.group_starts([3, 0, 9, 8], BM))
    assert starts.tolist() == [0, 8, 8, 24, 32]
    rows = dyad_gmm.buffer_rows(20, 4, BM)
    for split in ([20, 0, 0, 0], [1, 1, 1, 17], [5, 5, 5, 5]):
        assert int(dyad_gmm.group_starts(split, BM)[-1]) <= rows
    assert rows % BM == 0


def test_rows_off_whole_tiles_are_refused():
    x, w1, w2 = _data(2, 12)
    with pytest.raises(ValueError, match="12 rows"):
        ops.dyad_gmm_it(x, w1, w2, jnp.zeros(3, jnp.int32),
                        jnp.zeros(2, jnp.int32), row_tile=BM)
