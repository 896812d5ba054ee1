"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps, fwd + bwd, in
interpret mode (executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dyad
from repro.kernels import ops, ref
from repro.kernels.dyad_mm import (dyad_mm_blocks, dyad_mm_blocks_two,
                                   dyad_mm_dgrad, dyad_mm_dgrad_two,
                                   dyad_mm_wgrad, plan_tiles)

KEY = jax.random.PRNGKey(0)


def _assert_within_dot_bound(got, want, pairs):
    """Elementwise ``|got - want| <= 2 * gamma_n * sum |a_i * b_i|``.

    Summing ``n`` fp32 products in ANY order errs by at most
    ``gamma_n = n*u / (1 - n*u)`` (u = 2**-24, the fp32 unit roundoff)
    times the sum of the products' magnitudes (Higham, "Accuracy and
    Stability of Numerical Algorithms", sec. 3.1).  The kernel and the
    einsum reference each sum in their own order, hence the factor 2.
    ``pairs`` lists the ``(einsum spec, a, b)`` contractions ``want`` adds
    up; ``n`` is their total contraction length.  A padding or indexing
    bug errs by O(|value|), far above this bound."""
    u = np.finfo(np.float32).eps / 2
    n = sum(np.asarray(a).shape[-1] for _, a, _ in pairs)
    gamma = n * u / (1 - n * u)
    mag = sum(np.einsum(spec, np.abs(np.asarray(a, np.float64)),
                        np.abs(np.asarray(b, np.float64)))
              for spec, a, b in pairs)
    err = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    excess = err - 2 * gamma * mag
    assert np.all(excess <= 0), f"max excess over bound {excess.max():.3g}"

SHAPES = [
    # (f_in, f_out, n_dyad, batch)
    (16, 16, 4, 8),
    (32, 64, 4, 16),
    (24, 32, 4, 6),
    (64, 32, 8, 5),
    (12, 20, 2, 3),
    (128, 128, 4, 32),
]


@pytest.mark.parametrize("variant", ["it", "ot", "dt"])
@pytest.mark.parametrize("f_in,f_out,n,B", SHAPES)
def test_kernel_matches_ref(variant, f_in, f_out, n, B):
    spec = dyad.DyadSpec(n_dyad=n, variant=variant)
    p = dyad.init(KEY, f_in, f_out, spec, bias=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, f_in))
    y_ref = ref.dyad_mm_ref(x, p["w1"], p["w2"], variant=variant)
    y_ker = ops.dyad_mm(x, p["w1"], p["w2"], variant=variant)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_kernel_dtypes(dtype, tol):
    spec = dyad.DyadSpec(n_dyad=4)
    p = dyad.init(KEY, 32, 32, spec, bias=False, dtype=jnp.float32)
    x = jax.random.normal(KEY, (8, 32)).astype(dtype)
    y_ref = ref.dyad_mm_ref(x, p["w1"], p["w2"], variant="it")
    y_ker = ops.dyad_mm(x, p["w1"], p["w2"], variant="it")
    assert y_ker.dtype == dtype
    np.testing.assert_allclose(np.asarray(y_ker, np.float32),
                               np.asarray(y_ref, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("variant", ["it", "ot", "dt"])
def test_kernel_gradients(variant):
    spec = dyad.DyadSpec(n_dyad=4, variant=variant)
    p = dyad.init(KEY, 16, 24, spec, bias=False)
    x = jax.random.normal(KEY, (6, 16))
    f_r = lambda x, w1, w2: (ref.dyad_mm_ref(x, w1, w2, variant=variant) ** 2).sum()
    f_k = lambda x, w1, w2: (ops.dyad_mm(x, w1, w2, variant=variant) ** 2).sum()
    gr = jax.grad(f_r, argnums=(0, 1, 2))(x, p["w1"], p["w2"])
    gk = jax.grad(f_k, argnums=(0, 1, 2))(x, p["w1"], p["w2"])
    for a, b in zip(gr, gk):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4,
                                   atol=2e-4)


def test_kernel_block_tilings():
    """Sweep BlockSpec tilings: result must be invariant to tiling choice.
    Feature dims span two lane tiles so every legal tiling (multiples of
    8 rows / 128 lanes) of each axis is exercised."""
    x1 = jax.random.normal(KEY, (16, 4, 256))
    x2 = jax.random.normal(jax.random.PRNGKey(1), (16, 4, 256))
    w1 = jax.random.normal(jax.random.PRNGKey(2), (4, 256, 256))
    w2 = jax.random.normal(jax.random.PRNGKey(3), (4, 256, 256))
    # tilings differ only in fp32 summation order over k
    pairs = [("bgk,gok->bgo", x1, w1), ("bgk,gok->bgo", x2, w2)]
    base = dyad_mm_blocks(x1, x2, w1, w2, interpret=True)
    for bb, bo, bk in [(8, 128, 128), (16, 256, 256), (8, 128, 256),
                       (16, 128, 128)]:
        out = dyad_mm_blocks(x1, x2, w1, w2, block_b=bb, block_o=bo,
                             block_k=bk, interpret=True)
        _assert_within_dot_bound(out, base, pairs)
    z1, z2 = dyad_mm_blocks_two(x1, x2, w1, w2, block_b=8, block_o=128,
                                block_k=128, interpret=True)
    _assert_within_dot_bound(z1 + z2, base, pairs)


@pytest.mark.parametrize("B,n,d_in,d_out", [
    (10, 2, 33, 17),          # odd k, prime o
    (13, 3, 7, 5),            # everything prime
    (64, 2, 129, 130),        # just-past-128 feature dims
])
def test_kernel_degenerate_dims_exact(B, n, d_in, d_out):
    """Prime/odd dims used to collapse _largest_divisor to 1-wide tiles
    (catastrophic grid); the tile planner now pads instead — results must
    stay exact (zero padding contributes zero products)."""
    x1 = jax.random.normal(KEY, (B, n, d_in))
    x2 = jax.random.normal(jax.random.PRNGKey(1), (B, n, d_in))
    w1 = jax.random.normal(jax.random.PRNGKey(2), (n, d_out, d_in))
    w2 = jax.random.normal(jax.random.PRNGKey(3), (n, d_out, d_in))
    pairs = [("bgk,gok->bgo", x1, w1), ("bgk,gok->bgo", x2, w2)]
    want = sum(jnp.einsum(s, a, b) for s, a, b in pairs)
    got = dyad_mm_blocks(x1, x2, w1, w2, interpret=True)
    _assert_within_dot_bound(got, want, pairs)
    z1, z2 = dyad_mm_blocks_two(x1, x2, w1, w2, interpret=True)
    _assert_within_dot_bound(z1 + z2, want, pairs)


def test_plan_tiles_never_degenerate():
    """Tiles stay at lane/sublane granularity even for prime dims > block,
    and the grid never explodes to per-element steps."""
    plan = plan_tiles(521, 1031, 1031, 256, 256, 512)   # all prime
    # Mosaic-legal tiles (multiples of 8 sublanes / 128 lanes), padding
    # short of one unit, and a grid of tiles, not of elements
    for dim, padded, tile, unit in [(521, plan.padded_b, plan.bB, 8),
                                    (1031, plan.padded_o, plan.bO, 128),
                                    (1031, plan.padded_k, plan.bK, 128)]:
        assert tile % unit == 0 and padded % tile == 0
        assert 0 <= padded - dim < unit
    assert (plan.bB, plan.bO, plan.bK) == (176, 128, 384)
    assert plan.grid_steps == 3 * 9 * 3
    # healthy dims are untouched: no padding, exact divisors; a lane tile
    # is a multiple of 128 (192 would divide 384 but Mosaic refuses it)
    plan = plan_tiles(64, 384, 512, 256, 256, 512)
    assert (plan.padded_b, plan.padded_o, plan.padded_k) == (64, 384, 512)
    assert (plan.bB, plan.bO, plan.bK) == (64, 128, 512)
    # an axis within its block is one whole-axis tile, legal at any size
    plan = plan_tiles(13, 192, 129, 256, 256, 512)
    assert (plan.bB, plan.bO, plan.bK) == (13, 192, 129)
    assert (plan.padded_b, plan.padded_o, plan.padded_k) == (13, 192, 129)


def test_kernel_multi_dim_leading():
    """ops.dyad_mm flattens arbitrary leading dims."""
    spec = dyad.DyadSpec(n_dyad=4, variant="it", use_kernel=True)
    p = dyad.init(KEY, 16, 16, spec, bias=True)
    x = jax.random.normal(KEY, (2, 3, 5, 16))
    y = dyad.apply(p, x, spec)
    y_ref = dyad.apply(p, x, dyad.DyadSpec(n_dyad=4, variant="it"))
    assert y.shape == (2, 3, 5, 16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-5,
                               atol=2e-5)


# -- fused backward kernels ---------------------------------------------------


BWD_SHAPES = [
    # (B, n, d_in, d_out): healthy, odd/prime (exercising plan_tiles
    # padding), and just-past-lane dims
    (16, 4, 32, 24),
    (10, 2, 33, 17),
    (13, 3, 7, 5),
    (64, 2, 129, 130),
]


@pytest.mark.parametrize("B,n,d_in,d_out", BWD_SHAPES)
def test_dgrad_kernels_match_einsum(B, n, d_in, d_out):
    z1 = jax.random.normal(KEY, (B, n, d_out))
    z2 = jax.random.normal(jax.random.PRNGKey(1), (B, n, d_out))
    w1 = jax.random.normal(jax.random.PRNGKey(2), (n, d_out, d_in))
    w2 = jax.random.normal(jax.random.PRNGKey(3), (n, d_out, d_in))
    pairs = [("bgo,goi->bgi", z1, w1), ("bgo,goi->bgi", z2, w2)]
    want = sum(jnp.einsum(s, a, b) for s, a, b in pairs)
    got = dyad_mm_dgrad(z1, z2, w1, w2, interpret=True)
    _assert_within_dot_bound(got, want, pairs)
    d1, d2 = dyad_mm_dgrad_two(z1, z2, w1, w2, interpret=True)
    _assert_within_dot_bound(d1 + d2, want, pairs)


@pytest.mark.parametrize("B,n,d_in,d_out", BWD_SHAPES)
def test_wgrad_kernel_matches_einsum(B, n, d_in, d_out):
    x1 = jax.random.normal(KEY, (B, n, d_in))
    x2 = jax.random.normal(jax.random.PRNGKey(1), (B, n, d_in))
    z1 = jax.random.normal(jax.random.PRNGKey(2), (B, n, d_out))
    z2 = jax.random.normal(jax.random.PRNGKey(3), (B, n, d_out))
    dw1, dw2 = dyad_mm_wgrad(x1, x2, z1, z2, interpret=True)
    np.testing.assert_allclose(
        np.asarray(dw1), np.asarray(jnp.einsum("bgi,bgo->goi", x1, z1)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(dw2), np.asarray(jnp.einsum("bgi,bgo->goi", x2, z2)),
        rtol=1e-5, atol=1e-5)


def test_wgrad_out_dtype_fp32_accumulation():
    """bf16 inputs accumulate in fp32 and cast ONCE at the end — dw in the
    requested out_dtype must match the fp32 reference to fp32-ish
    tolerance, far tighter than a bf16-accumulated product chain."""
    B, n, d_in, d_out = 64, 2, 32, 32
    x1 = jax.random.normal(KEY, (B, n, d_in))
    z1 = jax.random.normal(jax.random.PRNGKey(1), (B, n, d_out))
    want = jnp.einsum("bgi,bgo->goi", x1, z1)
    dw1, _ = dyad_mm_wgrad(x1.astype(jnp.bfloat16), x1.astype(jnp.bfloat16),
                           z1.astype(jnp.bfloat16), z1.astype(jnp.bfloat16),
                           out_dtype=jnp.float32, interpret=True)
    assert dw1.dtype == jnp.float32
    # the only error is the bf16 INPUT rounding, not accumulation ordering
    np.testing.assert_allclose(np.asarray(dw1), np.asarray(want),
                               rtol=5e-2, atol=1e-1)


def _grad_pair(variant, dtype, f_in=16, f_out=24, B=6, use_kernel_bwd=True):
    spec = dyad.DyadSpec(n_dyad=4, variant=variant)
    p = dyad.init(KEY, f_in, f_out, spec, bias=False)
    x = jax.random.normal(KEY, (B, f_in)).astype(dtype)
    f_k = lambda x, w1, w2: (ops.dyad_mm(
        x, w1, w2, variant=variant, use_kernel_bwd=use_kernel_bwd) ** 2).sum()
    f_e = lambda x, w1, w2: (ops.dyad_mm(
        x, w1, w2, variant=variant, use_kernel_bwd=False) ** 2).sum()
    gk = jax.grad(f_k, argnums=(0, 1, 2))(x, p["w1"], p["w2"])
    ge = jax.grad(f_e, argnums=(0, 1, 2))(x, p["w1"], p["w2"])
    return gk, ge


@pytest.mark.parametrize("variant", ["it", "ot", "dt"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 5e-2)])
def test_kernel_bwd_matches_einsum_oracle(variant, dtype, tol):
    """use_kernel_bwd=True (default route) vs the einsum-VJP oracle."""
    gk, ge = _grad_pair(variant, dtype)
    for a, b in zip(gk, ge):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("variant", ["it", "ot", "dt"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 5e-2)])
def test_pallas_bwd_matches_einsum_oracle(variant, dtype, tol, monkeypatch):
    """REPRO_KERNEL_BWD=pallas forces the true dgrad/wgrad kernels through
    the VJP off-TPU (interpret mode) — still oracle-exact."""
    monkeypatch.setenv("REPRO_KERNEL_BWD", "pallas")
    gk, ge = _grad_pair(variant, dtype)
    for a, b in zip(gk, ge):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("variant", ["it", "ot", "dt"])
@pytest.mark.parametrize("f_in,f_out,B", [(33, 21, 10), (35, 25, 13)])
def test_pallas_bwd_odd_dims_exact(variant, f_in, f_out, B, monkeypatch):
    """Odd/prime per-block dims route the bwd kernels through plan_tiles
    zero-padding — gradients stay exact (padding contributes nothing)."""
    monkeypatch.setenv("REPRO_KERNEL_BWD", "pallas")
    spec = dyad.DyadSpec(n_dyad=1, variant=variant)
    p = dyad.init(KEY, f_in, f_out, spec, bias=False)
    x = jax.random.normal(KEY, (B, f_in))
    f_k = lambda x, w1, w2: (ops.dyad_mm(x, w1, w2, variant=variant) ** 2).sum()
    f_e = lambda x, w1, w2: (ops.dyad_mm(x, w1, w2, variant=variant,
                                         use_kernel_bwd=False) ** 2).sum()
    gk = jax.grad(f_k, argnums=(0, 1, 2))(x, p["w1"], p["w2"])
    ge = jax.grad(f_e, argnums=(0, 1, 2))(x, p["w1"], p["w2"])
    for a, b in zip(gk, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("route", ["xla", "pallas"])
def test_bwd_mixed_weight_dtypes(route, monkeypatch):
    """dw* cotangents must come back in each weight's OWN dtype on every
    route (custom_vjp enforces primal/cotangent aval agreement)."""
    monkeypatch.setenv("REPRO_KERNEL_BWD", route)
    x = jax.random.normal(KEY, (6, 16))
    spec = dyad.DyadSpec(n_dyad=4)
    p = dyad.init(KEY, 16, 24, spec, bias=False)
    w1, w2 = p["w1"], p["w2"].astype(jnp.bfloat16)
    g = jax.grad(lambda x, w1, w2: (ops.dyad_mm(x, w1, w2) ** 2).sum(),
                 argnums=(1, 2))(x, w1, w2)
    assert g[0].dtype == jnp.float32 and g[1].dtype == jnp.bfloat16


def test_grad_through_full_dyad_ff_block():
    """End-to-end jax.grad through a DYAD up/relu/down ff block: the
    kernel-routed spec (fwd + fused bwd) must match the plain jnp spec."""
    spec_k = dyad.DyadSpec(n_dyad=4, variant="it", use_kernel=True)
    spec_j = dyad.DyadSpec(n_dyad=4, variant="it")
    p = {"up": dyad.init(KEY, 16, 32, spec_k),
         "down": dyad.init(jax.random.PRNGKey(1), 32, 16, spec_k)}
    x = jax.random.normal(KEY, (8, 16))

    def loss(p, x, spec):
        h = jax.nn.relu(dyad.apply(p["up"], x, spec))
        return (dyad.apply(p["down"], h, spec) ** 2).mean()

    gk = jax.jit(jax.grad(lambda p, x: loss(p, x, spec_k)))(p, x)
    gj = jax.jit(jax.grad(lambda p, x: loss(p, x, spec_j)))(p, x)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gj)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
