"""The ff megakernel (up → act → down in one Pallas grid) vs the split
kernel chain vs the einsum oracle: forward, both backward routes, dispatch
from the mlp layer, and the 4-axis tile planner — all in interpret mode."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import factory
from repro.kernels import ops, ref
from repro.kernels.dyad_mm import dyad_ff_fused, plan_ff_tiles
from repro.layers import mlp as mlp_lib

KEY = jax.random.PRNGKey(0)

# (B, n, d_in, d_ff_b, d_out): healthy, odd/prime hidden (exercising
# plan_ff_tiles padding on the j axis), prime-everything, just-past-lane
FF_SHAPES = [
    (16, 4, 32, 64, 32),
    (10, 2, 24, 37, 24),
    (8, 3, 7, 5, 11),
    (12, 2, 129, 130, 129),
]


def _ff_weights(n, d_in, d_ff_b, d_out, dtype=jnp.float32, gated=False):
    def w(i, shape):
        return jax.random.normal(jax.random.fold_in(KEY, i), shape, dtype)

    ws = {"wu1": w(1, (n, d_ff_b, d_in)), "wu2": w(2, (n, d_ff_b, d_in)),
          "wd1": w(3, (n, d_out, d_ff_b)), "wd2": w(4, (n, d_out, d_ff_b))}
    if gated:
        ws["wg1"] = w(5, (n, d_ff_b, d_in))
        ws["wg2"] = w(6, (n, d_ff_b, d_in))
    return ws


def _close(got, want, tol):
    """allclose with atol scaled to the reference magnitude — ff outputs
    grow with sqrt(d_in * d_ff), so a flat atol misreads bf16 rounding on
    near-zero elements as error."""
    want = np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol * scale)


def _params(ws):
    p = {"up": {"w1": ws["wu1"], "w2": ws["wu2"]},
         "down": {"w1": ws["wd1"], "w2": ws["wd2"]}}
    if "wg1" in ws:
        p["gate"] = {"w1": ws["wg1"], "w2": ws["wg2"]}
    return p


@pytest.mark.parametrize("act", ["gelu", "relu", "silu", "swiglu"])
@pytest.mark.parametrize("B,n,d_in,d_ff_b,d_out", FF_SHAPES)
def test_megakernel_matches_oracle(act, B, n, d_in, d_ff_b, d_out):
    gated = act == "swiglu"
    ws = _ff_weights(n, d_in, d_ff_b, d_out, gated=gated)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, n * d_in))
    x1, x2 = ref.block_views(x, n, "it")
    want = ref.dyad_ff_ref(x, ws["wu1"], ws["wu2"], ws["wd1"], ws["wd2"],
                           ws.get("wg1"), ws.get("wg2"), act=act)
    z1, z2 = dyad_ff_fused(x1, x2, ws["wu1"], ws["wu2"], ws["wd1"],
                           ws["wd2"], wg1=ws.get("wg1"), wg2=ws.get("wg2"),
                           act=act, interpret=True)
    got = ref.combine(z1, z2, "ot")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("act", ["gelu", "swiglu"])
def test_megakernel_dtypes(act, dtype, tol):
    """bf16 activations: the megakernel keeps the hidden in fp32 until the
    down dot's input cast, so it can only be MORE accurate than the split
    path — compare against the fp32 oracle at bf16 tolerance."""
    gated = act == "swiglu"
    ws = _ff_weights(4, 32, 64, 32, gated=gated)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 128)).astype(dtype)
    y = ops.dyad_ff(_params(ws), x, act=act)
    assert y.dtype == dtype
    want = ref.dyad_ff_ref(x.astype(jnp.float32), ws["wu1"], ws["wu2"],
                           ws["wd1"], ws["wd2"], ws.get("wg1"),
                           ws.get("wg2"), act=act)
    _close(y, want, tol)


def test_megakernel_tiling_invariance():
    """Result must not depend on the tile choice (sweeps j and k blocks,
    the two axes the megakernel sequences).  The hidden and input widths
    span two lane tiles so each has two legal tilings."""
    ws = _ff_weights(2, 256, 256, 128)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 512))
    x1, x2 = ref.block_views(x, 2, "it")
    args = (x1, x2, ws["wu1"], ws["wu2"], ws["wd1"], ws["wd2"])
    base = ref.combine(*dyad_ff_fused(*args, act="gelu", interpret=True),
                       "ot")
    for bb, bo, bj, bk in [(8, 128, 128, 128), (16, 128, 256, 256),
                           (8, 128, 128, 256), (16, 128, 256, 128)]:
        out = ref.combine(*dyad_ff_fused(
            *args, act="gelu", block_b=bb, block_o=bo, block_j=bj,
            block_k=bk, interpret=True), "ot")
        # fp32 accumulation ORDER differs per tiling across two chained
        # matmuls — compare at fp32-chain tolerance, not bit-exactness
        _close(out, base, 1e-5)


@pytest.mark.parametrize("act", ["relu", "swiglu"])
def test_fused_vs_split_route(act, monkeypatch):
    """REPRO_KERNEL_FF=split runs the two/three-dispatch kernel chain —
    same numbers as the megakernel route to fp32 tolerance."""
    gated = act == "swiglu"
    ws = _ff_weights(4, 16, 32, 16, gated=gated)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    p = _params(ws)

    monkeypatch.setenv("REPRO_KERNEL_FF", "fused")
    ops._make_dyad_ff.cache_clear()
    y_fused = ops.dyad_ff(p, x, act=act)
    monkeypatch.setenv("REPRO_KERNEL_FF", "split")
    ops._make_dyad_ff.cache_clear()
    y_split = ops.dyad_ff(p, x, act=act)
    ops._make_dyad_ff.cache_clear()
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_split),
                               rtol=2e-5, atol=2e-5)


# bf16 gradients: the kernel route and the einsum oracle each round about
# six intermediates to bf16 at different points (pre-activation, hidden,
# output cotangent, dh, du, dx); each rounding moves a value by at most the
# bf16 unit roundoff u = 2**-8 relative to the reference scale, so the two
# routes may differ by up to 2 * 6 * u (about 4.7e-2) of that scale.
_BF16_FF_GRAD_TOL = 2 * 6 * 2.0 ** -8


@pytest.mark.parametrize("act", ["gelu", "relu", "swiglu"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, _BF16_FF_GRAD_TOL)])
def test_ff_bwd_matches_einsum_oracle(act, dtype, tol):
    """Default backward route (compiled direct-layout XLA off-TPU) vs
    autodiff of the einsum oracle."""
    gated = act == "swiglu"
    ws = _ff_weights(4, 16, 32, 16, gated=gated)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64)).astype(dtype)
    p = _params(ws)
    f_k = lambda p, x: (ops.dyad_ff(p, x, act=act) ** 2).mean()
    f_e = lambda p, x: (ops.dyad_ff(p, x, act=act,
                                    use_kernel_bwd=False) ** 2).mean()
    gk = jax.grad(f_k, argnums=(0, 1))(p, x)
    ge = jax.grad(f_e, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(ge)):
        _close(a, b, tol)


@pytest.mark.parametrize("act", ["gelu", "swiglu"])
def test_ff_pallas_bwd_matches_oracle(act, monkeypatch):
    """REPRO_KERNEL_BWD=pallas forces the rematerialize + dgrad/wgrad
    kernel composition off-TPU (interpret mode) — still oracle-exact."""
    monkeypatch.setenv("REPRO_KERNEL_BWD", "pallas")
    ops._make_dyad_ff.cache_clear()
    gated = act == "swiglu"
    ws = _ff_weights(2, 24, 37, 24, gated=gated)     # odd hidden: j padding
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 48))
    p = _params(ws)
    f_k = lambda p, x: (ops.dyad_ff(p, x, act=act) ** 2).mean()
    f_e = lambda p, x: (ops.dyad_ff(p, x, act=act,
                                    use_kernel_bwd=False) ** 2).mean()
    gk = jax.grad(f_k, argnums=(0, 1))(p, x)
    ge = jax.grad(f_e, argnums=(0, 1))(p, x)
    ops._make_dyad_ff.cache_clear()
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(ge)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_ff_bwd_mixed_weight_dtypes():
    """Weight cotangents come back in each tensor's OWN dtype."""
    ws = _ff_weights(4, 16, 32, 16)
    ws["wd2"] = ws["wd2"].astype(jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    g = jax.grad(lambda p, x: (ops.dyad_ff(p, x, act="gelu") ** 2).mean())(
        _params(ws), x)
    assert g["down"]["w1"].dtype == jnp.float32
    assert g["down"]["w2"].dtype == jnp.bfloat16


def test_grad_through_jitted_ff_block():
    """End-to-end jax.grad through a jitted loss over the fused ff op must
    match the plain-jnp mlp path (fuse_mlp einsum fusion as reference)."""
    lc_k = factory.LinearCfg(impl="dyad", n_dyad=4, variant="it",
                             use_kernel=True, fuse_ff_kernel=True)
    lc_e = factory.LinearCfg(impl="dyad", n_dyad=4, variant="it",
                             fuse_mlp=True)
    p = mlp_lib.init_mlp(KEY, 32, 64, lc_k, act="swiglu")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32))

    def loss(p, x, lc):
        return (mlp_lib.apply_mlp(p, x, lc, act="swiglu") ** 2).mean()

    gk = jax.jit(jax.grad(lambda p, x: loss(p, x, lc_k)))(p, x)
    ge = jax.jit(jax.grad(lambda p, x: loss(p, x, lc_e)))(p, x)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(ge)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# -- dispatch from the mlp layer ----------------------------------------------


@pytest.mark.parametrize("act", ["gelu", "swiglu"])
def test_apply_mlp_dispatches_megakernel(act):
    """fuse_ff_kernel config routes apply_mlp through ops.dyad_ff — the
    MIXED-VARIANT dataflow (up=IT, down=OT), i.e. the same function the
    fuse_mlp einsum fusion computes, and the same explicit
    IT-up/OT-down composition from core.dyad."""
    from repro.core import dyad

    lc = factory.LinearCfg(impl="dyad", n_dyad=4, variant="it",
                           use_kernel=True, fuse_ff_kernel=True)
    p = mlp_lib.init_mlp(KEY, 32, 64, lc, act=act)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32))
    y = mlp_lib.apply_mlp(p, x, lc, act=act)
    y_fmlp = mlp_lib.apply_mlp(p, x, lc.replace(fuse_mlp=True,
                                                fuse_ff_kernel=False,
                                                use_kernel=False), act=act)
    spec_it = dyad.DyadSpec(n_dyad=4, variant="it")
    spec_ot = dyad.DyadSpec(n_dyad=4, variant="ot")
    if act == "swiglu":
        h = (jax.nn.silu(dyad.apply(p["gate"], x, spec_it))
             * dyad.apply(p["up"], x, spec_it))
    else:
        h = jax.nn.gelu(dyad.apply(p["up"], x, spec_it))
    y_mix = dyad.apply(p["down"], h, spec_ot)
    _close(y, y_fmlp, 2e-4)
    _close(y, y_mix, 2e-4)


def test_apply_mlp_megakernel_requires_bias_free():
    """Biased ff params must fall back to the unfused path (the megakernel
    has no bias epilogue) — numbers still match the plain path."""
    lc = factory.LinearCfg(impl="dyad", n_dyad=4, variant="it",
                           use_kernel=True, fuse_ff_kernel=True)
    p = mlp_lib.init_mlp(KEY, 32, 64, lc, act="gelu", bias=True)
    assert not mlp_lib._ff_kernel_ready(p, lc, "gelu")
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    y = mlp_lib.apply_mlp(p, x, lc, act="gelu")
    y_plain = mlp_lib.apply_mlp(p, x, factory.LinearCfg(
        impl="dyad", n_dyad=4, variant="it"), act="gelu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_plain),
                               rtol=2e-4, atol=2e-4)


def test_megakernel_dispatch_under_sharding_ctx():
    """PR 8 contract: an active sharding context no longer demotes the
    megakernel — the shard_map TP wrappers (kernels/tp.py) keep the kernel
    route, and REPRO_KERNEL_TP=off is the explicit hatch back to the
    einsum fallback (route counters record the choice either way)."""
    from jax.sharding import Mesh
    from repro import obs
    from repro.sharding import ctx as shard_ctx

    lc = factory.LinearCfg(impl="dyad", n_dyad=4, variant="it",
                           use_kernel=True, fuse_ff_kernel=True)
    p = mlp_lib.init_mlp(KEY, 32, 64, lc, act="gelu")
    assert mlp_lib._ff_kernel_ready(p, lc, "gelu")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    with shard_ctx.activation_sharding(mesh, dp=("data",), model="model"):
        obs.reset_route_counts()
        assert mlp_lib._ff_kernel_ready(p, lc, "gelu")
        assert obs.routes_snapshot() == {"ff_tp:tp_fused": 1}
        os.environ["REPRO_KERNEL_TP"] = "off"
        try:
            assert not mlp_lib._ff_kernel_ready(p, lc, "gelu")
            assert obs.routes_snapshot()["ff_tp:tp_fallback"] == 1
        finally:
            del os.environ["REPRO_KERNEL_TP"]
    assert mlp_lib._ff_kernel_ready(p, lc, "gelu")


def test_linear_cfg_spec_token():
    from repro import configs

    lc = configs.linear_cfg("dyad_it_4_kernel_ffused")
    assert lc.use_kernel and lc.fuse_ff_kernel
    assert not configs.linear_cfg("dyad_it_4_kernel").fuse_ff_kernel


# -- tile planning ------------------------------------------------------------


def test_plan_ff_tiles_never_degenerate():
    plan = plan_ff_tiles(521, 1031, 769, 1031, 256, 256, 512, 512)
    # Mosaic-legal tiles (multiples of 8 sublanes / 128 lanes), padding
    # short of one unit, and a grid of tiles, not of elements
    for dim, padded, tile, unit in [(521, plan.padded_b, plan.bB, 8),
                                    (1031, plan.padded_o, plan.bO, 128),
                                    (769, plan.padded_j, plan.bJ, 128),
                                    (1031, plan.padded_k, plan.bK, 128)]:
        assert tile % unit == 0 and padded % tile == 0
        assert 0 <= padded - dim < unit
    assert (plan.bB, plan.bO, plan.bJ, plan.bK) == (176, 128, 128, 384)
    assert plan.grid_steps == 3 * 9 * 7 * 3
    # healthy dims are untouched
    plan = plan_ff_tiles(64, 192, 768, 192, 256, 256, 512, 512)
    assert (plan.padded_b, plan.padded_o, plan.padded_j,
            plan.padded_k) == (64, 192, 768, 192)
    assert (plan.bB, plan.bO, plan.bJ, plan.bK) == (64, 192, 384, 192)


def test_megakernel_validates_gate_args():
    ws = _ff_weights(2, 16, 32, 16)
    x = jax.random.normal(KEY, (4, 32))
    x1, x2 = ref.block_views(x, 2, "it")
    with pytest.raises(ValueError, match="swiglu"):
        dyad_ff_fused(x1, x2, ws["wu1"], ws["wu2"], ws["wd1"], ws["wd2"],
                      act="swiglu", interpret=True)
    # HALF a gate is as wrong as none
    with pytest.raises(ValueError, match="swiglu"):
        dyad_ff_fused(x1, x2, ws["wu1"], ws["wu2"], ws["wd1"], ws["wd2"],
                      wg1=ws["wu1"], act="swiglu", interpret=True)
