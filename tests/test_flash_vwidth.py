"""Flash prefill, dq and dkv with a value width apart from the query/key
width (latent attention: qk 192 against v 128), in the Pallas interpreter,
against the einsum oracle; and with equal widths the same results as the
narrower values padded to the qk width."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attn as fa
from repro.kernels import ref

KEY = jax.random.PRNGKey(0)


def _rand(S, K, G, h, dv, key=KEY):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (1, S, K, G, h))
    k = jax.random.normal(ks[1], (1, S, K, h))
    v = jax.random.normal(ks[2], (1, S, K, dv))
    do = jax.random.normal(ks[3], (1, S, K, G, dv))
    return q, k, v, do


@pytest.mark.parametrize("G,window", [(1, None), (2, None), (1, 100)])
def test_prefill_and_grads_with_narrower_values(G, window):
    """qk 24 against v 16 on a multi-tile causal grid: forward and the three
    cotangents against autodiff of the oracle."""
    S = 256
    q, k, v, do = _rand(S, 2, G, 24, 16)
    pos = jnp.arange(S)
    kw = dict(causal=True, window=window, block_q=64, block_k=128,
              interpret=True)
    o, lse = fa.flash_prefill(q, k, v, save_lse=True, **kw)
    want, vjp = jax.vjp(lambda q, k, v: ref.sdpa_ref(
        q, k, v, pos, pos, causal=True, window=window), q, k, v)
    assert o.shape == want.shape == (1, S, 2, G, 16)
    np.testing.assert_allclose(o, want, atol=2e-5)
    got = fa.flash_prefill_grads(q, k, v, o, lse, do, **kw)
    for name, a, b in zip(("dq", "dk", "dv"), got, vjp(do)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


def test_equal_widths_give_the_padded_result():
    """Values padded with zero columns to the qk width run the equal-width
    kernels; their output and gradients, cut back, are the narrow ones."""
    S, dv, h = 256, 16, 24
    q, k, v, do = _rand(S, 2, 1, h, dv, key=jax.random.PRNGKey(3))
    pad = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, h - dv)])
    kw = dict(causal=True, block_q=64, block_k=128, interpret=True)
    o, lse = fa.flash_prefill(q, k, v, save_lse=True, **kw)
    op, lsep = fa.flash_prefill(q, k, pad(v), save_lse=True, **kw)
    np.testing.assert_allclose(op[..., :dv], o, atol=1e-6)
    np.testing.assert_allclose(op[..., dv:], 0.0)
    np.testing.assert_allclose(lsep, lse, atol=1e-6)
    got = fa.flash_prefill_grads(q, k, v, o, lse, do, **kw)
    gotp = fa.flash_prefill_grads(q, k, pad(v), op, lsep, pad(do), **kw)
    np.testing.assert_allclose(gotp[0], got[0], atol=1e-5)
    np.testing.assert_allclose(gotp[1], got[1], atol=1e-5)
    np.testing.assert_allclose(gotp[2][..., :dv], got[2], atol=1e-5)
