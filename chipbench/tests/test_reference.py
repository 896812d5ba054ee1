"""The plain reference agrees with the program's forward pass on the smoke
configurations, on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common, models, reference
from chipbench.tests import cells


# each configuration as published, and OPT with dense ff layers (its
# ``program.linear`` set to ``dense``)
CONFIGS = pytest.mark.parametrize(
    "name,linear", [("opt-125m", None), ("qwen3-0.6b", None),
                    ("opt-125m", "dense")],
    ids=["opt-125m", "qwen3-0.6b", "opt-125m-dense"])


@CONFIGS
def test_weights_have_the_program_layout(name, linear):
    from repro.models import model

    conf = cells.config(name, compute="float32", linear=linear)
    cfg = common.program_cfg(conf)
    want = jax.eval_shape(lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: models.of(conf).make(
        conf, np.zeros(2, np.uint32)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@CONFIGS
def test_reference_matches_program_forward(name, linear):
    from repro.models import model

    conf = cells.config(name, compute="float32", linear=linear)
    cfg = common.program_cfg(conf)
    ref = models.of(conf)
    m = ref.dims(conf)
    params = models.make_jit(conf, 11)
    tokens = np.random.default_rng(0).integers(0, m["vocab"], 24)
    with jax.default_matmul_precision("highest"):
        want = model.forward(cfg, params, {"tokens": jnp.asarray(tokens)[None]})[0][0]
    got = ref.logits_at(m, params, jnp.asarray(tokens), jnp.arange(24),
                        "fp32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_control_departs_from_reference():
    conf = cells.config("qwen3-0.6b")
    model = models.of(conf)
    m = model.dims(conf)
    params = models.make_jit(conf, 5)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, m["vocab"], 16))
    a = model.logits_at(m, params, tokens, jnp.arange(16), "fp32")
    b = model.logits_at(m, params, tokens, jnp.arange(16), "fp8")
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3


def test_dyad_is_two_block_sparse_components():
    """A DYAD projection equals x @ W.T for the dense matrix holding its two
    components at the places the variant puts them."""
    n, d_out, d_in = 4, 3, 5
    rng = np.random.default_rng(2)
    w1, w2 = rng.standard_normal((2, n, d_out, d_in)).astype(np.float32)
    x = rng.standard_normal((7, n * d_in)).astype(np.float32)
    for variant in ("it", "ot"):
        W = np.zeros((n * d_out, n * d_in), np.float32)
        for g in range(n):
            for o in range(d_out):
                for i in range(d_in):
                    W[g * d_out + o, g * d_in + i] += w1[g, o, i]
                    if variant == "it":
                        W[g * d_out + o, i * n + g] += w2[g, o, i]
                    else:
                        W[o * n + g, g * d_in + i] += w2[g, o, i]
        got = reference.dyad({"w1": w1, "w2": w2}, x, variant, "fp32")
        np.testing.assert_allclose(np.asarray(got), x @ W.T, rtol=1e-5,
                                   atol=1e-5)
