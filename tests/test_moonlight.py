"""Moonlight-16B-A3B (the DeepSeek-V3 block) against the plain reference,
at smoke size on the CPU, float32: latent attention, the chip's-share
expert layer (the shares add up to the uncut layer, and no routed token is
dropped), and the whole model's loss and gradients.

The reference is the benchmark's (``chipbench/models/deepseek_v3.py``),
written from the published description without the program's code; the
expert oracle here runs the DYAD experts as einsums
(``repro.core.dyad`` without kernels), token by token.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common, models
from chipbench.models import deepseek_v3 as ref
from chipbench.tests import cells
from repro import configs
from repro.core import dyad
from repro.layers import mla as mla_lib
from repro.layers import moe as moe_lib
from repro.models import model

NAME = "moonlight-16b-a3b"
TOL = dict(rtol=2e-4, atol=2e-4)


def _setup(seed=11):
    conf = cells.config(NAME, compute="float32")
    return conf, common.program_cfg(conf), ref.dims(conf), \
        models.make_jit(conf, seed)


def _layer(params, group, i):
    return jax.tree.map(lambda a: a[i], params[group])


# -- latent attention ---------------------------------------------------------


@pytest.mark.parametrize("route", ["xla", "flash"])
def test_mla_forward_and_grads_match_reference(route, monkeypatch):
    """Both attention routes: the einsum path, and the flash kernels (in the
    interpreter) at qk width 24 against v width 16."""
    monkeypatch.setenv("REPRO_KERNEL_ATTN", route)
    conf, cfg, m, params = _setup()
    p = _layer(params, "layers", 0)["attn"]
    S = 24
    h = jax.random.normal(jax.random.PRNGKey(3), (1, S, m["d"]))
    pos = jnp.arange(S)
    g = jax.random.normal(jax.random.PRNGKey(4), (1, S, m["d"]))

    def program(p, h):
        y = mla_lib.apply_mla(
            p, h, n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope=cfg.qk_nope_head_dim, qk_rope=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, lin_cfg=cfg.linear,
            rope_theta=cfg.rope_theta, positions=pos, flash=True)
        return jnp.sum(y * g), y

    def reference(p, h):
        y = ref.attention(m, p, h[0], pos, "fp32", 8)[None]
        return jnp.sum(y * g), y

    with jax.default_matmul_precision("highest"):
        (_, y1), g1 = jax.value_and_grad(program, (0, 1), has_aux=True)(p, h)
        (_, y0), g0 = jax.value_and_grad(reference, (0, 1),
                                         has_aux=True)(p, h)
    np.testing.assert_allclose(y1, y0, **TOL)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, **TOL)


def test_rope_deinterleaves_pairs():
    """DeepSeek's rope: interleaved pairs (x[2i], x[2i+1]) rotate together by
    position x theta^(-2i/d)."""
    d, S = 8, 5
    x = jax.random.normal(jax.random.PRNGKey(0), (S, 1, d))
    pos = jnp.arange(S)
    got = mla_lib.rope_interleaved(x, pos, 100.0)
    inv = 1.0 / 100.0 ** (np.arange(0, d, 2) / d)
    ang = np.asarray(pos)[:, None] * inv                       # (S, d/2)
    a, b = np.asarray(x)[:, 0, 0::2], np.asarray(x)[:, 0, 1::2]
    want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           a * np.sin(ang) + b * np.cos(ang)], -1)
    np.testing.assert_allclose(got[:, 0], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref.rope_deepseek(x, pos, 100.0), got,
                               rtol=1e-6, atol=1e-6)


# -- the expert layer as one chip's share -------------------------------------


def _oracle(p, x, cfg, w, idx, experts, first):
    """Σ over each token's routed copies held in ``experts`` (ids from
    ``first``) of weight x the expert's SwiGLU, by DYAD einsums, token by
    token: the uncut layer when ``experts`` are all of them."""
    spec = dyad.DyadSpec(n_dyad=cfg.linear.n_dyad, variant="it")

    def swiglu(q, h):
        g = dyad.apply(q["gate"], h, spec)
        u = dyad.apply(q["up"], h, spec)
        return dyad.apply(q["down"], jax.nn.silu(g) * u, spec)

    y = np.zeros(x.shape, np.float32)
    n_held = experts["up"]["w1"].shape[0]
    for t in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j]) - first
            if 0 <= e < n_held:
                q = jax.tree.map(lambda a: a[e], experts)
                y[t] += float(w[t, j]) * np.asarray(swiglu(q, x[t]))
    return y


def _share_params(p, lo, hi):
    return {**p, "experts": jax.tree.map(lambda a: a[lo:hi], p["experts"])}


def _full_layer(seed=5):
    """A smoke expert layer with all 16 experts' weights drawn."""
    cfg = configs.get("moonlight_16b_a3b", smoke=True,
                      linear=configs.linear_cfg("dyad_it_4_kernel"))
    p = moe_lib.init_moe_share(jax.random.PRNGKey(seed), cfg.d_model,
                               cfg.expert_d_ff, cfg.n_experts, cfg.n_experts,
                               cfg.linear, n_shared=cfg.n_shared)
    p["score_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                              (cfg.n_experts,))
    return cfg, p


def _apply(cfg, p, x, first, **kw):
    kw = {"top_k": cfg.top_k, "score": cfg.router_score,
          "norm_topk": cfg.norm_topk, "routed_scale": cfg.routed_scale, **kw}
    with jax.default_matmul_precision("highest"):
        return moe_lib.apply_moe_share(p, x, cfg.linear, first=first, **kw)


@pytest.mark.parametrize("chunk", [None, 16])
def test_shares_add_up_to_the_uncut_layer(chunk):
    """Four chips, each holding 4 of the 16 experts (ids 0-3, 4-7, ...): the
    parts of the result their shares give, with the shared experts counted
    once, add up to the uncut layer over all 16 experts."""
    cfg, p = _full_layer()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model))
    shared = moe_lib.mlp_lib.apply_mlp(p["shared"], x, cfg.linear,
                                       act="swiglu")
    total, rows = -3 * shared, 0.0
    for first in range(0, cfg.n_experts, 4):
        y, stats = _apply(cfg, _share_params(p, first, first + 4), x, first,
                          chunk=chunk)
        total, rows = total + y, rows + stats["routed_rows"]
    xf = x.reshape(-1, cfg.d_model)
    w, idx = moe_lib.route_scores(p, xf, top_k=cfg.top_k,
                                  score=cfg.router_score,
                                  norm_topk=cfg.norm_topk,
                                  routed_scale=cfg.routed_scale)
    want = _oracle(p, np.asarray(xf), cfg, w, idx, p["experts"], 0)
    want = want + np.asarray(shared).reshape(xf.shape)
    np.testing.assert_allclose(total.reshape(xf.shape), want, **TOL)
    assert rows == xf.shape[0] * cfg.top_k       # every copy, once


def test_every_routed_copy_is_computed():
    """Dropless: with the selection bias steering every token to the same
    held expert, each (token, held expert) pair is still computed, and
    the busiest expert's counter holds them all."""
    cfg, p = _full_layer(seed=9)
    first = 4
    p = _share_params(p, first, first + 4)
    p["score_bias"] = p["score_bias"].at[first + 1].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, cfg.d_model))
    y, stats = _apply(cfg, p, x, first, row_tile=16)
    xf = x.reshape(-1, cfg.d_model)
    w, idx = moe_lib.route_scores(p, xf, top_k=cfg.top_k,
                                  score=cfg.router_score,
                                  norm_topk=cfg.norm_topk,
                                  routed_scale=cfg.routed_scale)
    idx = np.asarray(idx)
    held = (idx >= first) & (idx < first + 4)
    assert (idx == first + 1).any(axis=1).all()
    assert float(stats["routed_rows"]) == held.sum()
    assert float(stats["max_expert_rows"]) == xf.shape[0]
    shared = moe_lib.mlp_lib.apply_mlp(p["shared"], x, cfg.linear,
                                       act="swiglu")
    want = _oracle(p, np.asarray(xf), cfg, w, idx, p["experts"], first)
    np.testing.assert_allclose((y - shared).reshape(xf.shape), want, **TOL)


def test_share_gradients_match_the_oracle():
    cfg, p = _full_layer(seed=13)
    first = 8
    p = _share_params(p, first, first + 4)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 24, cfg.d_model))
    g = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    xf = x.reshape(-1, cfg.d_model)
    _, idx = moe_lib.route_scores(p, xf, top_k=cfg.top_k,
                                  score=cfg.router_score,
                                  norm_topk=cfg.norm_topk,
                                  routed_scale=cfg.routed_scale)
    spec = dyad.DyadSpec(n_dyad=cfg.linear.n_dyad, variant="it")

    def oracle(p, x):
        xf = x.reshape(-1, cfg.d_model)
        w, _ = moe_lib.route_scores(p, xf, top_k=cfg.top_k,
                                    score=cfg.router_score,
                                    norm_topk=cfg.norm_topk,
                                    routed_scale=cfg.routed_scale)
        y = moe_lib.mlp_lib.apply_mlp(p["shared"], x, cfg.linear,
                                      act="swiglu").reshape(xf.shape)
        for e in range(4):
            q = jax.tree.map(lambda a: a[e], p["experts"])
            we = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
            h = jax.nn.silu(dyad.apply(q["gate"], xf, spec)) \
                * dyad.apply(q["up"], xf, spec)
            y = y + we[:, None] * dyad.apply(q["down"], h, spec)
        return jnp.sum(y.reshape(x.shape) * g)

    def program(p, x):
        return jnp.sum(_apply(cfg, p, x, first, row_tile=8)[0] * g)

    with jax.default_matmul_precision("highest"):
        g1 = jax.grad(program, (0, 1))(p, x)
        g0 = jax.grad(oracle, (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, **TOL)


# -- the whole model ----------------------------------------------------------


def test_layer_pattern_is_a_dense_group_then_an_expert_group():
    cfg = configs.get("moonlight_16b_a3b")
    assert model.layer_groups(cfg) == (("dense_layers", "lm", 1),
                                       ("layers", "moe", 26))
    opt = configs.get("opt125m")
    assert model.layer_groups(opt) == (("layers", "lm", 12),)


def test_model_loss_and_grads_match_reference():
    conf, cfg, m, params = _setup(seed=21)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, m["vocab"], (2, 16)).astype(np.int32)
    lab = rng.integers(0, m["vocab"], (2, 16)).astype(np.int32)

    def program(p):
        loss, mt = model.loss_fn(cfg, p, {"tokens": tok, "labels": lab})
        return loss, mt

    def reference(p):
        return ref.nll_sum(m, p, tok, lab, "fp32") / tok.size

    with jax.default_matmul_precision("highest"):
        (l1, mt), g1 = jax.value_and_grad(program, has_aux=True)(params)
        l0, g0 = jax.value_and_grad(reference)(params)
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    assert jax.tree.structure(g1) == jax.tree.structure(g0)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)
    # the counters: 2 expert layers, 32 tokens, 4 of 16 experts held
    assert 0 < float(mt["max_expert_rows"]) <= float(mt["routed_rows"])
    assert float(mt["routed_rows"]) <= 2 * 32 * 4
