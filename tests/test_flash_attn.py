"""Flash-attention kernels: oracle equivalence across GQA/mask/dtype/odd
shapes, ring-cache decode, backward routes, dispatch gating, and autotune
integration (trace-time tile resolution)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attn as fa
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.layers import attention as attn_lib
from repro.perf import autotune
from repro.perf.autotune import BlockCache, tune_key

KEY = jax.random.PRNGKey(0)


@pytest.fixture
def cache(tmp_path):
    """Isolated BlockCache installed as the process singleton."""
    c = BlockCache(user_path=str(tmp_path / "blocks.json"),
                   defaults_path=str(tmp_path / "defaults.json"))
    autotune.reset_cache(c)
    yield c
    autotune.reset_cache(None)


def _rand(B, S, T, K, G, h, dtype=jnp.float32, key=KEY):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, S, K, G, h), dtype)
    k = jax.random.normal(ks[1], (B, T, K, h), dtype)
    v = jax.random.normal(ks[2], (B, T, K, h), dtype)
    return q, k, v, ks[3]


def _ring_kpos(idx, L):
    j = jnp.arange(L)
    kpos = idx - (idx - j) % L
    return jnp.where(kpos >= 0, kpos, -(10 ** 9))


# -- forward vs oracle --------------------------------------------------------


@pytest.mark.parametrize("K,G", [(2, 1), (2, 2), (1, 4)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 7),
                                           (False, None)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefill_vs_oracle(K, G, causal, window, dtype):
    """Kernel vs the einsum oracle across GQA ratios x masks x dtypes, at
    a prime S=T so both grid axes go through tile padding."""
    S = T = 37
    q, k, v, _ = _rand(2, S, T, K, G, 16, dtype)
    want = ref.sdpa_ref(q, k, v, jnp.arange(S), jnp.arange(T),
                        causal=causal, window=window)
    got, _ = fa.flash_prefill(q, k, v, causal=causal, window=window,
                              block_q=16, block_k=128, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_prefill_tile_invariance():
    """Different tile choices change only the schedule, never the values."""
    q, k, v, _ = _rand(1, 64, 64, 2, 2, 32)
    outs = [fa.flash_prefill(q, k, v, causal=True, window=9, block_q=bq,
                             block_k=bk, interpret=True)[0]
            for bq, bk in [(8, 128), (32, 128), (64, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=2e-5)


def test_prefill_offsets():
    """Contiguous positions from nonzero q/k offsets (the fresh-stream
    cache-prefill contract: q_off = k_off = idx)."""
    S = T = 24
    q, k, v, _ = _rand(2, S, T, 2, 2, 16)
    for qo, ko in [(5, 0), (7, 7)]:
        want = ref.sdpa_ref(q, k, v, qo + jnp.arange(S), ko + jnp.arange(T),
                            causal=True, window=6)
        got, _ = fa.flash_prefill(q, k, v, qo, ko, causal=True, window=6,
                                  block_q=8, block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)


# -- tile classes: empty / interior / edge ------------------------------------


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("block_k", [128, 512])
@pytest.mark.parametrize("block_q", [8, 128, 256])
def test_tile_class_matches_mask(block_q, block_k, G, window):
    """The predicate the kernels branch on agrees with the mask they
    build: a tile is interior exactly when its mask is all true,
    and an empty (out-of-band) tile's mask is all false — over padded and
    unpadded S/T, and offsets d on and around the tile boundaries, where a
    diagonal or window edge passes within one position of a tile corner."""
    for S, T in [(1024, 1024), (1001, 1000), (512, 1100)]:
        bQ, Sp, bT, Tp = fa._plan_attn(S, T, block_q, block_k)
        nq, nt = Sp // bQ, Tp // bT
        tile = jax.jit(jax.vmap(jax.vmap(
            lambda d, qi, ki: fa._tile_mask(d, qi, ki, bQ, bT, G, T, True,
                                            window),
            (None, None, 0)), (None, 0, None)))
        qi, ki = np.arange(nq)[:, None], np.arange(nt)[None, :]
        for d in (-3, -2, -1, 0, 1, 2, 5, 6, 7, 126, 127, 128, 129):
            masks = tile(jnp.int32(d), jnp.arange(nq), jnp.arange(nt))
            all_true = np.asarray(masks.all(axis=(2, 3)))
            any_true = np.asarray(masks.any(axis=(2, 3)))
            band, interior = fa._tile_class(True, window, d, qi, ki, bQ, bT,
                                            T)
            where = f"S={S} T={T} bQ={bQ} bT={bT} d={d}"
            np.testing.assert_array_equal(
                np.broadcast_to(interior, (nq, nt)), all_true, err_msg=where)
            assert not np.any(~np.broadcast_to(band, (nq, nt)) & any_true), \
                where


def test_tile_counts_at_the_training_shape():
    """OPT-125m training at S = T = 2,048 on the chip's default tiles
    (bQ 256, bK 512): per (batch, kv-head), 12 empty, 12 interior and 8
    edge tiles of 32."""
    bQ, Sp, bT, Tp = fa._plan_attn(2048, 2048, 256, 512)
    assert fa._tile_counts(Sp // bQ, Tp // bT, bQ, bT, 2048, True,
                           None) == (12, 12, 8)
    # unmasked attention is all interior; a padded column makes its tile edge
    assert fa._tile_counts(4, 2, 128, 128, 256, False, None) == (0, 8, 0)
    assert fa._tile_counts(4, 2, 128, 128, 250, False, None) == (0, 4, 4)


def _ref_lse(q, k, qpos, kpos, window):
    """Oracle log-sum-exp in the kernel's (B, K, S*G) row layout."""
    B, S, K, G, h = q.shape
    s = jnp.einsum("bskgh,btkh->bksgt", q, k) / float(h) ** 0.5
    valid = kpos[None, :] <= qpos[:, None]
    if window is not None:
        valid = valid & (qpos[:, None] - kpos[None, :] < window)
    s = jnp.where(valid[None, None, :, None, :], s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1).reshape(B, K, S * G)


# S = T = 512 on 128 x 128 tiles: 16 tiles per head, of which 6 are
# interior at d = 0 (fewer at d = 2, more at d = 128; none under the
# 200-position window, some under 400) — the unmasked dq body runs.
_MULTI_TILE = [pytest.param(G, offs, window,
                            id=f"G{G}-q{offs[0]}k{offs[1]}-w{window}")
               for G in (1, 2) for offs in [(0, 0), (128, 0), (5, 3)]
               for window in (None, 200, 400)]


@pytest.mark.parametrize("G,offs,window", _MULTI_TILE)
def test_prefill_multi_tile_vs_oracle(G, offs, window):
    """Forward ``o`` and ``lse`` on a grid with interior, edge and empty
    tiles, against the einsum oracle."""
    S = T = 512
    q, k, v, _ = _rand(1, S, T, 2, G, 16)
    qpos, kpos = offs[0] + jnp.arange(S), offs[1] + jnp.arange(T)
    got, lse = fa.flash_prefill(q, k, v, *offs, causal=True, window=window,
                                save_lse=True, block_q=128, block_k=128,
                                interpret=True)
    want = ref.sdpa_ref(q, k, v, qpos, kpos, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(_ref_lse(q, k, qpos, kpos, window)),
                               atol=2e-5)


@pytest.mark.parametrize("G,offs,window", _MULTI_TILE)
def test_grads_multi_tile_vs_einsum_vjp(G, offs, window):
    """``dq`` (forward grid) and ``dk``/``dv`` (transposed grid) on the same
    multi-tile grids, against autodiff of the einsum oracle."""
    S = T = 512
    q, k, v, kk = _rand(1, S, T, 2, G, 16)
    do = jax.random.normal(kk, q.shape)
    qpos, kpos = offs[0] + jnp.arange(S), offs[1] + jnp.arange(T)
    kw = dict(causal=True, window=window, block_q=128, block_k=128,
              interpret=True)
    o, lse = fa.flash_prefill(q, k, v, *offs, save_lse=True, **kw)
    got = fa.flash_prefill_grads(q, k, v, o, lse, do, *offs, **kw)
    _, vjp = jax.vjp(lambda q, k, v: ref.sdpa_ref(
        q, k, v, qpos, kpos, causal=True, window=window), q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, vjp(do)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=name)


def test_fully_masked_rows_are_zero():
    """A row with no valid key yields 0 — the guard the kernels implement
    explicitly and `_naive_sdpa` gained for parity with `_chunked_sdpa`."""
    S = T = 8
    q, k, v, _ = _rand(1, S, T, 2, 1, 16)
    # every key strictly in the future of every query -> causal masks all
    got, _ = fa.flash_prefill(q, k, v, 0, 100, causal=True,
                              block_q=8, block_k=128, interpret=True)
    assert np.all(np.asarray(got) == 0.0)
    dead = jnp.full((T,), -(10 ** 9))
    naive = attn_lib._naive_sdpa(q, k, v, jnp.arange(S), dead, True, None)
    assert np.all(np.isfinite(np.asarray(naive)))
    assert np.all(np.asarray(naive) == 0.0)
    chunked = attn_lib._chunked_sdpa(q, k, v, jnp.arange(S), dead, True,
                                     None, 4)
    assert np.all(np.asarray(chunked) == 0.0)
    qblock = attn_lib._q_block_sdpa(q, k, v, jnp.arange(S), dead, True,
                                    None, 4)
    assert np.all(np.asarray(qblock) == 0.0)


def test_naive_matches_independent_oracle():
    """The two oracles (layers._naive_sdpa, kernels.ref.sdpa_ref) agree —
    they are deliberately independent implementations."""
    q, k, v, _ = _rand(2, 13, 13, 2, 2, 16)
    a = attn_lib._naive_sdpa(q, k, v, jnp.arange(13), jnp.arange(13),
                             True, 5)
    b = ref.sdpa_ref(q, k, v, jnp.arange(13), jnp.arange(13),
                     causal=True, window=5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# -- the q-block scan fallback (satellite) ------------------------------------


@pytest.mark.parametrize("window", [None, 9])
def test_q_block_scan_matches_naive(window):
    """The lax.scan rewrite of `_q_block_sdpa` (O(1) trace size) must stay
    bit-compatible with the naive oracle, including the runtime band skip."""
    S = T = 64
    q, k, v, _ = _rand(2, S, T, 2, 2, 16)
    qpos, kpos = jnp.arange(S), jnp.arange(T)
    want = attn_lib._naive_sdpa(q, k, v, qpos, kpos, True, window)
    got = attn_lib._q_block_sdpa(q, k, v, qpos, kpos, True, window, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_q_block_scan_trace_is_depth_independent():
    """The whole point of the scan: the jaxpr no longer grows with S."""
    def n_eqns(S):
        q = jnp.zeros((1, S, 2, 1, 16))
        k = jnp.zeros((1, S, 2, 16))
        jaxpr = jax.make_jaxpr(
            lambda q, k: attn_lib._q_block_sdpa(
                q, k, k, jnp.arange(S), jnp.arange(S), True, None, 16)
        )(q, k)
        return len(jaxpr.jaxpr.eqns)
    assert n_eqns(256) == n_eqns(64)


# -- ring-cache decode --------------------------------------------------------


@pytest.mark.parametrize("L,idxs,window", [
    (8, [3], None),            # scalar idx, unwrapped
    (8, [11], 8),              # scalar idx, wrapped ring
    (8, [3, 11], 8),           # per-slot idx, mixed wrap state
    (10, [5, 20, 16], 7),      # odd L through tile padding
])
def test_decode_ring_equivalence(L, idxs, window):
    B, K, G, h = len(idxs), 2, 2, 16
    q, _, _, kk = _rand(B, 1, L, K, G, h)
    k = jax.random.normal(kk, (B, L, K, h))
    v = jax.random.normal(jax.random.fold_in(kk, 1), (B, L, K, h))
    idx = (jnp.asarray(idxs, jnp.int32) if B > 1
           else jnp.int32(idxs[0]))
    want = jnp.concatenate([
        ref.sdpa_ref(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                     jnp.array([idxs[b]]), _ring_kpos(idxs[b], L),
                     causal=True, window=window)
        for b in range(B)], axis=0)
    got = fa.flash_decode(q, k, v, idx, window=window, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_decode_mixed_cache_dtype():
    """bf16 KV cache under an fp32 query (and vice versa) promotes
    per-tile in VMEM instead of failing the kernel dot."""
    B, L, K, G, h = 2, 8, 2, 2, 16
    q, k, v, _ = _rand(B, 1, L, K, G, h)
    idx = jnp.int32(5)
    want = ref.sdpa_ref(q, k.astype(jnp.bfloat16).astype(jnp.float32),
                        v.astype(jnp.bfloat16).astype(jnp.float32),
                        jnp.array([5]), _ring_kpos(5, L), causal=True)
    got = fa.flash_decode(q, k.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16), idx, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)


# -- backward -----------------------------------------------------------------


@pytest.mark.parametrize("route", ["pallas", "xla"])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 5)])
def test_backward_vs_einsum_vjp(route, causal, window, monkeypatch):
    """Both kernel-backward routes (flash Pallas kernels, compiled XLA
    recompute) against autodiff of the einsum oracle."""
    S = T = 24
    q, k, v, _ = _rand(2, S, T, 2, 2, 16)
    monkeypatch.setenv("REPRO_KERNEL_BWD", route)
    kops._make_flash_attention.cache_clear()

    def loss(use_kernel_bwd):
        return lambda q, k, v: (kops.flash_attention(
            q, k, v, causal=causal, window=window,
            use_kernel_bwd=use_kernel_bwd) ** 2).sum()

    want = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
    got = jax.jit(jax.grad(loss(True), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    kops._make_flash_attention.cache_clear()


def test_e2e_grad_through_attention_block(monkeypatch):
    """Jitted jax.grad through a flash-routed `layers.attention` block
    equals the einsum-path gradient (same params, same loss)."""
    from repro.core import factory

    d_model, n_heads, n_kv, hd = 32, 4, 2, 8
    lc = factory.DENSE
    p = attn_lib.init_attention(KEY, d_model, n_heads, n_kv, hd, lc)
    x = jax.random.normal(jax.random.fold_in(KEY, 9), (2, 16, d_model))

    def make_loss(flash):
        def loss(p, x):
            o, _ = attn_lib.attention(
                p, x, n_heads=n_heads, n_kv=n_kv, head_dim=hd, lin_cfg=lc,
                causal=True, flash=flash)
            return (o ** 2).sum()
        return loss

    monkeypatch.setenv("REPRO_KERNEL_ATTN", "flash")
    g_flash = jax.jit(jax.grad(make_loss(True)))(p, x)
    monkeypatch.setenv("REPRO_KERNEL_ATTN", "xla")
    g_ref = jax.jit(jax.grad(make_loss(False)))(p, x)
    for a, b in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# -- dispatch -----------------------------------------------------------------


def _spied(monkeypatch):
    calls = {"prefill": 0, "decode": 0}
    real_p, real_d = kops.flash_attention, kops.flash_decode

    def spy_p(*a, **kw):
        calls["prefill"] += 1
        return real_p(*a, **kw)

    def spy_d(*a, **kw):
        calls["decode"] += 1
        return real_d(*a, **kw)

    monkeypatch.setattr(kops, "flash_attention", spy_p)
    monkeypatch.setattr(kops, "flash_decode", spy_d)
    return calls


def _attn(p, x, lc, *, flash=True, **kw):
    return attn_lib.attention(p, x, n_heads=4, n_kv=2, head_dim=8,
                              lin_cfg=lc, causal=True, flash=flash, **kw)


def test_dispatch_routes_and_fallbacks(monkeypatch):
    from jax.sharding import Mesh
    from repro.core import factory
    from repro.sharding import ctx as shard_ctx

    lc = factory.DENSE
    p = attn_lib.init_attention(KEY, 32, 4, 2, 8, lc)
    x = jax.random.normal(KEY, (2, 8, 32))
    monkeypatch.setenv("REPRO_KERNEL_ATTN", "flash")
    calls = _spied(monkeypatch)

    # positive control: plain forward routes to the prefill kernel
    _attn(p, x, lc)
    assert calls["prefill"] == 1

    # cache prefill routes to the prefill kernel; decode to the decode one
    cache = attn_lib.init_kv_cache(2, 16, 2, 8, jnp.float32)
    _, c = _attn(p, x, lc, cache=cache)
    assert calls["prefill"] == 2
    _attn(p, x[:, :1], lc, cache=c)
    assert calls["decode"] == 1

    # cross-attention falls back (separate K/V positions, no kernel path)
    _attn(p, x, lc, kv_input=jax.random.normal(KEY, (2, 12, 32)))
    # PR 8: an active sharding context KEEPS the kernel route (the TP
    # wrappers in kernels/tp.py run the same grids per shard) — only the
    # REPRO_KERNEL_TP=off hatch demotes it to the einsum path
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    with shard_ctx.activation_sharding(mesh, dp=("data",), model="model"):
        _attn(p, x, lc)
        assert calls["prefill"] == 3
        monkeypatch.setenv("REPRO_KERNEL_TP", "off")
        _attn(p, x, lc)
        assert calls["prefill"] == 3
        monkeypatch.delenv("REPRO_KERNEL_TP")
    # non-contiguous/per-batch positions on the no-cache path fall back
    _attn(p, x, lc, positions=jnp.tile(jnp.arange(8), (2, 1)))
    # flash=False (the config gate) and REPRO_KERNEL_ATTN=xla fall back
    _attn(p, x, lc, flash=False)
    monkeypatch.setenv("REPRO_KERNEL_ATTN", "xla")
    _attn(p, x, lc)
    assert calls == {"prefill": 3, "decode": 1}


def test_attn_route_env(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_ATTN", "flash")
    assert kops.attn_route() == "flash"
    monkeypatch.setenv("REPRO_KERNEL_ATTN", "xla")
    assert kops.attn_route() == "xla"
    monkeypatch.delenv("REPRO_KERNEL_ATTN")
    assert kops.attn_route() == ("flash" if jax.default_backend() == "tpu"
                                 else "xla")


# -- autotune integration -----------------------------------------------------


def test_flash_tiles_resolved_at_trace_time(cache, monkeypatch):
    """Acceptance spy: tuned flash_prefill/flash_decode tiles are consulted
    AT TRACE TIME of jitted kernel-routed calls."""
    from repro.perf import autotune as at

    S, K, G, h, L = 16, 2, 2, 8, 32
    tuned_p = {"block_b": 8, "block_o": 128, "block_k": 128}
    tuned_d = {"block_b": 1, "block_o": 128, "block_k": 256}
    cache.put(tune_key("flash_prefill", S, K, h, S, d_mid=G), tuned_p,
              us=1.0)
    cache.put(tune_key("flash_decode", 2, K, h, L, d_mid=G), tuned_d,
              us=1.0)

    seen = {}
    real = at.get_tuned_blocks

    def spy(op, *a, **kw):
        out = real(op, *a, **kw)
        seen[op] = dict(out)
        return out

    monkeypatch.setattr(at, "get_tuned_blocks", spy)
    q = jnp.zeros((2, S, K, G, h))
    kv = jnp.zeros((2, S, K, h))
    jax.jit(lambda q, k, v: kops.flash_attention(q, k, v)).lower(q, kv, kv)
    qd = jnp.zeros((2, 1, K, G, h))
    ckv = jnp.zeros((2, L, K, h))
    jax.jit(lambda q, k, v: kops.flash_decode(q, k, v, jnp.int32(3))).lower(
        qd, ckv, ckv)
    assert seen["flash_prefill"] == tuned_p
    assert seen["flash_decode"] == tuned_d


def test_flash_tiles_counter(cache, monkeypatch):
    """Tracing the flash op's backward records one ``flash_tiles`` route
    event (the dq kernel is the one that splits interior from edge tiles),
    counting the tile classes of one (batch, kv-head) grid as the predicate
    does; the forward alone records none."""
    from repro import obs

    S, K, G, h = 512, 2, 2, 16
    cache.put(tune_key("flash_prefill", S, K, h, S, d_mid=G),
              {"block_b": 128, "block_o": 128, "block_k": 128}, us=1.0)
    seen = []
    real = obs.route_event

    def spy(op, route, **args):
        if op == "flash_tiles":
            seen.append((route, args))
        return real(op, route, **args)

    monkeypatch.setattr(obs, "route_event", spy)
    monkeypatch.setenv("REPRO_KERNEL_BWD", "pallas")
    kops._make_flash_attention.cache_clear()
    q = jnp.zeros((1, S, K, G, h))
    kv = jnp.zeros((1, S, K, h))
    jax.jit(lambda q, k, v: kops.flash_attention(q, k, v)).lower(q, kv, kv)
    assert seen == []
    jax.jit(jax.grad(lambda q, k, v: kops.flash_attention(q, k, v).sum())
            ).lower(q, kv, kv)
    assert fa._tile_counts(4, 4, 128, 128, S, True, None) == (6, 6, 4)
    assert seen == [("empty6_interior6_edge4",
                     dict(empty=6, interior=6, edge=4))]
    kops._make_flash_attention.cache_clear()


def test_autotune_sweeps_flash_ops(cache):
    blocks, us = autotune.autotune_dyad(
        "flash_prefill", 32, 2, 16, 32, d_mid=2, iters=1,
        candidates=[{"block_b": 16, "block_o": 128, "block_k": 128},
                    {"block_b": 32, "block_o": 128, "block_k": 128}])
    assert blocks["block_b"] in (16, 32) and us > 0
    blocks, _ = autotune.autotune_dyad(
        "flash_decode", 2, 2, 16, 32, d_mid=2, iters=1,
        candidates=[{"block_b": 1, "block_o": 128, "block_k": 128}])
    assert blocks["block_k"] == 128
    with pytest.raises(ValueError):
        autotune.autotune_dyad("flash_prefill", 32, 2, 16, 32, iters=1)


def test_ensure_tuned_covers_flash(cache, monkeypatch):
    from repro import configs
    from repro.perf.autotune import ensure_tuned_for_model

    cfg = configs.get("qwen3_0_6b", smoke=True)
    assert cfg.flash_attn
    # the sweep only runs when dispatch will consult the tiles: inactive
    # route (CPU default) skips it entirely
    monkeypatch.setenv("REPRO_KERNEL_ATTN", "xla")
    assert ensure_tuned_for_model(cfg, tokens=2, iters=1, seq_len=16,
                                  kv_len=32) == {}
    monkeypatch.setenv("REPRO_KERNEL_ATTN", "flash")
    tuned = ensure_tuned_for_model(cfg, tokens=2, iters=1, seq_len=16,
                                   kv_len=32)
    assert any(k.startswith("flash_prefill") for k in tuned)
    assert any(k.startswith("flash_decode") for k in tuned)
    # window-bounded ring caches clamp the decode kv length
    wcfg = cfg.replace(window=8)
    tuned_w = ensure_tuned_for_model(wcfg, tokens=2, iters=1, kv_len=32)
    assert any("|o8|" in k for k in tuned_w if k.startswith("flash_decode"))
    # non-flash configs stay untouched
    plain = cfg.replace(flash_attn=False)
    assert ensure_tuned_for_model(plain, tokens=2, iters=1, seq_len=16,
                                  kv_len=32) == {}


def test_candidate_blocks_attn_vmem_filter():
    cands = autotune.candidate_blocks_attn(4096, 4096, 128, 8, "float32")
    assert cands and all(
        autotune.vmem_estimate_attn(c["block_b"], c["block_k"], 128, 8,
                                    "float32") <= autotune.VMEM_BUDGET_BYTES
        for c in cands)
    dec = autotune.candidate_blocks_attn(8, 4096, 128, 8, "float32",
                                         decode=True)
    assert dec and all(c["block_b"] == 1 for c in dec)


# -- model-level equivalence --------------------------------------------------


def test_model_flash_vs_xla_routes(monkeypatch):
    """Forward, fresh prefill, and ring decode through the real model:
    the flash route (forced on CPU) must reproduce the einsum route."""
    from repro import configs
    from repro.models import model

    cfg = configs.get("qwen3_0_6b", smoke=True)
    p = model.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 6), 0, cfg.vocab_size)

    def run():
        out = {}
        full, _ = model.forward(cfg, p, {"tokens": toks})
        out["fwd"] = full
        c = model.init_cache(cfg, 2, 10, dtype=jnp.float32)
        lo, c = model.prefill(cfg, p, c, toks)
        out["prefill"] = lo
        tok = jnp.argmax(lo[:, -1:], axis=-1)
        out["decode"], _ = model.decode_step(cfg, p, c, tok)
        return out

    monkeypatch.setenv("REPRO_KERNEL_ATTN", "xla")
    want = run()
    monkeypatch.setenv("REPRO_KERNEL_ATTN", "flash")
    got = run()
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), atol=3e-3,
                                   err_msg=name)


def test_warm_cache_continuation_prefill(monkeypatch):
    """Chunked prompt ingestion: a SECOND prefill on a warm cache
    (idx > 0) must still see the first chunk's keys on the flash route —
    the S < L flash path attends the post-write cache, not just the
    in-flight K/V."""
    from repro import configs
    from repro.models import model

    cfg = configs.get("qwen3_0_6b", smoke=True)
    p = model.init_params(cfg, KEY)
    t1 = jax.random.randint(KEY, (2, 4), 0, cfg.vocab_size)
    t2 = jax.random.randint(jax.random.fold_in(KEY, 1), (2, 3), 0,
                            cfg.vocab_size)

    def run():
        c = model.init_cache(cfg, 2, 12, dtype=jnp.float32)
        _, c = model.prefill(cfg, p, c, t1)
        lo, c = model.prefill(cfg, p, c, t2)
        return lo

    monkeypatch.setenv("REPRO_KERNEL_ATTN", "xla")
    want = run()
    monkeypatch.setenv("REPRO_KERNEL_ATTN", "flash")
    got = run()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-3)
