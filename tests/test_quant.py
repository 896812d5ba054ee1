"""Quantized DYAD serving: the repro.quant codec contract, the quantized
mm/ff Pallas kernels vs the fp oracles (through plan_tiles padding at
odd/prime dims), the int8 paged-KV decode path, dispatch/fallback routing
(sidecar presence x REPRO_KERNEL_QUANT x TP context), and the autotune
key/vmem plumbing — all in interpret mode."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs, quant
from repro.core import factory
from repro.kernels import dyad_mm, ops, ref
from repro.layers import attention as attn_lib
from repro.layers import mlp as mlp_lib
from repro.models import model
from repro.perf import autotune
from repro.perf.autotune import tune_key
from repro.serve import ContinuousBatchingEngine

KEY = jax.random.PRNGKey(0)

QDTYPES = ["int8", "fp8"]


def _w(i, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape, dtype)


def _dq(wq, ws):
    return quant.dequant(wq, ws, axis=-1)


# -- codec contract -----------------------------------------------------------


@pytest.mark.parametrize("dtype", QDTYPES)
def test_per_block_scale_exactness(dtype):
    """The scale contract: one fp32 scale per (block, out_row) over the
    contracted axis, scale = max|w| / qmax + eps, payload within half a
    step of the original (int8) — and every payload value representable."""
    w = _w(1, (3, 17, 29))
    wq, ws = quant.quantize_dyad_weight(w, dtype)
    assert wq.shape == w.shape and ws.shape == (3, 17)
    assert ws.dtype == jnp.float32
    qmax = 127.0 if dtype == "int8" else 448.0
    want = np.max(np.abs(np.asarray(w)), axis=-1) / qmax + 1e-12
    np.testing.assert_allclose(np.asarray(ws), want, rtol=1e-6)
    err = np.abs(np.asarray(_dq(wq, ws)) - np.asarray(w))
    if dtype == "int8":
        # round-to-nearest: at most half a quantization step per element
        assert np.all(err <= 0.5 * np.asarray(ws)[..., None] + 1e-7)
    else:
        assert np.max(err / np.asarray(ws)[..., None]) < 32.0  # fp8 mantissa


def test_quantize_dyad_weight_shape_guard():
    with pytest.raises(ValueError, match="DYAD"):
        quant.quantize_dyad_weight(_w(1, (8, 8)))
    with pytest.raises(ValueError, match="unknown quantization dtype"):
        quant.resolve_dtype("int4")


def test_quantize_params_sidecars_and_stacked():
    """quantize_params adds w1_q/w1_s/w2_q/w2_s SIDECARS (originals
    retained) to every DYAD module — including layer-STACKED 4-D weights,
    whose scales keep the leading layer axis for lax.scan slicing."""
    lc = factory.LinearCfg(impl="dyad", n_dyad=2, variant="it")
    p = mlp_lib.init_mlp(KEY, 16, 32, lc, act="gelu")
    q = quant.quantize_params(p, "int8")
    assert quant.ff_quantized(q) and not quant.ff_quantized(p)
    np.testing.assert_array_equal(np.asarray(q["up"]["w1"]),
                                  np.asarray(p["up"]["w1"]))
    assert q["up"]["w1_q"].dtype == jnp.int8
    stacked = {"mlp": {"up": {"w1": _w(1, (3, 2, 8, 8)),
                              "w2": _w(2, (3, 2, 8, 8))}}}
    qs = quant.quantize_params(stacked)
    assert qs["mlp"]["up"]["w1_s"].shape == (3, 2, 8)
    # per-layer slices match independently-quantized layers
    lone_q, lone_s = quant.quantize_dyad_weight(stacked["mlp"]["up"]["w1"][1])
    np.testing.assert_array_equal(np.asarray(qs["mlp"]["up"]["w1_q"][1]),
                                  np.asarray(lone_q))
    np.testing.assert_allclose(np.asarray(qs["mlp"]["up"]["w1_s"][1]),
                               np.asarray(lone_s), rtol=1e-6)


def test_compress_reexports_shared_codec():
    """The gradient compressor's codec IS repro.quant's (satellite:
    single implementation)."""
    from repro.optim import compress

    assert compress._quant_int8 is quant.quant_int8
    assert compress._dequant_int8 is quant.dequant_int8


# -- quantized mm kernels vs oracle -------------------------------------------

# (B, n, d_in, d_out): healthy, odd/prime through plan_tiles padding
MM_SHAPES = [(16, 4, 32, 32), (9, 3, 70, 130), (7, 2, 129, 67)]


@pytest.mark.parametrize("variant", ["it", "ot", "dt"])
@pytest.mark.parametrize("B,n,d_in,d_out", MM_SHAPES)
def test_quant_mm_matches_dequant_oracle(variant, B, n, d_in, d_out):
    """The in-kernel epilogue-multiply dequant must equal running the
    einsum oracle on EXPLICITLY dequantized weights — the scale is
    constant along the contraction, so the factorization is exact."""
    w1, w2 = _w(1, (n, d_out, d_in)), _w(2, (n, d_out, d_in))
    w1q, s1 = quant.quantize_dyad_weight(w1)
    w2q, s2 = quant.quantize_dyad_weight(w2)
    x = _w(3, (B, n * d_in))
    want = ref.dyad_mm_ref(x, _dq(w1q, s1), _dq(w2q, s2), variant=variant)
    got = ops.dyad_mm_quant(x, w1q, w2q, s1, s2, variant=variant)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", QDTYPES)
@pytest.mark.parametrize("act", ["gelu", "swiglu"])
def test_quant_ff_megakernel_matches_dequant_oracle(act, dtype):
    n, d_in, d_ffb, d_out = 2, 24, 37, 24        # odd hidden: j padding
    gated = act == "swiglu"
    names = ("wg1", "wg2", "wu1", "wu2") if gated else ("wu1", "wu2")
    ws = {nm: _w(i, (n, d_ffb, d_in)) for i, nm in enumerate(names)}
    ws["wd1"], ws["wd2"] = _w(7, (n, d_out, d_ffb)), _w(8, (n, d_out, d_ffb))
    qs = {nm: quant.quantize_dyad_weight(w, dtype) for nm, w in ws.items()}
    x = _w(9, (6, n * d_in))
    dq = {nm: _dq(*qs[nm]) for nm in qs}
    want = ref.dyad_ff_ref(x, dq["wu1"], dq["wu2"], dq["wd1"], dq["wd2"],
                           dq.get("wg1"), dq.get("wg2"), act=act)
    x1, x2 = ref.block_views(x, n, "it")
    gate_kw = {}
    if gated:
        gate_kw = dict(wg1=qs["wg1"][0], wg2=qs["wg2"][0],
                       sg1=qs["wg1"][1], sg2=qs["wg2"][1])
    z1, z2 = dyad_mm.dyad_ff_fused_q(
        x1, x2, qs["wu1"][0], qs["wu2"][0], qs["wd1"][0], qs["wd2"][0],
        qs["wu1"][1], qs["wu2"][1], qs["wd1"][1], qs["wd2"][1],
        act=act, interpret=True, **gate_kw)
    got = ref.combine(z1, z2, "ot")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_quant_ff_fused_vs_split_route(monkeypatch):
    """REPRO_KERNEL_FF=split composes the quantized mm kernels (up, XLA
    act, down) — same numbers as the quantized megakernel route."""
    lc = factory.LinearCfg(impl="dyad", n_dyad=4, variant="it",
                           use_kernel=True, fuse_ff_kernel=True,
                           quant="int8")
    p = quant.quantize_params(mlp_lib.init_mlp(KEY, 32, 64, lc, act="gelu"))
    x = _w(1, (8, 32))
    monkeypatch.setenv("REPRO_KERNEL_FF", "fused")
    y_fused = ops.dyad_ff_quant(p, x, act="gelu")
    monkeypatch.setenv("REPRO_KERNEL_FF", "split")
    y_split = ops.dyad_ff_quant(p, x, act="gelu")
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_split),
                               rtol=2e-5, atol=2e-5)


def test_quant_bf16_activations():
    """bf16 activation dataflow is unchanged: int8 payloads (<= 127) cast
    exactly to bf16 inside the kernel, the fp32 scale rides the epilogue."""
    lc = factory.LinearCfg(impl="dyad", n_dyad=4, variant="it",
                           use_kernel=True, fuse_ff_kernel=True,
                           quant="int8")
    p = quant.quantize_params(mlp_lib.init_mlp(KEY, 32, 64, lc, act="gelu"))
    x = _w(1, (8, 32)).astype(jnp.bfloat16)
    y = ops.dyad_ff_quant(p, x, act="gelu")
    assert y.dtype == jnp.bfloat16
    want = ref.dyad_ff_ref(
        x.astype(jnp.float32), _dq(p["up"]["w1_q"], p["up"]["w1_s"]),
        _dq(p["up"]["w2_q"], p["up"]["w2_s"]),
        _dq(p["down"]["w1_q"], p["down"]["w1_s"]),
        _dq(p["down"]["w2_q"], p["down"]["w2_s"]), act="gelu")
    scale = max(float(np.max(np.abs(np.asarray(want)))), 1.0)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(want),
                               rtol=5e-2, atol=5e-2 * scale)


# -- dispatch & fallback map --------------------------------------------------


def _quant_lc(**kw):
    return factory.LinearCfg(impl="dyad", n_dyad=4, variant="it",
                             use_kernel=True, fuse_ff_kernel=True,
                             quant="int8", **kw)


def test_apply_mlp_quant_dispatch_and_fallbacks(monkeypatch):
    """The routing contract: quant cfg + sidecars -> quantized kernels;
    missing sidecars (training params) -> fp megakernel, SAME numbers as
    no-quant cfg; REPRO_KERNEL_QUANT=off -> BIT-identical fp route."""
    lc = _quant_lc()
    p_fp = mlp_lib.init_mlp(KEY, 32, 64, lc, act="gelu")
    p_q = quant.quantize_params(p_fp)
    x = _w(1, (2, 5, 32))

    obs.reset_route_counts()
    assert mlp_lib._ff_quant_ready(p_q, lc, "gelu")
    assert obs.routes_snapshot() == {"ff_quant:int8": 1}
    y_q = mlp_lib.apply_mlp(p_q, x, lc, act="gelu")
    y_fp = mlp_lib.apply_mlp(p_fp, x, lc.replace(quant=None), act="gelu")
    # int8 weights: close to fp, not equal (proves the quant route ran)
    scale = max(float(np.max(np.abs(np.asarray(y_fp)))), 1.0)
    np.testing.assert_allclose(np.asarray(y_q), np.asarray(y_fp),
                               rtol=2e-2, atol=2e-2 * scale)
    assert np.max(np.abs(np.asarray(y_q) - np.asarray(y_fp))) > 0

    # no sidecars -> fp fallback, identical to the unquantized cfg
    obs.reset_route_counts()
    assert not mlp_lib._ff_quant_ready(p_fp, lc, "gelu")
    assert obs.routes_snapshot() == {"ff_quant:fp_fallback": 1}
    np.testing.assert_array_equal(
        np.asarray(mlp_lib.apply_mlp(p_fp, x, lc, act="gelu")),
        np.asarray(y_fp))

    # escape hatch: sidecars PRESENT but env off -> bit-identical fp route
    monkeypatch.setenv("REPRO_KERNEL_QUANT", "off")
    obs.reset_route_counts()
    assert not mlp_lib._ff_quant_ready(p_q, lc, "gelu")
    assert obs.routes_snapshot() == {"ff_quant:off": 1}
    np.testing.assert_array_equal(
        np.asarray(mlp_lib.apply_mlp(p_q, x, lc, act="gelu")),
        np.asarray(y_fp))


def test_quant_dispatch_under_sharding_ctx():
    """A sharding context keeps the quant route live (single-device mesh:
    the TP wrapper's tp==1 path delegates straight to the kernel — same
    numbers as the uncontexted dispatch)."""
    from jax.sharding import Mesh
    from repro.kernels import tp as ktp
    from repro.sharding import ctx as shard_ctx

    lc = _quant_lc()
    p = quant.quantize_params(mlp_lib.init_mlp(KEY, 32, 64, lc, act="gelu"))
    x = _w(1, (8, 32))
    y_plain = mlp_lib.apply_mlp(p, x, lc, act="gelu")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    with shard_ctx.activation_sharding(mesh, dp=("data",), model="model"):
        obs.reset_route_counts()
        assert mlp_lib._ff_quant_ready(p, lc, "gelu")
        assert obs.routes_snapshot() == {"ff_quant:int8": 1}
        ctx = shard_ctx.current()
        y_tp = ktp.dyad_ff_quant_tp(p, x, act="gelu", ctx=ctx)
    np.testing.assert_array_equal(np.asarray(y_tp), np.asarray(y_plain))


def test_factory_apply_quant_single_mm():
    """Non-ff scope: factory.apply streams a single quantized dyad_mm when
    the module carries sidecars (counted under mm_quant)."""
    lc = factory.LinearCfg(impl="dyad", n_dyad=4, variant="ot",
                           use_kernel=True, quant="int8")
    p = quant.quantize_params(
        factory.init(KEY, 32, 48, lc, site="ff", bias=False))
    x = _w(1, (6, 32))
    obs.reset_route_counts()
    y = factory.apply(p, x, lc, site="ff")
    assert obs.routes_snapshot()["mm_quant:int8"] == 1
    want = ref.dyad_mm_ref(x, _dq(p["w1_q"], p["w1_s"]),
                           _dq(p["w2_q"], p["w2_s"]), variant="ot")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_linear_cfg_quant_spec_tokens():
    assert configs.linear_cfg("dyad_it_4_kernel_ffused_w8").quant == "int8"
    assert configs.linear_cfg("dyad_it_4_kernel_ffused_wfp8").quant == "fp8"
    assert configs.linear_cfg("dyad_it_4_kernel_ffused").quant is None


# -- int8 paged KV ------------------------------------------------------------


def test_paged_kv_cache_quant_layout():
    c = attn_lib.init_paged_kv_cache(2, 32, 2, 16, page_size=8, n_pages=9,
                                     quant="int8")
    assert c["pages_k"].dtype == jnp.int8
    assert c["scales_k"].shape == (9, 8, 2)
    assert c["scales_k"].dtype == jnp.float32
    with pytest.raises(ValueError, match="int8"):
        attn_lib.init_paged_kv_cache(2, 32, 2, 16, page_size=8, n_pages=9,
                                     quant="fp8")
    # unquantized layout unchanged
    d = attn_lib.init_paged_kv_cache(2, 32, 2, 16, page_size=8, n_pages=9)
    assert "scales_k" not in d and d["pages_k"].dtype == jnp.bfloat16


def test_quant_paged_decode_kernel_vs_dequant_oracle():
    """The in-kernel per-token-row dequant (scores scaled per key column,
    probabilities scaled per row before PV) vs the same kernel on
    explicitly dequantized pools."""
    from repro.kernels import flash_attn as fa

    rng = np.random.default_rng(0)
    B, K, G, h, P, NB = 3, 2, 2, 64, 8, 5
    NP = 1 + B * NB
    q = jnp.asarray(rng.normal(size=(B, K, G, h)), jnp.float32)
    bt = np.arange(1, NP, dtype=np.int32).reshape(B, NB)
    idx = np.array([13, 37, 29], np.int32)
    kq, ks = quant.quantize_kv_rows(
        jnp.asarray(rng.normal(size=(NP, P, K, h)), jnp.float32))
    vq, vs = quant.quantize_kv_rows(
        jnp.asarray(rng.normal(size=(NP, P, K, h)), jnp.float32))
    for window in (None, 7):
        o_q = fa.flash_decode_paged(
            q, kq, vq, jnp.asarray(bt), jnp.asarray(idx), scales_k=ks,
            scales_v=vs, window=window, interpret=True)
        o_f = fa.flash_decode_paged(
            q, _dq(kq, ks).astype(jnp.float32), _dq(vq, vs).astype(
                jnp.float32), jnp.asarray(bt), jnp.asarray(idx),
            window=window, interpret=True)
        np.testing.assert_allclose(np.asarray(o_q), np.asarray(o_f),
                                   rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="together"):
        fa.flash_decode_paged(q, kq, vq, jnp.asarray(bt), jnp.asarray(idx),
                              scales_k=ks, interpret=True)


@functools.lru_cache(maxsize=None)
def _small_model():
    cfg = configs.get("qwen3_0_6b", smoke=True)
    return cfg, model.init_params(cfg, KEY)


def _engine_tokens(cfg, params, **kw):
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=24,
                                   page_size=4, **kw)
    rng = np.random.default_rng(7)
    uids = [eng.submit(rng.integers(0, cfg.vocab_size, size=(s,)), 5)
            for s in (9, 6)]
    out = eng.run()
    return [out[u] for u in uids]


def test_int8_kv_token_match_real_model(monkeypatch):
    """Greedy decode on the real smoke model: int8 paged KV (flash decode
    kernel dequantizing in-VMEM) must reproduce the fp cache's tokens."""
    monkeypatch.setenv("REPRO_KERNEL_ATTN", "flash")
    cfg, params = _small_model()
    obs.reset_route_counts()
    got = _engine_tokens(cfg.replace(kv_quant="int8"), params)
    assert obs.routes_snapshot().get("kv_quant:int8", 0) >= 1
    want = _engine_tokens(cfg, params)
    assert got == want


def test_kv_quant_dense_view_fallback():
    """Without the flash route (einsum oracle path) the quantized pool is
    dequantized in XLA after the dense-view gather — tokens still match
    the fp cache."""
    cfg, params = _small_model()
    got = _engine_tokens(cfg.replace(kv_quant="int8"), params)
    want = _engine_tokens(cfg, params)
    assert got == want


def test_kv_quant_env_escape_hatch(monkeypatch):
    """REPRO_KERNEL_QUANT=off keeps the paged pools in the engine dtype:
    no scale leaves, bit-identical to a config without kv_quant."""
    monkeypatch.setenv("REPRO_KERNEL_QUANT", "off")
    cfg, params = _small_model()
    eng = ContinuousBatchingEngine(cfg.replace(kv_quant="int8"), params,
                                   n_slots=2, max_len=16, page_size=4)
    assert "scales_k" not in eng.cache["kv"]
    assert eng.cache["kv"]["pages_k"].dtype != jnp.int8


# -- autotune plumbing --------------------------------------------------------


def test_quant_tune_keys_distinct():
    """_w8 op keys carry the PAYLOAD dtype — int8 and fp8 sweeps must not
    collide with each other or with the bf16 kernel's entries."""
    base = tune_key("dyad_ff_fused", 32, 4, 8, 8, "bfloat16", d_mid=16)
    k8 = tune_key("dyad_ff_fused_w8", 32, 4, 8, 8, "int8", d_mid=16)
    kf8 = tune_key("dyad_ff_fused_w8", 32, 4, 8, 8, "float8_e4m3fn",
                   d_mid=16)
    assert len({base, k8, kf8}) == 3
    assert "int8" in k8 and "float8_e4m3fn" in kf8


def test_dtype_bytes_fp8_and_unknown():
    assert autotune._dtype_bytes("float8_e4m3fn") == 1
    assert autotune._dtype_bytes("int8") == 1
    assert autotune._dtype_bytes("bfloat16") == 2
    with pytest.raises(ValueError, match="unknown dtype"):
        autotune._dtype_bytes("float4_e2m1")


def test_vmem_estimate_quant_weights_cheaper():
    """Quantized weight streams price at payload bytes (+ fp32 scale
    tiles): the estimate must drop vs the same tiles at bf16 weights."""
    full = autotune.vmem_estimate_ff(64, 128, 128, 256, "bfloat16")
    q = autotune.vmem_estimate_ff(64, 128, 128, 256, "bfloat16",
                                  w_dtype="int8")
    assert q < full
    fullm = autotune.vmem_estimate(64, 128, 128, "bfloat16")
    qm = autotune.vmem_estimate(64, 128, 128, "bfloat16", w_dtype="int8")
    assert qm < fullm


def test_autotune_quant_op_runs(tmp_path):
    """autotune_dyad on a _w8 op quantizes its sweep weights and lands a
    cache entry under the payload-dtype key."""
    from repro.perf.autotune import BlockCache

    c = BlockCache(user_path=str(tmp_path / "b.json"),
                   defaults_path=str(tmp_path / "d.json"))
    autotune.reset_cache(c)
    try:
        best, _ = autotune.autotune_dyad(
            "dyad_mm_blocks_w8", 8, 2, 16, 16, dtype="int8", iters=1,
            candidates=[{"block_b": 8, "block_o": 128, "block_k": 128}])
        assert best == {"block_b": 8, "block_o": 128, "block_k": 128}
        key = tune_key("dyad_mm_blocks_w8", 8, 2, 16, 16, "int8")
        assert c.get(key) is not None
    finally:
        autotune.reset_cache(None)


def test_ensure_tuned_covers_quant_ops(tmp_path, monkeypatch):
    """A quant-configured model tunes the _w8 twins of its mm and ff ops."""
    from repro.perf.autotune import BlockCache, ensure_tuned_for_model

    c = BlockCache(user_path=str(tmp_path / "b.json"),
                   defaults_path=str(tmp_path / "d.json"))
    autotune.reset_cache(c)
    try:
        cfg, _ = _small_model()
        cfg = cfg.replace(linear=configs.linear_cfg(
            "dyad_it_4_kernel_ffused_w8"))
        tuned = ensure_tuned_for_model(cfg, tokens=4, iters=1)
        w8 = [k for k in tuned if "_w8|" in k]
        assert any(k.startswith("dyad_ff_fused") for k in w8)
        assert all("|int8|" in k for k in w8)
        # escape hatch: env off tunes NO quant twins
        monkeypatch.setenv("REPRO_KERNEL_QUANT", "off")
        c2 = BlockCache(user_path=str(tmp_path / "b2.json"),
                        defaults_path=str(tmp_path / "d2.json"))
        autotune.reset_cache(c2)
        tuned = ensure_tuned_for_model(cfg, tokens=4, iters=1)
        assert not any("_w8|" in k for k in tuned)
    finally:
        autotune.reset_cache(None)
