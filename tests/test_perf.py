"""Performance subsystem: autotune cache, bench records, regression gate."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.perf import autotune, compare
from repro.perf.autotune import (BlockCache, DEFAULT_BLOCKS, autotune_dyad,
                                 candidate_blocks, get_tuned_blocks,
                                 tune_key, vmem_estimate)
from repro.perf.record import (BenchResult, Recorder, current_recorder,
                               load_bench, recording)
from repro.perf.registry import available_suites, register, run_suite


@pytest.fixture
def cache(tmp_path):
    """Isolated BlockCache installed as the process singleton."""
    c = BlockCache(user_path=str(tmp_path / "blocks.json"),
                   defaults_path=str(tmp_path / "defaults.json"))
    autotune.reset_cache(c)
    yield c
    autotune.reset_cache(None)


# -- BenchResult / Recorder ---------------------------------------------------


def test_bench_result_round_trip():
    r = BenchResult(name="ff_fwd", us_per_call=123.456, suite="ff_timing",
                    shape=(2048, 768), dtype="float32",
                    metrics={"ratio": 2.1, "flops": 1e9, "verdict": "PASS"})
    r2 = BenchResult.from_dict(json.loads(json.dumps(r.to_dict())))
    assert r2.name == r.name
    assert r2.shape == (2048, 768)
    assert r2.metrics == r.metrics
    assert abs(r2.us_per_call - r.us_per_call) < 1e-3


def test_bench_result_rejects_malformed():
    with pytest.raises(ValueError):
        BenchResult.from_dict({"us_per_call": 1.0})    # no name


def test_recorder_writes_and_loads(tmp_path):
    rec = Recorder("unit", out_dir=str(tmp_path))
    rec.add("b_cell", 20.0, shape=(4, 4), tok_s=100)
    rec.add("a_cell", 10.0)
    path = rec.write()
    assert os.path.basename(path) == "BENCH_unit.json"
    doc = load_bench(path)
    assert doc["suite"] == "unit"
    assert [r.name for r in doc["results"]] == ["a_cell", "b_cell"]  # sorted
    assert doc["results"][1].metrics["tok_s"] == 100


def test_recording_context_routes_emit(tmp_path):
    from benchmarks.common import emit

    assert current_recorder() is None
    with recording("ctx", str(tmp_path)) as rec:
        emit("x", 1.5, ratio=2.0)
        emit("y", 2.5, "legacy=3.5;tag=str")     # legacy derived string
    assert current_recorder() is None
    by = {r.name: r for r in rec.results}
    assert by["x"].metrics["ratio"] == 2.0
    assert by["y"].metrics["legacy"] == 3.5
    assert by["y"].metrics["tag"] == "str"


def test_registry_runs_suite(tmp_path):
    from benchmarks.common import emit

    @register("unit_suite")
    def _suite():
        emit("one_cell", 42.0, ratio=1.0)

    assert "unit_suite" in available_suites()
    rec = run_suite("unit_suite", out_dir=str(tmp_path))
    assert os.path.exists(rec.path)
    assert rec.results[0].name == "one_cell"


# -- autotune cache -----------------------------------------------------------


def test_cache_miss_returns_default(cache):
    assert cache.get(tune_key("dyad_mm_blocks", 8, 2, 64, 64)) is None
    blocks = get_tuned_blocks("dyad_mm_blocks", 8, 2, 64, 64)
    assert blocks == DEFAULT_BLOCKS


def test_cache_put_then_hit(cache):
    key = tune_key("dyad_mm_blocks", 8, 2, 64, 64)
    tuned = {"block_b": 8, "block_o": 64, "block_k": 64}
    cache.put(key, tuned, us=12.3)
    assert get_tuned_blocks("dyad_mm_blocks", 8, 2, 64, 64) == tuned
    # persisted: a fresh cache over the same file sees it
    fresh = BlockCache(user_path=cache.user_path,
                       defaults_path=cache.defaults_path)
    assert fresh.get(key) == tuned
    # B is bucketed: B=7 and B=8 share an entry
    assert get_tuned_blocks("dyad_mm_blocks", 7, 2, 64, 64) == tuned


def test_cache_corrupt_file_recovery(cache):
    os.makedirs(os.path.dirname(cache.user_path), exist_ok=True)
    with open(cache.user_path, "w") as f:
        f.write("{not json!")
    with pytest.warns(UserWarning, match="corrupt"):
        assert cache.get(tune_key("dyad_mm_blocks", 8, 2, 64, 64)) is None
    # put() recovers: rewrites a valid file on top of the corrupt one
    key = tune_key("dyad_mm_blocks", 8, 2, 64, 64)
    cache.put(key, DEFAULT_BLOCKS, us=1.0)
    fresh = BlockCache(user_path=cache.user_path,
                       defaults_path=cache.defaults_path)
    assert fresh.get(key) == DEFAULT_BLOCKS


def test_cache_ignores_malformed_entry(cache):
    key = tune_key("dyad_mm_blocks", 8, 2, 64, 64)
    cache.user[key] = {"blocks": {"block_b": "big"}}   # wrong types
    assert cache.get(key) is None


def test_candidate_blocks_respect_vmem_budget():
    cands = candidate_blocks(4096, 4, 4096, 4096)
    assert cands, "sweep must produce candidates"
    assert any(c == DEFAULT_BLOCKS for c in cands)
    for c in cands:
        assert vmem_estimate(c["block_b"], c["block_o"], c["block_k"],
                             "float32") <= autotune.VMEM_BUDGET_BYTES


def test_autotune_sweep_caches_and_short_circuits(cache):
    cands = [DEFAULT_BLOCKS, {"block_b": 16, "block_o": 32, "block_k": 32}]
    blocks, us = autotune_dyad("dyad_mm_blocks", 16, 2, 32, 32,
                               candidates=cands, iters=1, warmup=0,
                               cache=cache)
    assert blocks in cands and us > 0
    # second call is a cache hit: passing impossible candidates proves the
    # sweep didn't run again
    blocks2, _ = autotune_dyad("dyad_mm_blocks", 16, 2, 32, 32,
                               candidates=[], iters=1, cache=cache)
    assert blocks2 == blocks


def test_tuned_blocks_picked_up_by_kernel(cache):
    """End-to-end: a cache entry changes what dyad_mm_blocks resolves and
    the kernel still computes the exact product with those tiles."""
    from repro.kernels.dyad_mm import dyad_mm_blocks, resolve_blocks

    B, n, d_in, d_out = 16, 2, 64, 64
    tuned = {"block_b": 8, "block_o": 32, "block_k": 16}
    cache.put(tune_key("dyad_mm_blocks", B, n, d_in, d_out), tuned, us=1.0)
    assert resolve_blocks("dyad_mm_blocks", B, n, d_in, d_out,
                          jnp.float32) == (8, 32, 16)
    # explicit arguments beat the cache
    assert resolve_blocks("dyad_mm_blocks", B, n, d_in, d_out, jnp.float32,
                          block_o=64) == (8, 64, 16)

    k = jax.random.PRNGKey(0)
    x1 = jax.random.normal(k, (B, n, d_in))
    x2 = jax.random.normal(jax.random.fold_in(k, 1), (B, n, d_in))
    w1 = jax.random.normal(jax.random.fold_in(k, 2), (n, d_out, d_in))
    w2 = jax.random.normal(jax.random.fold_in(k, 3), (n, d_out, d_in))
    want = (jnp.einsum("bgk,gok->bgo", x1, w1)
            + jnp.einsum("bgk,gok->bgo", x2, w2))
    got = dyad_mm_blocks(x1, x2, w1, w2, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-5)


# -- autotune: backward op keys -----------------------------------------------


@pytest.mark.parametrize("op", ["dyad_mm_dgrad", "dyad_mm_dgrad_two",
                                "dyad_mm_wgrad"])
def test_autotune_bwd_op_sweep_caches_and_short_circuits(op, cache):
    cands = [DEFAULT_BLOCKS, {"block_b": 16, "block_o": 32, "block_k": 32}]
    blocks, us = autotune_dyad(op, 16, 2, 32, 32, candidates=cands,
                               iters=1, warmup=0, cache=cache)
    assert blocks in cands and us > 0
    entry = cache.get_entry(tune_key(op, 16, 2, 32, 32))
    assert entry is not None and entry["op"] == op
    # cache hit short-circuits: impossible candidates prove no re-sweep
    blocks2, _ = autotune_dyad(op, 16, 2, 32, 32, candidates=[],
                               iters=1, cache=cache)
    assert blocks2 == blocks


def test_bwd_op_keys_are_distinct_from_fwd(cache):
    """dgrad/wgrad tiles must never collide with the forward's: the same
    shape tunes per OP."""
    keys = {tune_key(op, 32, 4, 64, 128)
            for op in ("dyad_mm_blocks", "dyad_mm_dgrad", "dyad_mm_wgrad")}
    assert len(keys) == 3
    cache.put(tune_key("dyad_mm_dgrad", 32, 4, 64, 128),
              {"block_b": 8, "block_o": 64, "block_k": 128})
    assert get_tuned_blocks("dyad_mm_blocks", 32, 4, 64, 128) == DEFAULT_BLOCKS
    assert get_tuned_blocks("dyad_mm_dgrad", 32, 4, 64, 128)["block_o"] == 64


def test_tp_shard_keys_are_distinct_from_single_device(cache):
    """A per-shard shape tuned under tensor parallelism must never collide
    with a single-device entry for the same dims: the ambient tp_shards
    count suffixes the key (|tpN), and tp=1 keys keep the legacy spelling
    so every committed cache entry stays valid."""
    from repro.perf.autotune import tp_shards

    base = tune_key("dyad_ff_fused", 256, 4, 64, 64, d_mid=128)
    assert "|tp" not in base                       # legacy spelling intact
    with tp_shards(2):
        k2 = tune_key("dyad_ff_fused", 256, 4, 64, 64, d_mid=128)
    with tp_shards(4):
        k4 = tune_key("dyad_ff_fused", 256, 4, 64, 64, d_mid=128)
    assert len({base, k2, k4}) == 3 and "|tp2|" in k2 and "|tp4|" in k4
    # explicit tp= overrides the ambient count; tp=1 is the no-suffix case
    assert tune_key("dyad_ff_fused", 256, 4, 64, 64, d_mid=128, tp=1) == base
    with tp_shards(8):
        assert tune_key("dyad_ff_fused", 256, 4, 64, 64,
                        d_mid=128, tp=2) == k2
    # lookups route through the same ambient tag: a tp2 entry must be
    # invisible to single-device lookups of the same shape (and vice versa)
    cache.put(k2, {"block_b": 8, "block_o": 64, "block_k": 128})
    assert get_tuned_blocks("dyad_ff_fused", 256, 4, 64, 64,
                            d_mid=128) != {"block_b": 8, "block_o": 64,
                                           "block_k": 128}
    with tp_shards(2):
        assert get_tuned_blocks("dyad_ff_fused", 256, 4, 64, 64,
                                d_mid=128)["block_o"] == 64


def test_bwd_cache_corrupt_file_recovery(cache):
    """Corrupt user cache: bwd key lookups degrade to defaults, and the
    next put() rewrites a valid file containing the bwd entry."""
    os.makedirs(os.path.dirname(cache.user_path), exist_ok=True)
    with open(cache.user_path, "w") as f:
        f.write("{broken")
    with pytest.warns(UserWarning, match="corrupt"):
        assert get_tuned_blocks("dyad_mm_wgrad", 8, 2, 64, 64) == DEFAULT_BLOCKS
    key = tune_key("dyad_mm_wgrad", 8, 2, 64, 64)
    tuned = {"block_b": 8, "block_o": 64, "block_k": 64}
    cache.put(key, tuned, us=3.0)
    fresh = BlockCache(user_path=cache.user_path,
                       defaults_path=cache.defaults_path)
    assert fresh.get(key) == tuned


def test_ensure_tuned_include_bwd(cache):
    """include_bwd=True tunes the variant's dgrad op + wgrad alongside the
    forward for every model dyad shape."""
    from repro import configs
    from repro.perf.autotune import ensure_tuned_for_model

    lin = configs.linear_cfg("dyad_it_4_kernel")
    cfg = configs.get("qwen3_0_6b", smoke=True, linear=lin)
    tuned = ensure_tuned_for_model(cfg, tokens=16, iters=1, include_bwd=True)
    ops_seen = {k.split("|")[0] for k in tuned}
    assert ops_seen == {"dyad_mm_blocks", "dyad_mm_dgrad_two",
                        "dyad_mm_wgrad"}
    # every entry landed in the cache
    for k in tuned:
        assert cache.get(k) is not None


def test_tuned_bwd_tiles_resolved_in_value_and_grad_trace(cache, monkeypatch):
    """Tuned dgrad/wgrad tiles are consulted AT TRACE TIME of a jitted
    value_and_grad over the kernel-routed op (pallas route forced so the
    backward actually resolves tiles off-TPU)."""
    from repro.kernels import ops as kops
    from repro.perf import autotune as at

    B, n, d_in, d_out = 16, 2, 64, 64
    tuned = {"block_b": 8, "block_o": 32, "block_k": 32}
    for op in ("dyad_mm_dgrad_two", "dyad_mm_wgrad"):
        cache.put(tune_key(op, B, n, d_in, d_out), tuned, us=1.0)

    seen = {}
    real = at.get_tuned_blocks

    def spy(op, *a, **kw):
        out = real(op, *a, **kw)
        seen[op] = dict(out)
        return out

    monkeypatch.setattr(at, "get_tuned_blocks", spy)
    monkeypatch.setenv("REPRO_KERNEL_BWD", "pallas")

    x = jax.random.normal(jax.random.PRNGKey(0), (B, n * d_in))
    w = jax.random.normal(jax.random.PRNGKey(1), (n, d_out, d_in))

    def loss(x, w1, w2):
        return (kops.dyad_mm(x, w1, w2, variant="it") ** 2).sum()

    # trace (no execution needed): tile resolution happens here
    jax.jit(jax.value_and_grad(loss)).lower(x, w, w + 1)
    assert seen["dyad_mm_dgrad_two"] == tuned
    assert seen["dyad_mm_wgrad"] == tuned
    assert "dyad_mm_blocks" in seen        # forward resolved too


# -- autotune: trace-time memo ------------------------------------------------


def test_get_tuned_blocks_memoized(cache):
    """Repeated trace-time lookups hit the in-process memo instead of
    re-walking the JSON-backed cache layers."""
    before = autotune.memo_counts()
    blocks = get_tuned_blocks("dyad_mm_blocks", 8, 2, 64, 64)
    mid = autotune.memo_counts()
    assert mid["misses"] == before["misses"] + 1
    for _ in range(5):
        assert get_tuned_blocks("dyad_mm_blocks", 8, 2, 64, 64) == blocks
    after = autotune.memo_counts()
    assert after["hits"] >= mid["hits"] + 5
    assert after["misses"] == mid["misses"]
    # the memo hands out copies: mutating a result must not poison it
    got = get_tuned_blocks("dyad_mm_blocks", 8, 2, 64, 64)
    got["block_b"] = -1
    assert get_tuned_blocks("dyad_mm_blocks", 8, 2, 64, 64)["block_b"] > 0


def test_get_tuned_blocks_memo_invalidated_by_put(cache):
    """put() must invalidate the memo — freshly tuned tiles have to reach
    the very next trace."""
    key = tune_key("dyad_mm_blocks", 8, 2, 64, 64)
    assert get_tuned_blocks("dyad_mm_blocks", 8, 2, 64, 64) == DEFAULT_BLOCKS
    tuned = {"block_b": 8, "block_o": 64, "block_k": 64}
    cache.put(key, tuned, us=1.0)
    assert get_tuned_blocks("dyad_mm_blocks", 8, 2, 64, 64) == tuned


def test_resolved_blocks_reports_each_key(cache):
    """resolved_blocks() lists the tiles each traced key resolved to, as
    copies, and forgets them when the memo is cleared."""
    assert autotune.resolved_blocks() == {}
    tuned = {"block_b": 8, "block_o": 64, "block_k": 64}
    cache.put(tune_key("dyad_mm_blocks", 8, 2, 64, 64), tuned, us=1.0)
    get_tuned_blocks("dyad_mm_blocks", 8, 2, 64, 64)
    get_tuned_blocks("dyad_mm_wgrad", 8, 2, 64, 64)
    got = autotune.resolved_blocks()
    assert got == {tune_key("dyad_mm_blocks", 8, 2, 64, 64): tuned,
                   tune_key("dyad_mm_wgrad", 8, 2, 64, 64): DEFAULT_BLOCKS}
    got[tune_key("dyad_mm_blocks", 8, 2, 64, 64)]["block_b"] = -1
    assert get_tuned_blocks("dyad_mm_blocks", 8, 2, 64, 64) == tuned
    cache.invalidate()
    assert autotune.resolved_blocks() == {}


# -- autotune: ff megakernel op keys ------------------------------------------


def test_tune_key_carries_d_mid(cache):
    k_ff = tune_key("dyad_ff_fused", 32, 4, 192, 192, d_mid=768)
    assert "|j768|" in k_ff
    assert k_ff != tune_key("dyad_ff_fused", 32, 4, 192, 192, d_mid=384)
    # single-matmul keys are unchanged by the new field
    assert "|j" not in tune_key("dyad_mm_blocks", 32, 4, 192, 192)


def test_ff_defaults_and_block_j_round_trip(cache):
    ff = get_tuned_blocks("dyad_ff_fused", 8, 2, 64, 64, d_mid=128)
    assert ff == autotune.DEFAULT_FF_BLOCKS and "block_j" in ff
    key = tune_key("dyad_ff_fused", 8, 2, 64, 64, d_mid=128)
    tuned = {"block_b": 8, "block_o": 64, "block_k": 64, "block_j": 128}
    cache.put(key, tuned, us=1.0)
    assert get_tuned_blocks("dyad_ff_fused", 8, 2, 64, 64,
                            d_mid=128) == tuned
    # an entry written before the j axis existed degrades to the default j
    cache.put(key, {"block_b": 8, "block_o": 64, "block_k": 64}, us=1.0)
    got = get_tuned_blocks("dyad_ff_fused", 8, 2, 64, 64, d_mid=128)
    assert got["block_j"] == autotune.DEFAULT_FF_BLOCKS["block_j"]
    assert got["block_b"] == 8


def test_candidate_blocks_ff_respect_vmem_budget():
    for gated in (False, True):
        cands = autotune.candidate_blocks_ff(4096, 4, 1024, 1024, 4096,
                                             gated=gated)
        assert cands
        for c in cands:
            assert autotune.vmem_estimate_ff(
                c["block_b"], c["block_o"], c["block_k"], c["block_j"],
                "float32", gated=gated) <= autotune.VMEM_BUDGET_BYTES
    # the gate's extra weight stream + second hidden accumulator must COST:
    # same tiles estimate strictly higher when gated
    assert (autotune.vmem_estimate_ff(256, 256, 512, 512, "float32", True)
            > autotune.vmem_estimate_ff(256, 256, 512, 512, "float32",
                                        False))


@pytest.mark.parametrize("op", ["dyad_ff_fused", "dyad_ff_fused_swiglu"])
def test_autotune_ff_sweep_caches_and_short_circuits(op, cache):
    cands = [dict(autotune.DEFAULT_FF_BLOCKS),
             {"block_b": 16, "block_o": 32, "block_k": 32, "block_j": 16}]
    blocks, us = autotune_dyad(op, 16, 2, 32, 32, candidates=cands,
                               iters=1, warmup=0, cache=cache, d_mid=48)
    assert blocks in cands and us > 0
    entry = cache.get_entry(tune_key(op, 16, 2, 32, 32, d_mid=48))
    assert entry is not None and entry["op"] == op
    blocks2, _ = autotune_dyad(op, 16, 2, 32, 32, candidates=[],
                               iters=1, cache=cache, d_mid=48)
    assert blocks2 == blocks


def test_autotune_ff_requires_d_mid(cache):
    with pytest.raises(ValueError, match="d_mid"):
        autotune_dyad("dyad_ff_fused", 16, 2, 32, 32, cache=cache)


def test_ensure_tuned_covers_ff_megakernel(cache):
    """A fuse_ff_kernel config tunes the ff op (+ the down dgrad the
    megakernel VJP composes) alongside the per-matmul ops."""
    from repro import configs
    from repro.perf.autotune import ensure_tuned_for_model

    lin = configs.linear_cfg("dyad_it_4_kernel_ffused")
    cfg = configs.get("opt125m", smoke=True, linear=lin, mlp_bias=False)
    tuned = ensure_tuned_for_model(cfg, tokens=16, iters=1, include_bwd=True)
    ops_seen = {k.split("|")[0] for k in tuned}
    assert "dyad_ff_fused" in ops_seen            # opt125m act == relu
    assert "dyad_mm_dgrad" in ops_seen            # OT down dgrad
    for k in tuned:
        assert cache.get(k) is not None
    # a BIASED ff never dispatches the megakernel (mlp._ff_kernel_ready),
    # so the sweep must skip it too — no minutes burned on an unused op
    cfg_b = configs.get("opt125m", smoke=True, linear=lin)   # mlp_bias=True
    tuned_b = ensure_tuned_for_model(cfg_b, tokens=16, iters=1)
    assert not any(k.startswith("dyad_ff_fused") for k in tuned_b)
    # without the flag the ff op is not tuned either
    cfg2 = configs.get("opt125m", smoke=True,
                       linear=configs.linear_cfg("dyad_it_4_kernel"))
    tuned2 = ensure_tuned_for_model(cfg2, tokens=16, iters=1)
    assert not any(k.startswith("dyad_ff_fused") for k in tuned2)


def test_tuned_ff_tiles_resolved_in_trace(cache, monkeypatch):
    """The megakernel resolves its 4-axis tiles from the cache at trace
    time of a jitted fuse_ff_kernel mlp forward."""
    import jax
    from repro.core import factory
    from repro.layers import mlp as mlp_lib
    from repro.perf import autotune as at

    seen = {}
    real = at.get_tuned_blocks

    def spy(op, *a, **kw):
        out = real(op, *a, **kw)
        seen[op] = dict(out)
        return out

    monkeypatch.setattr(at, "get_tuned_blocks", spy)
    lc = factory.LinearCfg(impl="dyad", n_dyad=2, variant="it",
                           use_kernel=True, fuse_ff_kernel=True)
    p = mlp_lib.init_mlp(jax.random.PRNGKey(0), 32, 64, lc, act="gelu")
    x = jax.jit(lambda p, x: mlp_lib.apply_mlp(p, x, lc, act="gelu")).lower(
        p, jax.ShapeDtypeStruct((8, 32), jnp.float32))
    assert "block_j" in seen["dyad_ff_fused"]


# -- compare / regression gate ------------------------------------------------


def _results(**us_by_name):
    return [BenchResult(name=k, us_per_call=v) for k, v in us_by_name.items()]


def test_compare_flags_regression():
    rows = compare.compare_runs(_results(a=200.0, b=200.0),
                                _results(a=200.0, b=300.0), tol=0.25)
    by = {r.name: r for r in rows}
    assert not by["a"].regressed
    assert by["b"].regressed and by["b"].status == "REGRESSED"
    assert compare.summarize(rows)["regressed"] == 1


def test_compare_within_tolerance_and_noise_floor():
    rows = compare.compare_runs(_results(a=200.0, tiny=10.0, small=100.0),
                                _results(a=240.0, tiny=40.0, small=140.0),
                                tol=0.25)
    by = {r.name: r for r in rows}
    assert not by["a"].regressed            # 20% < 25% tol
    assert not by["tiny"].regressed         # current below the noise floor
    assert not by["small"].regressed        # delta 40us below the floor


def test_compare_tiny_baseline_can_still_regress():
    """A sub-floor baseline must not immunize a cell: 30us -> 5000us is a
    real regression even though the baseline is under the noise floor."""
    rows = compare.compare_runs(_results(k=30.0), _results(k=5000.0))
    assert rows[0].regressed


def test_compare_new_and_removed_never_fail():
    rows = compare.compare_runs(_results(old=100.0), _results(new=900.0))
    assert {r.status for r in rows} == {"REMOVED", "NEW"}
    assert compare.summarize(rows)["regressed"] == 0


def test_compare_roofline_annotation():
    cur = [BenchResult(name="a", us_per_call=1000.0,
                       metrics={"flops": 1e9, "bytes": 1e6})]
    rows = compare.compare_runs([], cur)
    assert rows[0].gflops == pytest.approx(1e9 / 1000.0 / 1e3)
    assert rows[0].intensity == pytest.approx(1000.0)
    assert rows[0].roofline_frac is not None
    assert "GF/s" in compare.format_table(rows)


def test_check_cli_passes_on_identical(tmp_path):
    """python -m repro.perf.check against a committed baseline == current."""
    repo = tmp_path / "r"
    repo.mkdir()
    env = {**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}

    def git(*a):
        subprocess.run(["git", *a], cwd=repo, check=True, env=env,
                       capture_output=True)

    git("init", "-q")
    rec = Recorder("gate", out_dir=str(repo))
    rec.add("cell", 100.0)
    rec.write()
    git("add", "-A")
    git("commit", "-qm", "baseline")

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.perf.check"], cwd=repo,
        env={**env, "PYTHONPATH": src + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PERF GATE: PASS" in out.stdout

    # regress the current file 2x -> gate fails
    rec2 = Recorder("gate", out_dir=str(repo))
    rec2.add("cell", 250.0)
    rec2.write()
    out = subprocess.run(
        [sys.executable, "-m", "repro.perf.check"], cwd=repo,
        env={**env, "PYTHONPATH": src + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
        capture_output=True, text=True)
    assert out.returncode == 1
    assert "PERF GATE: FAIL" in out.stdout
