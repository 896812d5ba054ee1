"""The work functions count the model's mathematics."""
import pytest

from chipbench import common, work
from chipbench.models import decoder


def dims(name):
    return decoder.dims(common.load_json(
        f"{common.BENCH}/configs/{name}.json"))


def test_dense_projection_flops():
    m = dims("opt-125m")
    d = m["d"]
    # OPT's q, k, v and o are dense d x d: 2 * d_in * d_out each, per token
    assert decoder.attn_proj_flops_per_token(m) == 4 * 2 * d * d
    assert decoder.unembed_flops_per_token(m) == 2 * d * m["vocab"]


def test_dyad_projection_counts_its_nonzeros():
    m = dims("opt-125m")
    assert decoder.ff_weights(m, 768, 3072) == 2 * 768 * 3072 // 4
    assert decoder.ff_flops_per_token(m) == 2 * 2 * (2 * 768 * 3072 // 4)


def test_dense_ff_counts_the_whole_matrix_and_no_dyad_calls():
    conf = common.load_json(f"{common.BENCH}/configs/opt-125m.json")
    conf["program"]["linear"] = "dense"
    m = decoder.dims(conf)
    assert decoder.ff_flops_per_token(m) == 2 * (2 * 768 * 3072)
    assert decoder.train_dyad_mm_calls(m, 8, 2048) == []
    assert decoder.serve_ff_calls(m, 16) == []
    # attention is as in the DYAD model
    assert decoder.train_flash_calls(m, 8, 2048) == \
        decoder.train_flash_calls(dims("opt-125m"), 8, 2048)


def test_opt_125m_training_flops_per_token():
    m = dims("opt-125m")
    fwd = decoder.forward_flops_per_token(m, 2048)
    # 57 (attention projections) + 57 (DYAD ff) + 38 (attention) + 77
    # (unembedding) MFLOP per token
    assert fwd == pytest.approx(228.1e6, rel=2e-3)
    assert decoder.train_flops_per_token(m, 2048) == 3 * fwd


def test_qwen3_decode_bytes_are_the_weights_and_the_cache():
    m = dims("qwen3-0.6b")
    (f, b), = set(decoder.serve_ff_calls(m, 16))
    # three DYAD projections of 1024 x 3072 / 4 * 2 nonzeros, bf16
    assert b == 2 * (3 * 2 * 1024 * 3072 // 4 + 2 * 16 * 1024)
    (f, b), = set(decoder.paged_decode_calls(m, [4096]))
    assert b == 2 * (2 * 4096 * 8 * 128 + 2 * 16 * 128)
    assert f == 4 * 4096 * 128 * 16


def test_least_time_is_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(200.0, 10.0, peaks) == 2.0
    assert work.least_time(100.0, 50.0, peaks) == 5.0


def test_serve_step_flops_counts_causal_prefill():
    m = dims("qwen3-0.6b")
    one = decoder.serve_step_flops(m, {"chunks": [(0, 1, False)],
                                       "contexts": []})
    two = decoder.serve_step_flops(m, {"chunks": [(0, 2, False)],
                                       "contexts": []})
    per_key = 4 * m["hd"] * m["heads"] * m["layers"]
    # the second token sees two keys, the first one
    assert two - 2 * one == pytest.approx(per_key)
