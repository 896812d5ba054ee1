"""The DeepSeek-V3 model module (Moonlight-16B-A3B): the configuration finds
it, its weights have the program's layout, the program's configuration
matches the file's sizes, its work counts follow the model's mathematics,
and the cell runs end to end at smoke size on the CPU, its comparison
with the reference passing."""
import json

import jax
import numpy as np
import pytest

from chipbench import common, models, run
from chipbench.models import deepseek_v3
from chipbench.tests import cells

NAME = "moonlight-16b-a3b"
CELL = NAME + ".train"
PEAKS = common.load_json(f"{common.BENCH}/peaks.json")["TPU v5 lite"]


def published():
    return common.load_json(f"{common.BENCH}/configs/{NAME}.json")


def test_the_model_type_finds_the_module():
    conf = published()
    assert "model_module" not in conf
    assert models.of(conf) is deepseek_v3


def test_the_file_states_the_cut():
    conf = published()
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert conf["published"] == {"num_hidden_layers": 27,
                                 "n_routed_experts": 64,
                                 "vocab_size": 163840}
    # one dense layer and four expert layers; 8 of the router's 64 experts;
    # an eighth of the vocabulary
    assert conf["num_hidden_layers"] == 1 + 4
    assert (conf["n_routed_experts"], conf["router_experts"]) == (8, 64)
    assert conf["vocab_size"] * 8 == conf["published"]["vocab_size"]


@pytest.mark.parametrize("size", ["smoke", "published"])
def test_weights_have_the_program_layout(size):
    from repro.models import model

    conf = cells.config(NAME) if size == "smoke" else published()
    cfg = common.program_cfg(conf)     # the sizes check passes
    want = jax.eval_shape(lambda: model.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: deepseek_v3.make(conf,
                                                  np.zeros(2, np.uint32)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_forward_flops_at_published_widths():
    m = deepseek_v3.dims(published())
    d, H, S = 2048, 16, 8192
    proj = 2 * (d * H * 192 + d * (512 + 64) + 512 * H * 256 + H * 128 * d)
    core = S * (192 + 128) * H
    # a DYAD-IT n=4 projection f_in -> f_out: 2 x (2 f_in f_out / 4) FLOPs
    swiglu = lambda f: 3 * 2 * (2 * d * f // 4)
    dense = swiglu(11264)
    expert_layer = 2 * d * 64 + 6 * 8 / 64 * swiglu(1408) + swiglu(2816)
    head = 2 * d * 20480
    want = 5 * (proj + core) + dense + 4 * expert_layer + head
    assert proj == 27_525_120
    assert deepseek_v3.forward_flops_per_token(m, S) == pytest.approx(want)
    assert deepseek_v3.train_flops_per_token(m, S) == pytest.approx(3 * want)


def test_kernel_call_counts():
    m = deepseek_v3.dims(published())
    B, S = 4, 8192
    gmm = deepseek_v3.train_expert_gmm_calls(m, B, S)
    # 4 expert layers x 3 projections x fwd, dgrad, wgrad
    assert len(gmm) == 4 * 3 * 3
    rows = B * S * 6 * 8 / 64                     # 24,576 expected rows
    w = 2 * 2048 * 1408 // 4
    assert gmm[0] == (2 * w * rows, 2 * (rows * (2048 + 1408) + 8 * w))
    mm = deepseek_v3.train_dyad_mm_calls(m, B, S)
    # the dense layer's and the 4 shared experts' SwiGLU
    assert len(mm) == (1 + 4) * 3 * 3
    flash = deepseek_v3.train_flash_calls(m, B, S)
    assert len(flash) == 2 * 5
    T = B * S
    assert flash[0] == (S * 320 * 16 * T,
                        2 * (2 * T * 16 * 192 + 2 * T * 16 * 128))


def test_control_departs_from_reference():
    conf = cells.config(NAME)
    m = deepseek_v3.dims(conf)
    params = models.make_jit(conf, 5)
    tok = np.random.default_rng(1).integers(0, m["vocab"], 16)
    a = deepseek_v3.logits_at(m, params, tok, np.arange(16), "fp32")
    b = deepseek_v3.logits_at(m, params, tok, np.arange(16), "fp8")
    assert float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) > 1e-3


def test_cell_runs_end_to_end(capsys):
    cell = cells.cell(CELL, compute="float32")
    args = cells.args(CELL, seed=2**31 + 29)
    devices = jax.devices()
    rec, out = run.execute(cell, args, devices)
    assert out["ok"], out["checks"]
    assert [p for p in out["problems"] if "interpret" not in p] == []
    run.finish(cell, args, devices, PEAKS, rec, out)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_the_benchmark_lists_the_cell():
    cell = common.cell(CELL)
    assert cell["entry"]["chips"] == 1
    assert cell["config"] == published()
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s",
                                                      "setup_s"}
    new = {"expert_gmm_roofline", "router_ms.train", "experts_ms.train"}
    opt = {m["name"] for m in common.cell("opt-125m.train")["per_layer"]}
    assert {m["name"] for m in cell["per_layer"]} == opt | new
    assert not opt & new
