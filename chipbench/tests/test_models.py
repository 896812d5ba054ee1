"""The model modules: a configuration finds its module or fails loudly, and
the decoder module makes the same weights, reference losses and work
counts, at smoke size and (the counts) at published size, as the harness
made before they moved into it (``golden.json``, recorded on the CPU)."""
import hashlib
import os

import jax
import numpy as np
import pytest

from chipbench import common, models
from chipbench.models import decoder
from chipbench.tests import cells

GOLDEN = common.load_json(os.path.join(os.path.dirname(__file__),
                                       "golden.json"))
NAMES = ["opt-125m", "qwen3-0.6b"]


def published(name):
    return common.load_json(f"{common.BENCH}/configs/{name}.json")


def run_lengths(calls):
    """``[[flops, bytes, times], ...]`` of consecutive equal calls."""
    out = []
    for f, b in calls:
        if out and out[-1][:2] == [f, b]:
            out[-1][2] += 1
        else:
            out.append([f, b, 1])
    return out


@pytest.mark.parametrize("name", NAMES)
def test_a_known_model_resolves(name):
    conf = published(name)
    assert models.of(conf) is decoder
    # without the key the published model_type names the module
    conf.pop("model_module")
    conf["model_type"] = "decoder"
    assert models.of(conf) is decoder


def test_an_unknown_model_fails_loudly():
    conf = {**published("opt-125m"), "model_module": "no_such_model"}
    with pytest.raises(SystemExit,
                       match=r"chipbench/models/no_such_model\.py"):
        models.of(conf)
    with pytest.raises(SystemExit, match="no_such_model"):
        common.program_cfg(conf)
    conf = {"model_type": "no_such_type"}
    with pytest.raises(SystemExit, match=r"models/no_such_type\.py"):
        models.of(conf)


@pytest.mark.parametrize("name", NAMES)
def test_weights_are_bitwise_as_before(name):
    params = models.make_jit(cells.config(name), GOLDEN["seed"])
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    got = {jax.tree_util.keystr(k): hashlib.sha256(
        np.asarray(v).tobytes()).hexdigest()[:16] for k, v in flat}
    assert got == GOLDEN["configs"][name]["leaves"]


@pytest.mark.parametrize("prec", ["fp32", "fp8"])
@pytest.mark.parametrize("name", NAMES)
def test_reference_nll_sum_is_as_before(name, prec):
    conf = cells.config(name)
    m = decoder.dims(conf)
    params = models.make_jit(conf, GOLDEN["seed"])
    rng = np.random.default_rng(0)
    tok = rng.integers(0, m["vocab"], (2, 32)).astype(np.int32)
    lab = rng.integers(0, m["vocab"], (2, 32)).astype(np.int32)
    got = jax.jit(lambda p, t, y: decoder.nll_sum(m, p, t, y, prec))(
        params, tok, lab)
    # the same operations in the same order: equal to the last bit
    assert float(got) == GOLDEN["configs"][name]["nll_sum"][prec]


@pytest.mark.parametrize("size", ["smoke", "published"])
@pytest.mark.parametrize("name", NAMES)
def test_work_counts_are_as_before(name, size):
    conf = cells.config(name) if size == "smoke" else published(name)
    m = decoder.dims(conf)
    g = GOLDEN
    batch, seq = g["train"]["batch"], g["train"]["seq"]
    step = {"chunks": [tuple(c) for c in g["serve_step"]["chunks"]],
            "contexts": g["serve_step"]["contexts"]}
    got = {
        "train_flops_per_token": decoder.train_flops_per_token(m, seq),
        "train_dyad_mm_calls": run_lengths(
            decoder.train_dyad_mm_calls(m, batch, seq)),
        "train_flash_calls": run_lengths(
            decoder.train_flash_calls(m, batch, seq)),
        "serve_ff_calls": run_lengths(
            decoder.serve_ff_calls(m, g["serve_ff_tokens"])),
        "paged_decode_calls": run_lengths(
            decoder.paged_decode_calls(m, g["paged_decode_contexts"])),
        "serve_step_flops": decoder.serve_step_flops(m, step),
    }
    assert got == g["configs"][name]["counts"][size]
