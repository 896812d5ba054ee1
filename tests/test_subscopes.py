"""The scopes nested in the expert layer and the latent attention
(``router``, ``experts``, ``shared`` in ``ff``; ``latent`` in ``attn``),
and the benchmark's readers of them: (a) the Moonlight train step at
smoke size, compiled on the CPU, carries them in forward and backward
``op_name``s under the outer layer scopes; (b) ``chipbench/subscopes.py``
and the grouped kernels' roofline on a hand-made trace."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import common, scopes, subscopes, tracing, work  # noqa: E402
from repro import configs  # noqa: E402
from repro.optim import AdamW, schedule  # noqa: E402
from repro.train import init_train_state, make_train_step  # noqa: E402

INNER = {"router": "ff", "experts": "ff", "shared": "ff", "latent": "attn"}


@pytest.fixture(scope="module")
def step_names():
    cfg = configs.get("moonlight_16b_a3b", smoke=True,
                      linear=configs.linear_cfg("dyad_it_4_kernel"),
                      compute_dtype="bfloat16", moe_chunk=16)
    opt = AdamW(lr=schedule.constant(1e-3))
    state = jax.eval_shape(lambda k: init_train_state(cfg, opt, k),
                           jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    step = jax.jit(make_train_step(cfg, opt))
    return scopes.op_names(step.lower(state, {"tokens": tok, "labels": tok})
                           .compile().as_text())


@pytest.mark.parametrize("inner", sorted(INNER))
def test_inner_scope_lies_in_its_layer_scope(step_names, inner):
    ops = [op for op in step_names.values()
           if subscopes.has_scope(op, inner)]
    assert [op for op in ops if "transpose(" not in op]
    assert [op for op in ops if "transpose(" in op]
    # XLA hoists a few casts and constants out of the chunk loop with a
    # name stack that lost the layer scope; every product and kernel keeps
    # it
    assert {scopes.scope_of(op) for op in ops} <= {INNER[inner],
                                                  scopes.UNSCOPED}
    heavy = [op for op in ops if op.endswith(("pallas_call", "dot_general"))]
    assert heavy and {scopes.scope_of(op) for op in heavy} == {INNER[inner]}


MS = 1e6  # ns
NAMES = {
    "fusion.1": "jit(train_step)/jvp()/while/body/closed_call/ff/router/"
                "dot_general",
    "_gmm_impl.2": "jit(train_step)/jvp()/while/body/closed_call/ff/"
                   "while/body/checkpoint/experts/jit(_gmm_impl)/"
                   "pallas_call",
    "_gmm_wgrad_impl.3": "jit(train_step)/transpose(jvp())/while/body/"
                         "transpose(jvp(ff))/transpose(jvp(experts))/"
                         "jit(_gmm_wgrad_impl)/pallas_call",
    "fusion.4": "jit(train_step)/transpose(jvp(ff))/transpose(jvp(router))/"
                "gather",
    "fusion.5": "jit(train_step)/jvp(ff)/shared/mul",
    "fusion.6": "jit(train_step)/jvp(attn)/latent/dot_general",
}


def _op(name, start_ms, dur_ms):
    return (f"%{name} = f32[8]{{0}} fusion(x)", start_ms * MS, dur_ms * MS)


def trace():
    """Two 50 ms steps and the profiler's 0.2 ms stub of a third: router
    2 + 3 ms, the grouped forward 6 ms and wgrad 4 ms, shared 5 ms,
    latent 1 ms."""
    ops, mods = [], []
    for t0, dur in ((0, 50), (50, 50), (110, 0.2)):
        mods.append(("jit_train_step", t0 * MS, dur * MS))
        ops += [_op("fusion.1", t0, 2), _op("_gmm_impl.2", t0 + 2, 6),
                _op("_gmm_wgrad_impl.3", t0 + 8, 4),
                _op("fusion.4", t0 + 12, 3), _op("fusion.5", t0 + 15, 5),
                _op("fusion.6", t0 + 20, 1)]
    return {"device": {"/device:TPU:0": {"ops": ops, "modules": mods}},
            "host": [], "window": (0.0, 130 * MS)}


def _rec():
    from chipbench.models import deepseek_v3
    conf = common.load_json(f"{common.BENCH}/configs/moonlight-16b-a3b.json")
    return {"kind": "train", "reduced": tracing.Reduced(trace()),
            "op_names": NAMES, "model": deepseek_v3,
            "m": deepseek_v3.dims(conf), "batch": 4, "seq": 8192,
            "peaks": common.peaks("TPU v5 lite")}


def test_inner_scopes_per_step():
    rec = _rec()
    assert subscopes.ms(rec, "router") == pytest.approx(5.0)
    assert subscopes.ms(rec, "experts") == pytest.approx(10.0)
    assert subscopes.ms(rec, "latent") == pytest.approx(1.0)
    assert subscopes.ms(rec, "no_such_scope") is None
    for name in ("router_ms.train", "experts_ms.train"):
        mod = common.load_module(f"{common.BENCH}/metrics/{name}.py")
        assert mod.read(rec) == pytest.approx(
            5.0 if name.startswith("router") else 10.0)


def test_expert_gmm_roofline_counts_whole_steps():
    rec = _rec()
    mod = common.load_module(
        f"{common.BENCH}/metrics/expert_gmm_roofline.py")
    least = sum(work.least_time(f, b, rec["peaks"]) for f, b in
                rec["model"].train_expert_gmm_calls(rec["m"], 4, 8192))
    # 10 ms of grouped kernels a step, the stub not counted as a step
    assert mod.read(rec) == pytest.approx(100.0 * least / 10e-3)


def test_a_program_without_the_scopes_reads_nothing():
    rec = {**_rec(), "op_names": {k: "jit(train_step)/jvp()/mul"
                                  for k in NAMES}}
    assert subscopes.ms(rec, "router") is None
    rec = {**_rec(), "model": object()}
    mod = common.load_module(
        f"{common.BENCH}/metrics/expert_gmm_roofline.py")
    assert mod.read(rec) is None
