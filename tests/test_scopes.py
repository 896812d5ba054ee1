"""The program's layer scopes and the benchmark's per-scope device time.

(a) The train step of a tiny OPT-shaped model with the DYAD kernel linear,
compiled on the CPU: each layer's scope reaches the ``op_name`` of its
forward and backward instructions, and every product or kernel of the
optimized HLO outside the layer scan's own bookkeeping falls under a
scope.  (b) ``chipbench/scopes.py`` on a hand-made trace.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import scopes, tracing  # noqa: E402
from repro import configs  # noqa: E402
from repro.optim import AdamW, schedule  # noqa: E402
from repro.train import init_train_state, make_train_step  # noqa: E402

MODEL_SCOPES = ("embed", "norm", "attn", "ff", "head")
HEAVY = ("dot", "convolution", "custom-call")
# the layer scan's own instructions: the loop, its counter and condition,
# the slicing of the stacked layers and the copies at the body's call,
# and the broadcasts that allocate its stacked outputs
SCAN_OWN = re.compile(r"jit\(train_step\)/(?:transpose\()?jvp\(\)\)?/"
                      r"(?:while(?:/(?:body|cond)/[\w-]+)?|broadcast_in_dim)")


@pytest.fixture(scope="module")
def step_hlo():
    cfg = configs.get("opt125m", smoke=True,
                      linear=configs.linear_cfg("dyad_it_4_kernel"),
                      compute_dtype="bfloat16")
    opt = AdamW(lr=schedule.constant(1e-3))
    state = jax.eval_shape(lambda k: init_train_state(cfg, opt, k),
                           jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    step = jax.jit(make_train_step(cfg, opt))
    return step.lower(state, {"tokens": tok, "labels": tok}).compile(
    ).as_text()


@pytest.mark.parametrize("scope", scopes.SCOPES)
def test_scope_reaches_forward_and_backward(step_hlo, scope):
    ops = [op for op in scopes.op_names(step_hlo).values()
           if scopes.scope_of(op) == scope]
    forward = [op for op in ops if "transpose(" not in op]
    backward = [op for op in ops if "transpose(" in op]
    assert forward
    # the optimizer is not differentiated
    assert bool(backward) == (scope in MODEL_SCOPES)


def test_every_product_and_kernel_is_scoped(step_hlo):
    comps = scopes.parse_hlo(step_hlo)
    names = scopes.op_names(step_hlo)
    fused = {c for body in comps.values() for ins in body
             if ins[1] == "fusion" for c in ins[3]}
    seen = 0
    for comp, body in comps.items():
        if comp in fused:
            continue
        for name, opcode, _, calls, _ in body:
            if opcode not in HEAVY + ("fusion",):
                continue
            seen += 1
            op = names.get(name)
            if op is None:
                # XLA's CPU passes leave some fusions (converts, constant
                # broadcasts) without metadata; none of them computes
                inner = [i[1] for c in calls for i in comps.get(c, [])]
                assert not set(inner) & set(HEAVY), (name, inner)
            elif scopes.scope_of(op) == scopes.UNSCOPED:
                assert SCAN_OWN.fullmatch(op), (name, op)
    assert seen > 100


def test_step_hlo_builds_the_commanded_cell(monkeypatch):
    """After the window the reader compiles the step of the cell that the
    command names again; here at smoke size, the kernels interpreted."""
    from chipbench.tests import cells

    monkeypatch.setattr(sys, "argv", ["run.py", "--workload",
                                      "opt-125m.train", "--trace", "1"])
    smoke = cells.cell("opt-125m.train")
    monkeypatch.setattr(scopes.common, "cell", lambda name: smoke)
    names = scopes.op_names(scopes.step_hlo())
    assert {scopes.scope_of(op) for op in names.values()} == set(
        scopes.SCOPES) | {scopes.UNSCOPED}
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert scopes.step_hlo() is None


def test_scope_of_takes_the_outermost_scope():
    assert scopes.scope_of(
        "jit(train_step)/transpose(jvp(head))/jit(take_along_axis)/gather"
    ) == "head"
    assert scopes.scope_of(
        "jit(train_step)/jvp()/while/body/closed_call/ff/jit(relu)/max"
    ) == "ff"
    assert scopes.scope_of("jit(train_step)/optimizer/attn/mul") == \
        "optimizer"
    assert scopes.scope_of("jit(train_step)/jvp()/while/cond/lt") == \
        scopes.UNSCOPED
    assert scopes.scope_of(None) == scopes.UNSCOPED


HLO = """HloModule jit_train_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.2 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(train_step)/jvp(norm)/mul"}
}

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x[\\'a\\']"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  ROOT %dot.4 = f32[8]{0:T(8,128)} dot(%fusion.1, %fusion.1), metadata={op_name="jit(train_step)/transpose(jvp(attn))/dot_general" source_file="m.py"}
}
"""


def test_op_names_from_hlo_text():
    names = scopes.op_names(HLO)
    # a fusion with no metadata of its own takes its root's
    assert names["fusion.1"] == "jit(train_step)/jvp(norm)/mul"
    assert names["dot.4"] == "jit(train_step)/transpose(jvp(attn))/dot_general"
    assert names["x"] == "x[\\'a\\']"
    assert [i[1] for i in scopes.parse_hlo(HLO)["main.3"]] == [
        "parameter", "fusion", "dot"]


MS = 1e6  # ns
NAMES = {
    "fusion.1": "jit(train_step)/jvp(embed)/dot_general",
    "while.2": "jit(train_step)/jvp()/while",
    "_mm_impl.3": "jit(train_step)/jvp()/while/body/closed_call/ff/"
                  "jit(_mm_impl)/pallas_call",
    "fusion.4": "jit(train_step)/transpose(jvp())/while/body/closed_call/"
                "attn/dot_general",
    "fusion.5": "jit(train_step)/jvp(head)/dot_general",
    "fusion.6": "jit(train_step)/optimizer/mul",
    "fusion.7": "jit(train_step)/transpose(jvp(norm))/mul",
}


def _op(name, start_ms, dur_ms):
    return (f"%{name} = f32[8]{{0}} fusion(x)", start_ms * MS, dur_ms * MS)


def scoped_trace():
    """Two train steps of 50 ms inside a 0..130 ms window and a third that
    runs past its end, of which the profiler recorded a 0.2 ms stub.  Each
    step: embed 2 ms, a 20 ms loop over ff 8 ms and, 2 ms later, attn
    10 ms, head 5 ms, norm 4 ms, an unscoped copy 1 ms, the optimizer
    3 ms; the rest of the step is idle."""
    ops, mods = [], []
    for t0, dur in ((0, 50), (50, 50), (110, 0.2)):
        mods.append(("jit_train_step", t0 * MS, dur * MS))
        ops += [_op("fusion.1", t0 + 0, 2), _op("while.2", t0 + 2, 20),
                _op("_mm_impl.3", t0 + 2, 8), _op("fusion.4", t0 + 12, 10),
                _op("fusion.5", t0 + 22, 5), _op("fusion.7", t0 + 27, 4),
                _op("copy.8", t0 + 31, 1), _op("fusion.6", t0 + 32, 3)]
    return {"device": {"/device:TPU:0": {"ops": ops, "modules": mods}},
            "host": [], "window": (0.0, 130 * MS)}


def test_split_per_scope_per_step():
    red = tracing.Reduced(scoped_trace())
    # the stub lies inside the window but is not a step
    assert len(red.modules(lambda n: "train_step" in n)) == 3
    assert scopes.whole_steps(red) == [(0.0, 50 * MS), (50 * MS, 100 * MS)]
    split = scopes.split_s(red, NAMES)
    want = {"embed": 2, "ff": 8, "attn": 10, "head": 5, "norm": 4,
            "optimizer": 3, "unscoped": 1}
    for scope, ms in want.items():
        # the loop (20 ms) is not counted again over its body (18 ms)
        assert split[scope] == pytest.approx(ms * 1e-3), scope
    # busy time counts the loop where its body runs nothing (10..12 ms)
    assert split["busy"] == pytest.approx(35e-3)
    assert split["top_unscoped"] == [["copy.8 f32[8]",
                                      pytest.approx(1e-3)]]


def test_readers_of_the_split():
    rec = {"kind": "train", "reduced": tracing.Reduced(scoped_trace()),
           "op_names": NAMES}
    assert scopes.ms(rec, "attn") == pytest.approx(10.0)
    assert scopes.unscoped_share(rec) == pytest.approx(100.0 / 33)


def test_a_program_without_scopes_reads_nothing():
    unscoped = {k: "jit(train_step)/jvp()/mul" for k in NAMES}
    red = tracing.Reduced(scoped_trace())
    assert scopes.split_s(red, unscoped) is None
    for rec in ({"kind": "train", "reduced": red, "op_names": unscoped},
                {"kind": "train", "reduced": red, "op_names": None},
                {"kind": "serve", "reduced": red, "op_names": NAMES}):
        assert scopes.ms(rec, "ff") is None
        assert scopes.unscoped_share(rec) is None
