"""The trace reduction: busy time as the union of device operations inside
the traced window, time per kernel group, roofline shares and the
breakdown, on a hand-made trace."""
import pytest

from chipbench import tracing

MS = 1e6  # ns


def hand_trace():
    # window 0..100 ms; ops overlap (10..30 and 20..40 -> 30 ms busy), one
    # straddles the window's end (90..110 -> 10 ms), one lies outside
    ops = [("%_mm_impl.1 = bf16[4,8]{1,0} custom-call(x)", 10 * MS, 20 * MS), ("%fusion.1 = f32[8]{0} fusion(y)", 20 * MS, 20 * MS),
           ("%_wgrad_impl.2 = f32[4]{0} custom-call(z)", 90 * MS, 20 * MS), ("copy", 150 * MS, 5 * MS)]
    mods = [("jit_train_step", 5 * MS, 40 * MS),
            ("jit_train_step", 80 * MS, 40 * MS)]
    host = [("chipbench.dispatch", 0.0, 8 * MS, {}),
            ("chipbench.block", 45 * MS, 44 * MS, {}),
            ("chipbench.step", 40 * MS, 60 * MS, {"step": 3})]
    return {"device": {"/device:TPU:0": {"ops": ops, "modules": mods}},
            "host": host, "window": (0.0, 100 * MS)}


def test_busy_and_idle():
    red = tracing.Reduced(hand_trace())
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.040)
    assert red.idle_share == pytest.approx(0.6)


def test_kernel_time_and_modules():
    red = tracing.Reduced(hand_trace())
    mm = lambda n: n.startswith(("%_mm_impl", "%_wgrad_impl"))
    assert red.op_time_s(mm) == pytest.approx(0.030)
    steps = red.modules(lambda n: "train_step" in n)
    assert steps == [(5 * MS, 45 * MS)]     # the second runs past the end
    assert red.op_time_s(mm, steps) == pytest.approx(0.020)
    assert [h[3]["step"] for h in red.host("step")] == [3]


def test_breakdown():
    b = tracing.Reduced(hand_trace()).breakdown()
    assert b["device_ops"][0] == ["_mm_impl.1 bf16[4,8]", pytest.approx(0.02)]
    # idle gaps: 40..90 (50 ms; the step span covers more of it than the
    # block span), 0..10 (dispatch); none after the window's end
    assert b["idle_gaps"][0] == ["step", pytest.approx(0.05)]
    assert b["idle_gaps"][1] == ["dispatch", pytest.approx(0.01)]
    assert len(b["idle_gaps"]) == 2


def test_leaves_skip_loops_that_hold_operations():
    ops = [("%while.1 = ()", 0, 100), ("%a = ()", 10, 20), ("%b = ()", 40, 5),
           ("%c = ()", 200, 5)]
    assert [e[0] for e in tracing.leaves(ops)] == ["%a = ()", "%b = ()",
                                                   "%c = ()"]


def test_union():
    assert tracing.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]

