"""The traffic generators are seeded and deterministic, and every seed
offers the same work in another order."""
import os

import numpy as np

from chipbench import common
from chipbench.tests import cells

TRAFFIC = os.path.join(common.BENCH, "traffic")


def mod(kind):
    return common.load_module(os.path.join(TRAFFIC, kind + ".py"))


def spec(name):
    return common.load_json(os.path.join(common.BENCH, "workloads",
                                         name + ".json"))["traffic"]


def test_open_loop_schedule_is_seeded():
    ol = mod("open_loop")
    s = spec("qwen3-0.6b.serve-steady")
    a, b = ol.schedule(s, 2**31 + 7, 50), ol.schedule(s, 2**31 + 7, 50)
    assert a == b
    c = ol.schedule(s, 12345, 50)
    assert a != c
    # the same requests and gaps, in another order
    assert sorted(x[1:] for x in a) == sorted(x[1:] for x in c)
    assert abs(a[-1][0] - c[-1][0]) < 10.0
    for _, p, o in a:
        assert 128 <= p <= 2048 and p % 128 == 0 and 16 <= o <= 512
    rate = len(a) / (a[-1][0] - a[0][0])
    assert 0.7 * s["rate"] < rate < 1.3 * s["rate"]


def test_open_loop_traffic_tokens_are_seeded():
    ol = mod("open_loop")
    s = spec("qwen3-0.6b.serve-steady")
    t1 = ol.Traffic(s, 99, 10, 1000, 0.0)
    t2 = ol.Traffic(s, 99, 10, 1000, 0.0)
    a, b = t1.due(20.0), t2.due(20.0)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x["due_abs"] == y["due_abs"] and x["max_new"] == y["max_new"]
        np.testing.assert_array_equal(x["prompt"], y["prompt"])


def test_closed_loop_is_seeded_and_staggered():
    cl = mod("closed_loop")
    s = spec("qwen3-0.6b.serve-longctx")
    t1, t2 = cl.Traffic(s, 5, 10, 1000, 0.0), cl.Traffic(s, 5, 10, 1000, 0.0)
    a, b = t1.initial(0.0), t2.initial(0.0)
    assert [x["max_new"] for x in a] == [x["max_new"] for x in b]
    assert len(a) == s["clients"]
    for x in a:
        assert len(x["prompt"]) % 512 == 0
        assert 4096 <= len(x["prompt"]) <= 8192
        assert len(x["prompt"]) + x["max_new"] - 1 <= 8960
    nxt = t1.finished({"client": 1}, 3.0)
    assert nxt[0]["client"] == 1 and nxt[0]["due_abs"] == 3.0
    t1.closed = True
    assert t1.finished({"client": 1}, 4.0) == []


def test_train_ring_is_seeded():
    tb = mod("train_batches")
    s = cells.cell("opt-125m.train")["workload"]["traffic"]
    a = tb.ring(s, 256, 2**32 + 3)
    b = tb.ring(s, 256, 2**32 + 3, count=2)
    np.testing.assert_array_equal(a[1]["tokens"], b[1]["tokens"])
    np.testing.assert_array_equal(a[0]["labels"][:, :-1], a[0]["tokens"][:, 1:])
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])
