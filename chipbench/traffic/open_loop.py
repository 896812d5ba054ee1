"""Open-loop arrivals: independent users sending on a schedule whether or
not earlier requests have finished.

Parameters (the workload file's ``traffic``): ``rate`` (requests/s,
Poisson), ``prompt`` and ``output`` length distributions
(:mod:`lengths`), ``warm_s`` seconds of traffic before the window opens,
and ``shape_seed``.  The gaps and sizes are a fixed multiset drawn from
``shape_seed``; the run's seed permutes them and draws the prompts' token
ids, so every seed offers the same work.
"""
from __future__ import annotations

import os

import numpy as np

from chipbench import common

lengths = common.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "lengths.py"))


def schedule(spec: dict, seed: int, seconds: float) -> list:
    """``[(due_s, prompt_len, max_new)]`` with ``due_s`` from the start of
    the warm traffic, covering ``warm_s + seconds`` and a second more.  The
    gaps are as many as it takes to cover that span, so every seed sends
    the same requests."""
    base = np.random.default_rng(spec["shape_seed"])
    span = spec["warm_s"] + seconds + 1.0
    gaps = [0.0]
    while sum(gaps) < span:
        gaps.append(base.exponential(1.0 / spec["rate"]))
    gaps = np.asarray(gaps[1:])
    prompts = lengths.draw(base, spec["prompt"], len(gaps))
    outputs = lengths.draw(base, spec["output"], len(gaps))
    rng = common.rng(seed, 1)
    gaps = gaps[rng.permutation(len(gaps))]
    order = rng.permutation(len(gaps))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [(float(t), int(prompts[i]), int(outputs[i]))
            for t, i in zip(due, order)]


class Traffic:
    open_loop = True

    def __init__(self, spec: dict, seed: int, seconds: float, vocab: int,
                 t_start: float):
        self.warm_s = spec["warm_s"]
        self.items = schedule(spec, seed, seconds)
        self.t_start = t_start
        self.ids = common.rng(seed, 2)
        self.vocab = vocab
        self.next = 0
        self.window = None
        self.closed = False

    def initial(self, now: float) -> list:
        return []

    def next_due(self):
        if self.closed or self.next >= len(self.items):
            return None
        return self.t_start + self.items[self.next][0]

    def due(self, now: float) -> list:
        out = []
        while (not self.closed and self.next < len(self.items)
               and self.t_start + self.items[self.next][0] <= now):
            t, p, o = self.items[self.next]
            out.append({"due_abs": self.t_start + t, "max_new": o,
                        "prompt": self.ids.integers(0, self.vocab, p,
                                                    dtype=np.int32)})
            self.next += 1
        return out

    def finished(self, rec: dict, now: float) -> list:
        return []
