"""Closed-loop clients: each of ``clients`` callers sends its next request
as soon as its previous one finished.

Parameters: ``clients``, ``prompt`` and ``output`` length distributions
(:mod:`lengths`), ``pool`` (requests in the fixed multiset drawn from
``shape_seed``, dealt to the clients in an order the run's seed picks),
and ``warm_s``.  Set-up starts every client at once, with the first
requests' outputs cut to ``(k + 1) / clients`` of their length for client
``k`` so that retirements do not line up.
"""
from __future__ import annotations

import os

import numpy as np

from chipbench import common

lengths = common.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "lengths.py"))


class Traffic:
    open_loop = False

    def __init__(self, spec: dict, seed: int, seconds: float, vocab: int,
                 t_start: float):
        base = np.random.default_rng(spec["shape_seed"])
        n = spec["pool"]
        prompts = lengths.draw(base, spec["prompt"], n)
        outputs = lengths.draw(base, spec["output"], n)
        order = common.rng(seed, 1).permutation(n)
        C = spec["clients"]
        self.queues = [[(int(prompts[i]), int(outputs[i]))
                        for i in order[k::C]] for k in range(C)]
        self.warm_s = spec["warm_s"]
        self.ids = common.rng(seed, 2)
        self.vocab = vocab
        self.sent = [0] * C
        self.window = None
        self.closed = False

    def _item(self, client: int, now: float, cut: float = 1.0) -> dict:
        q = self.queues[client]
        p, o = q[self.sent[client] % len(q)]
        self.sent[client] += 1
        return {"due_abs": now, "client": client,
                "max_new": max(1, int(o * cut)),
                "prompt": self.ids.integers(0, self.vocab, p,
                                            dtype=np.int32)}

    def initial(self, now: float) -> list:
        C = len(self.queues)
        return [self._item(k, now, (k + 1) / C) for k in range(C)]

    def next_due(self):
        return None

    def due(self, now: float) -> list:
        return []

    def finished(self, rec: dict, now: float) -> list:
        if self.closed or rec.get("client") is None:
            return []
        return [self._item(rec["client"], now)]
