"""A run with the timed path broken underneath reads as not correct: the
harness's look for a chip is skipped and the rest of a run is driven on the
CPU at smoke size, once for each fault the cell can have (one chip: no
exchange between chips to leave out)."""
import jax
import pytest

from chipbench import run
from chipbench.tests import cells


def outcome(name):
    cell, args = cells.cell(name), cells.args(name, seed=5)
    return run.execute(cell, args, jax.devices())[1]


def test_state_left_unchanged(monkeypatch):
    import repro.train

    real = repro.train.make_train_step

    def broken(*a, **kw):
        step = real(*a, **kw)

        def unchanged(state, batch):
            return state, step(state, batch)[1]
        return unchanged

    monkeypatch.setattr(repro.train, "make_train_step", broken)
    out = outcome("opt-125m.train")
    assert not out["ok"], out["checks"]
    assert out["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(monkeypatch):
    import repro.train

    real = repro.train.make_train_step

    def broken(*a, **kw):
        step = real(*a, **kw)

        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half

    monkeypatch.setattr(repro.train, "make_train_step", broken)
    out = outcome("opt-125m.train")
    assert not out["ok"], out["checks"]


@pytest.mark.parametrize("name", ["qwen3-0.6b.serve-steady",
                                  "qwen3-0.6b.serve-longctx"])
def test_token_altered_where_produced(monkeypatch, name):
    from repro.serve import engine

    real = engine.ContinuousBatchingEngine._emit

    def altered(self, req, token):
        if len(req.tokens) == 1:
            token = (token + 1) % self.cfg.vocab_size
        return real(self, req, token)

    monkeypatch.setattr(engine.ContinuousBatchingEngine, "_emit", altered)
    out = outcome(name)
    assert not out["ok"], out["checks"]
