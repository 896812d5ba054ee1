"""The control, the reference computed with float8 operands in the
program's place, reads as not correct, at a size the CPU holds: a
Qwen3-shaped model with a vocabulary wide enough that the top logits lie
close, and OPT-shaped training at smoke size."""
import numpy as np

from chipbench import common, models, serve, train
from chipbench.tests import cells

WIDER = {"hidden_size": 256, "num_hidden_layers": 4, "intermediate_size": 768,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
         "vocab_size": 16384}


def greedy_requests(conf, seed, n_req=3, prompt=48, new=24):
    """Requests whose served tokens are the float32 reference's own greedy
    choices: the program's reading on them is 0 by construction."""
    import jax
    import jax.numpy as jnp

    model = models.of(conf)
    m = model.dims(conf)
    params = models.make_jit(conf, seed)
    fwd = jax.jit(lambda p, t: model.logits_at(
        m, p, t, jnp.arange(t.shape[0]), "fp32")[-1])
    rng = common.rng(seed, 3)
    reqs = {}
    for uid in range(n_req):
        seq = list(rng.integers(0, m["vocab"], prompt))
        toks = []
        for _ in range(new):
            toks.append(int(jnp.argmax(fwd(params, jnp.asarray(seq,
                                                               jnp.int32)))))
            seq.append(toks[-1])
        reqs[uid] = {"uid": uid, "prompt": np.asarray(seq[:prompt], np.int32),
                     "tokens": toks}
    return m, list(reqs.values())


def test_serving_control_departs_from_the_reference():
    """The served cells carry no limit set from chip readings yet (PERF.md,
    Open questions); the control already picks tokens whose reference logit
    lies below the best, where the program's reading is 0."""
    conf = cells.config("qwen3-0.6b")
    conf.update(WIDER)
    worst = []
    for seed in (1, 2, 3):
        m, recs = greedy_requests(conf, seed)
        assert max(serve.token_gaps(conf, m, seed, recs, 96, 32)) == 0.0
        worst.append(max(serve.token_gaps(conf, m, seed, recs, 96, 32,
                                          control=True)))
    assert max(worst) > 0.0, worst


def test_training_control_fails_a_limit():
    cell = cells.cell("opt-125m.train")
    conf, wl = cell["config"], cell["workload"]
    limits = wl["check"]
    for seed in (1, 2, 3):
        ref = train.reference_readings(conf, wl, seed)
        got = train.gaps(train.reference_readings(conf, wl, seed, prec="fp8"),
                         ref)
        assert any(got[k] > limits[k] for k in got), got
