"""Training batches: the copy-task token stream of the program's
``SyntheticLM`` (a fixed random permutation ``perm`` of the vocabulary;
with probability ``p_copy`` the next token is ``perm[prev]``, otherwise
uniform noise), so that the loss can fall.  One jitted call makes a ring of
``ring`` distinct batches on the device from the seed; the window cycles
through it, so it times the step and not the generator.  No host input
pipeline is modelled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import common


def _batch(key, perm, vocab: int, batch: int, seq: int, p_copy: float):
    k1, k2, k3 = jax.random.split(key, 3)
    first = jax.random.randint(k1, (batch,), 0, vocab)
    noise = jax.random.randint(k2, (batch, seq), 0, vocab)
    copy = jax.random.bernoulli(k3, p_copy, (batch, seq))

    def step(prev, inp):
        nz, uc = inp
        nxt = jnp.where(uc, perm[prev], nz)
        return nxt, nxt

    _, toks = jax.lax.scan(step, first, (noise.T, copy.T))
    s = jnp.concatenate([first[:, None], toks.T], axis=1)
    return {"tokens": s[:, :-1].astype(jnp.int32),
            "labels": s[:, 1:].astype(jnp.int32)}


def ring(spec: dict, vocab: int, seed: int, count: int = None) -> list:
    """The first ``count`` (default: ``spec["ring"]``) batches of the ring,
    each ``{"tokens", "labels"}`` of shape ``(batch, seq)``."""
    count = count or spec["ring"]

    def make(kd):
        key = jax.random.wrap_key_data(kd, impl="threefry2x32")
        perm = jax.random.permutation(jax.random.fold_in(key, 7919), vocab)
        return [_batch(jax.random.fold_in(key, i), perm, vocab,
                       spec["batch"], spec["seq"], spec["p_copy"])
                for i in range(count)]

    return jax.jit(make)(jnp.asarray(common.key_data(seed)))
