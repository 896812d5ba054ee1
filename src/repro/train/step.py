"""Train/eval steps: value_and_grad + microbatch accumulation + optimizer.

The returned step function is pure (state, batch) -> (state, metrics) and is
what the launcher jits with in/out shardings — the SAME function serves the
single-host tests and the 512-chip dry-run.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import model
from repro.models.config import ModelCfg
from repro.optim.adamw import AdamW
from repro.optim.compress import Compressor


def init_train_state(cfg: ModelCfg, opt: AdamW, key,
                     compressor: Optional[Compressor] = None) -> dict:
    params = model.init_params(cfg, key)
    state = {"params": params, "opt": opt.init(params)}
    if compressor is not None and compressor.codec != "none":
        state["compress"] = compressor.init(params)
    return state


def make_train_step(cfg: ModelCfg, opt: AdamW,
                    compressor: Optional[Compressor] = None,
                    nan_guard: bool = True):
    """Build the pure (state, batch) -> (state, metrics) step.

    ``nan_guard=True`` (default) adds an IN-JIT skip-step: when the loss or
    gradient norm comes out non-finite, the optimizer update is discarded
    (``state`` passes through unchanged, selected inside the jit — the
    launcher donates ``state``, so a host-side retry of the old state is
    impossible) and ``metrics["nonfinite"]`` is 1.  The trainer counts
    consecutive strikes and rolls back to the last checkpoint.

    A ``"_fault_poison"`` batch key (float scalar, injected by the trainer
    when the ``nan_loss`` fault site is armed) multiplies the gradients and
    the loss metric by NaN when nonzero — it is popped before the batch
    reaches the model, so the loss itself is oblivious."""
    accum = max(cfg.grad_accum, 1)

    def loss_of(params, batch):
        return model.loss_fn(cfg, params, batch)

    def train_step(state, batch):
        poison = None
        if isinstance(batch, dict) and "_fault_poison" in batch:
            batch = dict(batch)
            poison = batch.pop("_fault_poison")
        params = state["params"]
        if accum == 1:
            (_, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch)
        else:
            def split(x):
                # micro-batch-major layout: each device keeps its LOCAL batch
                # shard across ALL accumulation steps (the naive
                # reshape(accum, B//accum) would partition the scan axis
                # across data-parallel devices).
                return x.reshape(x.shape[0] // accum, accum,
                                 *x.shape[1:]).swapaxes(0, 1)
            micro = jax.tree.map(split, batch)

            def mb(carry, b):
                gsum, gcomp, msum = carry
                (_, m), g = jax.value_and_grad(loss_of, has_aux=True)(params, b)
                # Kahan-compensated sum: the per-microbatch gradients are the
                # same magnitude, so a plain sequential sum loses ~accum ulps
                # of the mean; the compensation term keeps the accumulated
                # gradient within 1 ulp of the exact sum regardless of accum.
                y = jax.tree.map(jnp.subtract, g, gcomp)
                t = jax.tree.map(jnp.add, gsum, y)
                gcomp = jax.tree.map(lambda t_, s, y_: (t_ - s) - y_,
                                     t, gsum, y)
                msum = jax.tree.map(jnp.add, msum, m)
                return (t, gcomp, msum), None

            zero_g = jax.tree.map(jnp.zeros_like, params)
            zero_c = jax.tree.map(jnp.zeros_like, params)
            zero_m = {"loss": jnp.zeros(()), "aux": jnp.zeros(()),
                      "ppl_proxy": jnp.zeros(())}
            (grads, _, msum), _ = jax.lax.scan(
                mb, (zero_g, zero_c, zero_m), micro)
            grads = jax.tree.map(lambda g: g / accum, grads)
            metrics = jax.tree.map(lambda m: m / accum, msum)

        if poison is not None:
            nanify = jnp.where(jnp.asarray(poison) != 0,
                               jnp.float32(jnp.nan), jnp.float32(1.0))
            grads = jax.tree.map(lambda g: g * nanify.astype(g.dtype), grads)
            metrics = dict(metrics, loss=metrics["loss"] * nanify)

        with jax.named_scope("optimizer"):
            new_state = dict(state)
            if "compress" in state and compressor is not None:
                grads, new_state["compress"] = compressor.compress_decompress(
                    grads, state["compress"])
            new_params, new_opt, om = opt.update(grads, state["opt"], params)
            new_state["params"], new_state["opt"] = new_params, new_opt
            metrics = dict(metrics, **om)
            if nan_guard:
                # skip-step, decided INSIDE the jit: a non-finite loss or
                # grad norm keeps the old state leaf-for-leaf.  grad_norm
                # is the cheap single-scalar witness for "any grad is
                # non-finite" (AdamW already computes it), loss catches
                # forward blowups.
                ok = (jnp.isfinite(metrics["loss"])
                      & jnp.isfinite(om["grad_norm"]))
                new_state = jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o).astype(n.dtype)
                    if hasattr(n, "dtype") else n,
                    new_state, state)
                metrics = dict(metrics, nonfinite=(~ok).astype(jnp.float32))
        return new_state, metrics

    return train_step


def make_eval_step(cfg: ModelCfg):
    def eval_step(params, batch):
        _, metrics = model.loss_fn(cfg, params, batch)
        return metrics
    return eval_step
