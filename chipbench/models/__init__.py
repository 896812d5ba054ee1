"""Model modules: everything the harness knows of one architecture.

A configuration file names its module by the key ``model_module``, or else
by its published ``model_type``: the module is
``chipbench/models/<name>.py``.  A new architecture comes as a new module,
a configuration file and a workload file; no harness file changes.  A
module provides:

* ``dims(conf)``: the sizes the other functions take (``m`` below), from
  the configuration file;
* ``make(conf, key_data)``: the parameter tree, float32, in the program's
  layout, drawn from the seed's key data by a rule of the benchmark's own
  (the reference draws the same weights itself);
* ``program_sizes(conf)``: ``{attribute of the program's model config:
  value}``, which :func:`chipbench.common.program_cfg` checks;
* the plain reference (on :mod:`chipbench.reference`):
  ``nll_sum(m, params, tokens, labels, prec)``, the summed next-token
  negative log-likelihood of a block of sequences, and ``logits_at(m,
  params, tokens, where, prec)``, one sequence's logits at ``where``;
* the work counts the readers use (on :mod:`chipbench.work`):
  ``train_flops_per_token(m, seq)``, ``train_dyad_mm_calls(m, batch,
  seq)``, ``train_flash_calls(m, batch, seq)``, ``serve_ff_calls(m,
  tokens)``, ``paged_decode_calls(m, contexts)`` and
  ``serve_step_flops(m, step)``;
* ``smoke(conf)``: ``(sizes, program overrides)`` that cut the
  configuration to the size of the CPU tests (``chipbench/tests``).
"""
from __future__ import annotations

import importlib
import os

DIR = os.path.dirname(os.path.abspath(__file__))


def of(conf: dict):
    """The model module of a configuration file's contents."""
    name = conf.get("model_module", conf["model_type"])
    path = os.path.join(DIR, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"chipbench: no model module {path} for "
                         f"model_type {conf.get('model_type')!r}")
    return importlib.import_module(f"{__name__}.{name}")


def make_jit(conf: dict, seed: int) -> dict:
    """The module's ``make`` for ``seed`` as one jitted call on the default
    device."""
    import jax
    import jax.numpy as jnp

    from chipbench import common

    model = of(conf)
    fn = jax.jit(lambda kd: model.make(conf, kd))
    return fn(jnp.asarray(common.key_data(seed)))
