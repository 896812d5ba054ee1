"""Weights made by the benchmark from ``--seed``, in the layout the program
takes (``params`` of ``repro.models.model``), drawn by a rule of the
benchmark's own so that the reference can make the same ones itself.

Both configurations are pre-norm decoder stacks with the layer weights
stacked on a leading layer axis.  A DYAD projection ``f_in -> f_out`` holds
two ``(n, f_out/n, f_in/n)`` block tensors ``w1`` (block-diagonal) and
``w2`` (block-diagonal after the variant's feature permutation).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common


def dims(cfg: dict) -> dict:
    """The sizes the weights and the reference need, from a configuration
    file's published keys."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    opt = cfg["model_type"] == "opt"
    return {
        "layers": cfg["num_hidden_layers"],
        "d": d,
        "vocab": cfg["vocab_size"],
        "heads": heads,
        "kv_heads": cfg.get("num_key_value_heads", heads),
        "hd": cfg.get("head_dim", d // heads),
        "ff": cfg["ffn_dim"] if opt else cfg["intermediate_size"],
        "n": cfg["dyad"]["n_dyad"],
        "ff_bias": cfg["dyad"]["bias"],
        # OPT: LayerNorm, learned positions, ReLU ff.  Qwen3: RMSNorm,
        # rope, qk-norm, SwiGLU ff with the down projection DYAD-OT.
        "opt": opt,
        "positions": cfg["max_position_embeddings"] if opt else 0,
        "rope_theta": None if opt else float(cfg["rope_theta"]),
        "eps": 1e-5 if opt else cfg["rms_norm_eps"],
    }


def _uniform(key, shape, f_in):
    k = 1.0 / np.sqrt(f_in)
    return jax.random.uniform(key, shape, jnp.float32, -k, k)


def _norm(key, n_layers, dim, layernorm: bool):
    ks, kb = jax.random.split(key)
    p = {"scale": 1.0 + 0.1 * jax.random.normal(ks, (n_layers, dim))}
    if layernorm:
        p["bias"] = 0.1 * jax.random.normal(kb, (n_layers, dim))
    return p


def _dyad(key, L, f_in, f_out, n, bias: bool):
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (L, n, f_out // n, f_in // n)
    p = {"w1": _uniform(k1, shape, f_in), "w2": _uniform(k2, shape, f_in)}
    if bias:
        p["b"] = _uniform(k3, (L, f_out), f_in)
    return p


def make(cfg: dict, key_data) -> dict:
    """The parameter tree, float32, from the seed's key data (see
    :func:`common.key_data`).  Call under ``jax.jit`` with ``cfg`` static
    (:func:`make_jit`) so that one program makes every leaf on the device."""
    m = dims(cfg)
    L, d, hd, n = m["layers"], m["d"], m["hd"], m["n"]
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    ks = iter(jax.random.split(key, 16))
    attn = {
        "wq": {"w": _uniform(next(ks), (L, m["heads"] * hd, d), d)},
        "wk": {"w": _uniform(next(ks), (L, m["kv_heads"] * hd, d), d)},
        "wv": {"w": _uniform(next(ks), (L, m["kv_heads"] * hd, d), d)},
        "wo": {"w": _uniform(next(ks), (L, d, m["heads"] * hd),
                             m["heads"] * hd)},
    }
    if not m["opt"]:
        attn["q_norm"] = _norm(next(ks), L, hd, False)
        attn["k_norm"] = _norm(next(ks), L, hd, False)
    mlp = {"up": _dyad(next(ks), L, d, m["ff"], n, m["ff_bias"]),
           "down": _dyad(next(ks), L, m["ff"], d, n, m["ff_bias"])}
    if not m["opt"]:
        mlp["gate"] = _dyad(next(ks), L, d, m["ff"], n, m["ff_bias"])
    layers = {"norm1": _norm(next(ks), L, d, m["opt"]),
              "attn": attn,
              "norm2": _norm(next(ks), L, d, m["opt"]),
              "mlp": mlp}
    final = jax.tree.map(lambda a: a[0],
                         _norm(next(ks), 1, d, m["opt"]))
    p = {"embed": {"table": jax.random.normal(next(ks), (m["vocab"], d))
                   / np.sqrt(d)},
         "layers": layers,
         "final_norm": final}
    if m["positions"]:
        p["pos"] = {"table": jax.random.normal(next(ks), (m["positions"], d))
                    / np.sqrt(d)}
    return p


def make_jit(cfg: dict, seed: int) -> dict:
    """:func:`make` as one jitted call on the default device."""
    fn = jax.jit(lambda kd: make(cfg, kd))
    return fn(jnp.asarray(common.key_data(seed)))
