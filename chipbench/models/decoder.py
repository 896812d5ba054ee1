"""Pre-norm decoder stacks with GQA attention and a tied head: OPT
(LayerNorm, learned positions, ReLU ff) and Qwen3 (RMSNorm, rope,
qk-norm, SwiGLU ff with the down projection DYAD-OT).

The ff projections are DYAD, or dense where the configuration's
``program.linear`` is ``dense``.  The layer weights are stacked on a
leading layer axis.  A DYAD projection ``f_in -> f_out`` holds two ``(n,
f_out/n, f_in/n)`` block tensors ``w1`` (block-diagonal) and ``w2``
(block-diagonal after the variant's feature permutation); a dense one a
``(f_out, f_in)`` matrix ``w`` (``repro/core/linear.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import work
from chipbench.reference import (causal_attention, dense, dyad, layernorm, mm,
                                 rmsnorm, rope)


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
        "dense": cfg["program"]["linear"] == "dense",
        # OPT: LayerNorm, learned positions, ReLU ff.  Qwen3: RMSNorm,
        # rope, qk-norm, SwiGLU ff with the down projection DYAD-OT.
        "opt": opt,
        "positions": cfg["max_position_embeddings"] if opt else 0,
        "rope_theta": None if opt else float(cfg["rope_theta"]),
        "eps": 1e-5 if opt else cfg["rms_norm_eps"],
    }


def program_sizes(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {"n_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"], "vocab_size": cfg["vocab_size"],
            "n_heads": heads,
            "n_kv_heads": cfg.get("num_key_value_heads", heads),
            "hd": cfg.get("head_dim", cfg["hidden_size"] // heads),
            "d_ff": cfg.get("intermediate_size", cfg.get("ffn_dim"))}


def smoke(cfg: dict):
    if cfg["model_type"] == "opt":
        return ({"hidden_size": 64, "num_hidden_layers": 2, "ffn_dim": 128,
                 "num_attention_heads": 4, "vocab_size": 256,
                 "max_position_embeddings": 128, "word_embed_proj_dim": 64},
                {"n_layers": 2, "d_model": 64, "vocab_size": 256,
                 "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
                 "max_position": 128})
    return ({"hidden_size": 64, "num_hidden_layers": 2,
             "intermediate_size": 128, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256},
            {"n_layers": 2, "d_model": 64, "vocab_size": 256, "n_heads": 4,
             "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
             "attn_chunk": None})


# -- weights ------------------------------------------------------------------

def _uniform(key, shape, f_in):
    k = 1.0 / np.sqrt(f_in)
    return jax.random.uniform(key, shape, jnp.float32, -k, k)


def _norm(key, n_layers, dim, bias: bool):
    ks, kb = jax.random.split(key)
    p = {"scale": 1.0 + 0.1 * jax.random.normal(ks, (n_layers, dim))}
    if bias:
        p["bias"] = 0.1 * jax.random.normal(kb, (n_layers, dim))
    return p


def _ff_proj(key, m, f_in, f_out):
    """One stacked ff projection, DYAD or dense, with its bias when the
    configuration's ff has one."""
    L, n = m["layers"], m["n"]
    k1, k2, k3 = jax.random.split(key, 3)
    if m["dense"]:
        p = {"w": _uniform(k1, (L, f_out, f_in), f_in)}
    else:
        shape = (L, n, f_out // n, f_in // n)
        p = {"w1": _uniform(k1, shape, f_in), "w2": _uniform(k2, shape, f_in)}
    if m["ff_bias"]:
        p["b"] = _uniform(k3, (L, f_out), f_in)
    return p


def make(cfg: dict, key_data) -> dict:
    """The parameter tree, float32, from the seed's key data (see
    :func:`chipbench.common.key_data`).  Call under ``jax.jit``
    (:func:`chipbench.models.make_jit`) so that one program makes every
    leaf on the device."""
    m = dims(cfg)
    L, d, hd = m["layers"], m["d"], m["hd"]
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
    mlp = {"up": _ff_proj(next(ks), m, d, m["ff"]),
           "down": _ff_proj(next(ks), m, m["ff"], d)}
    if not m["opt"]:
        mlp["gate"] = _ff_proj(next(ks), m, d, m["ff"])
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


# -- the reference ------------------------------------------------------------

def attention(m, p, h, pos, prec: str, q_block: int):
    """Causal grouped-query attention of one sequence ``h (S, d)``."""
    S = h.shape[0]
    H, K, hd = m["heads"], m["kv_heads"], m["hd"]
    q = dense(p["wq"], h, prec).reshape(S, H, hd)
    k = dense(p["wk"], h, prec).reshape(S, K, hd)
    v = dense(p["wv"], h, prec).reshape(S, K, hd)
    if not m["opt"]:
        q = rope(rmsnorm(p["q_norm"], q, m["eps"]), pos, m["rope_theta"])
        k = rope(rmsnorm(p["k_norm"], k, m["eps"]), pos, m["rope_theta"])
    o = causal_attention(q, k, v, prec, q_block)
    return dense(p["wo"], o, prec)


def ff(m, p, h, prec: str):
    def proj(q, x, variant):
        return dense(q, x, prec) if m["dense"] else dyad(q, x, variant, prec)

    if m["opt"]:
        return proj(p["down"], jax.nn.relu(proj(p["up"], h, "it")), "it")
    g = proj(p["gate"], h, "it")
    u = proj(p["up"], h, "it")
    return proj(p["down"], jax.nn.silu(g) * u, "ot")


def hidden(m, params, tokens, prec: str, q_block: int = 1024):
    """Final-norm hidden states ``(S, d)`` of one sequence."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["embed"]["table"][tokens]
    if m["opt"]:
        x = x + params["pos"]["table"][:S]
    norm = layernorm if m["opt"] else rmsnorm

    def layer(x, lp):
        x = x + attention(m, lp["attn"], norm(lp["norm1"], x, m["eps"]),
                          pos, prec, q_block)
        x = x + ff(m, lp["mlp"], norm(lp["norm2"], x, m["eps"]), prec)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return norm(params["final_norm"], x, m["eps"])


def logits_at(m, params, tokens, where, prec: str):
    """Logits ``(len(where), vocab)`` of one sequence at positions
    ``where`` (tied unembedding)."""
    h = hidden(m, params, tokens, prec)[where]
    return mm("sd,vd->sv", h, params["embed"]["table"], prec)


def nll_sum(m, params, tokens, labels, prec: str):
    """Summed next-token negative log-likelihood of a block of sequences."""
    def one(t, y):
        h = hidden(m, params, t, prec)
        z = mm("sd,vd->sv", h, params["embed"]["table"], prec)
        gold = jnp.take_along_axis(z, y[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(z, -1) - gold)
    return sum(one(tokens[i], labels[i]) for i in range(tokens.shape[0]))


# -- work counts (by the rules of chipbench/work.py) --------------------------

def ff_projections(m: dict) -> list:
    """(f_in, f_out) of every projection of one layer's ff."""
    d, f = m["d"], m["ff"]
    ups = [(d, f)] if m["opt"] else [(d, f), (d, f)]  # Qwen3: gate and up
    return ups + [(f, d)]


def ff_weights(m: dict, f_in: int, f_out: int) -> int:
    """Nonzero weights of one ff projection: a DYAD projection's two
    block-diagonal components, or the whole dense matrix."""
    return f_in * f_out if m["dense"] else 2 * f_in * f_out // m["n"]


def ff_flops_per_token(m: dict) -> int:
    """One layer's ff, forward."""
    return sum(2 * ff_weights(m, i, o) for i, o in ff_projections(m))


def attn_proj_flops_per_token(m: dict) -> int:
    """One layer's dense q, k, v and o projections, forward."""
    d, q, kv = m["d"], m["heads"] * m["hd"], m["kv_heads"] * m["hd"]
    return 2 * d * q + 2 * 2 * d * kv + 2 * q * d


def unembed_flops_per_token(m: dict) -> int:
    return 2 * m["d"] * m["vocab"]


def forward_flops_per_token(m: dict, seq: int) -> float:
    """Forward FLOPs per token of a causal sequence of ``seq`` tokens."""
    attn = 2 * seq * m["hd"] * m["heads"]
    return (m["layers"] * (attn_proj_flops_per_token(m)
                           + ff_flops_per_token(m) + attn)
            + unembed_flops_per_token(m))


def train_flops_per_token(m: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(m, seq)


def train_dyad_mm_calls(m: dict, batch: int, seq: int) -> list:
    """(flops, bytes) of every DYAD kernel call of one training step: per
    layer and ff projection a forward, a dgrad (dx) and a wgrad (dw); none
    where the ff is dense."""
    if m["dense"]:
        return []
    T = batch * seq
    calls = []
    for f_in, f_out in ff_projections(m):
        w = ff_weights(m, f_in, f_out)
        flops = 2 * w * T
        act = work.BYTES * T * (f_in + f_out)  # x in and y out (or dy, dx)
        calls += [(flops, act + work.BYTES * w)] * 3 * m["layers"]
    return calls


def train_flash_calls(m: dict, batch: int, seq: int) -> list:
    """(flops, bytes) of the attention kernels of one training step: per
    layer the causal forward (q, k, v read, o written) and its backward
    (twice the FLOPs; q, k, v, o, do read, dq, dk, dv written)."""
    q = batch * seq * m["heads"] * m["hd"]
    kv = batch * seq * m["kv_heads"] * m["hd"]
    fwd = 2 * seq * m["hd"] * m["heads"] * batch * seq
    return ([(fwd, work.BYTES * (2 * q + 2 * kv))] * m["layers"]
            + [(2 * fwd, work.BYTES * (4 * q + 4 * kv))] * m["layers"])


def serve_ff_calls(m: dict, tokens: int) -> list:
    """The DYAD ff megakernel over ``tokens`` rows, once per layer: each
    weight read once, x read, y written; none where the ff is dense."""
    if m["dense"]:
        return []
    w = sum(ff_weights(m, i, o) for i, o in ff_projections(m))
    flops = 2 * w * tokens
    nbytes = work.BYTES * (w + 2 * tokens * m["d"])
    return [(flops, nbytes)] * m["layers"]


def paged_decode_calls(m: dict, contexts: list) -> list:
    """Paged decode attention of one step, once per layer: every live
    lane's query against its whole context (K and V read once)."""
    ctx = sum(contexts)
    flops = 4 * ctx * m["hd"] * m["heads"]
    nbytes = work.BYTES * (2 * ctx * m["kv_heads"] * m["hd"]
                           + 2 * len(contexts) * m["heads"] * m["hd"])
    return [(flops, nbytes)] * m["layers"]


def serve_step_flops(m: dict, step: dict) -> float:
    """Model FLOPs of one engine step: its prefill chunks (``(pos, len,
    last)``: the tokens at ``pos .. pos + len``, with the unembedding of
    the prompt's last token when ``last``) and one decode token per live
    lane at its context length."""
    proj = attn_proj_flops_per_token(m) + ff_flops_per_token(m)
    per_head = m["hd"] * m["heads"]
    total = 0.0
    for pos, n, last in step["chunks"]:
        # query j of the chunk sees pos + j + 1 keys: 4*hd per key visit
        visits = n * pos + n * (n + 1) / 2
        total += m["layers"] * (n * proj + 4 * per_head * visits)
        total += unembed_flops_per_token(m) if last else 0
    for ctx in step["contexts"]:
        total += m["layers"] * (proj + 4 * per_head * ctx)
        total += unembed_flops_per_token(m)
    return total
