"""DeepSeek-V3 decoder stacks (Moonlight-16B-A3B): latent attention (MLA)
without q compression, leading dense SwiGLU layers, then expert layers of
sigmoid-scored routed experts with a selection bias, normalised top-k
weights times a routed scale, and shared experts; RMSNorm, an untied head.

The configuration is one chip's share of an expert-parallel deployment:
the router scores all ``router_experts`` and picks its top-k, and the chip
computes the part of the layer that its own experts (``n_routed_experts``
of them, ids from ``experts_held_first``) give, for the tokens routed to
them; the shared experts are counted once.  The reference computes that
share too.  It follows the published description (DeepSeek-V3's
``modeling_deepseek.py``); its departures, each the program's as well:

* the selection bias is a weight drawn from the seed and held fixed (no
  load-based update), and the sequence-wise balance loss is left out;
* every ff projection (dense layer, shared and routed experts) is DYAD-IT,
  two block-sparse components, in place of a dense matrix;
* no multi-token prediction layers (the config has none).

The latent attention runs unabsorbed: ``k_nope`` and ``v`` per head from
the normalised latent.  The ff projections' weights are stacked on a
leading layer axis, the routed experts' on a second, expert axis; DYAD
projections hold ``w1`` and ``w2`` as in ``chipbench/models/decoder.py``.
The program keeps the leading dense layers under ``dense_layers`` and the
expert layers under ``layers``.

This module has the training counts only (no serving cell runs it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import work
from chipbench.reference import (causal_attention, dense, dyad, mm, rmsnorm,
                                 rope)

LATENT_EPS = 1e-6        # DeepSeek-V3's RMSNorm default, the latent's norm
BIAS_SPREAD = 0.1        # the selection bias: BIAS_SPREAD x normal
HEAD_GROUP = 2           # the reference's attention, heads at a time
LOSS_ROWS = 2048         # the reference's logits, positions at a time


def dims(cfg: dict) -> dict:
    """The sizes the weights and the reference need, from a configuration
    file's published keys."""
    return {
        "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["first_k_dense_replace"],
        "d": cfg["hidden_size"],
        "vocab": cfg["vocab_size"],
        "heads": cfg["num_attention_heads"],
        "latent": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"],
        "dense_ff": cfg["intermediate_size"],
        "expert_ff": cfg["moe_intermediate_size"],
        "experts": cfg["router_experts"],
        "held": cfg["n_routed_experts"],
        "first": cfg["experts_held_first"],
        "top_k": cfg["num_experts_per_tok"],
        "shared_ff": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "norm_topk": cfg["norm_topk_prob"],
        "routed_scale": cfg["routed_scaling_factor"],
        "rope_theta": float(cfg["rope_theta"]),
        "eps": cfg["rms_norm_eps"],
        "n": cfg["dyad"]["n_dyad"],
    }


def program_sizes(cfg: dict) -> dict:
    m = dims(cfg)
    return {"n_layers": m["layers"], "n_dense_layers": m["dense_layers"],
            "d_model": m["d"], "vocab_size": m["vocab"],
            "n_heads": m["heads"], "kv_lora_rank": m["latent"],
            "qk_nope_head_dim": m["nope"], "qk_rope_head_dim": m["rope"],
            "v_head_dim": m["v"], "d_ff": m["dense_ff"],
            "expert_d_ff": m["expert_ff"], "n_experts": m["experts"],
            "n_experts_held": m["held"], "expert_first": m["first"],
            "top_k": m["top_k"], "n_shared": cfg["n_shared_experts"],
            "router_score": cfg["scoring_func"],
            "norm_topk": m["norm_topk"], "routed_scale": m["routed_scale"],
            "rope_theta": m["rope_theta"], "norm_eps": m["eps"],
            "tie_embeddings": cfg["tie_word_embeddings"]}


def smoke(cfg: dict):
    """Smoke sizes: 1 dense + 2 expert layers, 4 of 16 experts held (ids
    4-7), top-4."""
    return ({"hidden_size": 64, "num_hidden_layers": 3,
             "intermediate_size": 128, "num_attention_heads": 4,
             "num_key_value_heads": 4, "kv_lora_rank": 32,
             "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "moe_intermediate_size": 32,
             "router_experts": 16, "n_routed_experts": 4,
             "experts_held_first": 4, "num_experts_per_tok": 4,
             "vocab_size": 256},
            {"n_layers": 3, "d_model": 64, "vocab_size": 256, "n_heads": 4,
             "n_kv_heads": 4, "head_dim": 24, "kv_lora_rank": 32,
             "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "d_ff": 128, "expert_d_ff": 32,
             "n_experts": 16, "n_experts_held": 4, "expert_first": 4,
             "top_k": 4})


# -- weights ------------------------------------------------------------------

def _uniform(key, shape, f_in):
    k = 1.0 / np.sqrt(f_in)
    return jax.random.uniform(key, shape, jnp.float32, -k, k)


def _scale(key, shape):
    return {"scale": 1.0 + 0.1 * jax.random.normal(key, shape)}


def _dyad(key, lead: tuple, n: int, f_in: int, f_out: int):
    k1, k2 = jax.random.split(key)
    shape = (*lead, n, f_out // n, f_in // n)
    return {"w1": _uniform(k1, shape, f_in), "w2": _uniform(k2, shape, f_in)}


def _swiglu(key, lead: tuple, n: int, d: int, f: int):
    kg, ku, kd = jax.random.split(key, 3)
    return {"gate": _dyad(kg, lead, n, d, f), "up": _dyad(ku, lead, n, d, f),
            "down": _dyad(kd, lead, n, f, d)}


def _attention(key, m, L):
    ks = jax.random.split(key, 5)
    d, H, lat = m["d"], m["heads"], m["latent"]
    return {
        "wq": {"w": _uniform(ks[0], (L, H * (m["nope"] + m["rope"]), d), d)},
        "wkv_a": {"w": _uniform(ks[1], (L, lat + m["rope"], d), d)},
        "kv_norm": _scale(ks[2], (L, lat)),
        "wkv_b": {"w": _uniform(ks[3], (L, H * (m["nope"] + m["v"]), lat),
                                lat)},
        "wo": {"w": _uniform(ks[4], (L, d, H * m["v"]), H * m["v"])},
    }


def _group(key, m, L, expert: bool):
    ks = jax.random.split(key, 8)
    d, n = m["d"], m["n"]
    p = {"norm1": _scale(ks[0], (L, d)), "attn": _attention(ks[1], m, L),
         "norm2": _scale(ks[2], (L, d))}
    if not expert:
        p["mlp"] = _swiglu(ks[3], (L,), n, d, m["dense_ff"])
        return p
    p["moe"] = {
        "router": {"w": _uniform(ks[4], (L, m["experts"], d), d)},
        "score_bias": BIAS_SPREAD * jax.random.normal(
            ks[5], (L, m["experts"])),
        "experts": _swiglu(ks[6], (L, m["held"]), n, d, m["expert_ff"]),
        "shared": _swiglu(ks[7], (L,), n, d, m["shared_ff"]),
    }
    return p


def make(cfg: dict, key_data) -> dict:
    """The parameter tree, float32, from the seed's key data (see
    :func:`chipbench.common.key_data`), in the program's layout."""
    m = dims(cfg)
    d = m["d"]
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    ks = jax.random.split(key, 6)
    return {
        "embed": {"table": jax.random.normal(ks[0], (m["vocab"], d))
                  / np.sqrt(d)},
        "dense_layers": _group(ks[1], m, m["dense_layers"], False),
        "layers": _group(ks[2], m, m["layers"] - m["dense_layers"], True),
        "final_norm": _scale(ks[3], (d,)),
        "head": {"table": jax.random.normal(ks[4], (m["vocab"], d))
                 / np.sqrt(d)},
    }


# -- the reference ------------------------------------------------------------

def rope_deepseek(x, pos, theta):
    """DeepSeek-V3's rope: the pairs are stored interleaved; de-interleave,
    then rotate by halves."""
    d = x.shape[-1]
    x = jnp.swapaxes(x.reshape(*x.shape[:-1], d // 2, 2), -1, -2)
    return rope(x.reshape(*x.shape[:-2], d), pos, theta)


def attention(m, p, h, pos, prec: str, q_block: int):
    """Causal latent attention of one sequence ``h (S, d)``, unabsorbed."""
    S = h.shape[0]
    H, nope, rp = m["heads"], m["nope"], m["rope"]
    q = dense(p["wq"], h, prec).reshape(S, H, nope + rp)
    kv_a = dense(p["wkv_a"], h, prec)
    c_kv = rmsnorm(p["kv_norm"], kv_a[:, :m["latent"]], LATENT_EPS)
    k_pe = rope_deepseek(kv_a[:, None, m["latent"]:], pos, m["rope_theta"])
    kv = dense(p["wkv_b"], c_kv, prec).reshape(S, H, nope + m["v"])
    q = jnp.concatenate(
        [q[..., :nope], rope_deepseek(q[..., nope:], pos, m["rope_theta"])],
        -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (S, H, rp))], -1)
    # HEAD_GROUP heads at a time, each group recomputed in the backward:
    # the score blocks of all heads would not fit beside the state
    def by_group(x):
        return jnp.swapaxes(x.reshape(S, H // HEAD_GROUP, HEAD_GROUP, -1),
                            0, 1)

    o = jax.lax.map(
        jax.checkpoint(lambda a: causal_attention(*a, prec, q_block)),
        (by_group(q), by_group(k), by_group(kv[..., nope:])))
    return dense(p["wo"], jnp.swapaxes(o, 0, 1).reshape(S, -1), prec)


def swiglu(p, h, prec: str):
    g = dyad(p["gate"], h, "it", prec)
    u = dyad(p["up"], h, "it", prec)
    return dyad(p["down"], jax.nn.silu(g) * u, "it", prec)


def routing(m, p, h, prec: str):
    """Each token's weight for each held expert ``(S, held)``: sigmoid
    scores over all experts, the top-k of score + bias, their scores
    normalised and scaled; zero for an expert the token did not pick."""
    s = jax.nn.sigmoid(mm("sd,ed->se", h, p["router"]["w"], prec))
    _, idx = jax.lax.top_k(s + p["score_bias"], m["top_k"])
    w = jnp.take_along_axis(s, idx, -1)
    if m["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * m["routed_scale"]
    held = m["first"] + jnp.arange(m["held"])
    return jnp.einsum("sk,ske->se", w,
                      (idx[:, :, None] == held).astype(jnp.float32))


def moe(m, p, h, prec: str):
    """The held experts' part of the expert layer, each expert over every
    token weighed by its routing weight (zero where not routed), plus the
    shared experts."""
    @jax.checkpoint
    def expert(y, ew):
        ex, we = ew
        return y + we[:, None] * swiglu(ex, h, prec), None

    y, _ = jax.lax.scan(expert, swiglu(p["shared"], h, prec),
                        (p["experts"], routing(m, p, h, prec).T))
    return y


def hidden(m, params, tokens, prec: str, q_block: int = 2048):
    """Final-norm hidden states ``(S, d)`` of one sequence."""
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"]["table"][tokens]

    def block(ff):
        # each layer recomputed in the backward, so that one sequence's
        # gradient fits a chip: the math is unchanged
        @jax.checkpoint
        def layer(x, lp):
            x = x + attention(m, lp["attn"], rmsnorm(lp["norm1"], x, m["eps"]),
                              pos, prec, q_block)
            x = x + ff(lp, rmsnorm(lp["norm2"], x, m["eps"]))
            return x, None
        return layer

    x, _ = jax.lax.scan(block(lambda lp, h: swiglu(lp["mlp"], h, prec)), x,
                        params["dense_layers"])
    x, _ = jax.lax.scan(block(lambda lp, h: moe(m, lp["moe"], h, prec)), x,
                        params["layers"])
    return rmsnorm(params["final_norm"], x, m["eps"])


def logits_at(m, params, tokens, where, prec: str):
    """Logits ``(len(where), vocab)`` of one sequence at positions
    ``where`` (untied head)."""
    h = hidden(m, params, tokens, prec)[where]
    return mm("sd,vd->sv", h, params["head"]["table"], prec)


def nll_sum(m, params, tokens, labels, prec: str):
    """Summed next-token negative log-likelihood of a block of sequences,
    the logits LOSS_ROWS positions at a time (each block recomputed in
    the backward: a sequence's logits and their gradient would not fit
    beside the state)."""
    @jax.checkpoint
    def block(hy):
        h, y = hy
        z = mm("sd,vd->sv", h, params["head"]["table"], prec)
        gold = jnp.take_along_axis(z, y[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(z, -1) - gold)

    def one(t, y):
        h = hidden(m, params, t, prec)
        rows = min(LOSS_ROWS, h.shape[0])
        n = h.shape[0] // rows
        return jnp.sum(jax.lax.map(block, (h.reshape(n, rows, -1),
                                           y.reshape(n, rows))))
    return sum(one(tokens[i], labels[i]) for i in range(tokens.shape[0]))


# -- work counts (by the rules of chipbench/work.py) --------------------------

def dyad_weights(m: dict, f_in: int, f_out: int) -> int:
    """Nonzero weights of one DYAD projection."""
    return 2 * f_in * f_out // m["n"]


def swiglu_projections(d: int, f: int) -> list:
    return [(d, f), (d, f), (f, d)]


def swiglu_flops_per_token(m: dict, f: int) -> int:
    return sum(2 * dyad_weights(m, i, o)
               for i, o in swiglu_projections(m["d"], f))


def attn_proj_flops_per_token(m: dict) -> int:
    """One layer's dense q, kv_a, kv_b and o projections, forward."""
    d, H = m["d"], m["heads"]
    return 2 * (d * H * (m["nope"] + m["rope"])
                + d * (m["latent"] + m["rope"])
                + m["latent"] * H * (m["nope"] + m["v"])
                + H * m["v"] * d)


def attn_core_flops_per_token(m: dict, seq: int) -> float:
    """QKᵀ at the qk width and AV at the v width over the causal half."""
    return seq * (m["nope"] + m["rope"] + m["v"]) * m["heads"]


def routed_rows_per_token(m: dict) -> float:
    """Token copies computed here per token, at their expectation: top-k
    over all experts, of which the held share."""
    return m["top_k"] * m["held"] / m["experts"]


def expert_layer_flops_per_token(m: dict) -> float:
    """Router, the held experts' expected rows, the shared experts."""
    return (2 * m["d"] * m["experts"]
            + routed_rows_per_token(m) * swiglu_flops_per_token(
                m, m["expert_ff"])
            + swiglu_flops_per_token(m, m["shared_ff"]))


def forward_flops_per_token(m: dict, seq: int) -> float:
    expert_layers = m["layers"] - m["dense_layers"]
    return (m["layers"] * (attn_proj_flops_per_token(m)
                           + attn_core_flops_per_token(m, seq))
            + m["dense_layers"] * swiglu_flops_per_token(m, m["dense_ff"])
            + expert_layers * expert_layer_flops_per_token(m)
            + 2 * m["d"] * m["vocab"])


def train_flops_per_token(m: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(m, seq)


def _calls(rows: float, f_in: int, f_out: int, weights: int) -> list:
    """(flops, bytes) of a projection's forward, dgrad and wgrad over
    ``rows``: x in and y out (or dy, dx), each weight once."""
    flops = 2 * weights * rows
    return [(flops, work.BYTES * (rows * (f_in + f_out) + weights))] * 3


def train_dyad_mm_calls(m: dict, batch: int, seq: int) -> list:
    """The DYAD kernel calls of one training step (``_mm_impl``,
    ``_wgrad_impl``): the dense layers' SwiGLU and the shared experts'."""
    T = batch * seq
    calls = []
    for f, layers in ((m["dense_ff"], m["dense_layers"]),
                      (m["shared_ff"], m["layers"] - m["dense_layers"])):
        for f_in, f_out in swiglu_projections(m["d"], f):
            calls += _calls(T, f_in, f_out,
                            dyad_weights(m, f_in, f_out)) * layers
    return calls


def train_expert_gmm_calls(m: dict, batch: int, seq: int) -> list:
    """The grouped DYAD kernel calls of one training step (``_gmm_impl``,
    ``_gmm_wgrad_impl``): per expert layer and projection a forward, a
    dgrad and a wgrad over the rows routed to the held experts, counted at
    their expectation (tokens x top-k x held / experts), each held
    expert's weights once; padding and skipped tiles do not count."""
    rows = batch * seq * routed_rows_per_token(m)
    calls = []
    for f_in, f_out in swiglu_projections(m["d"], m["expert_ff"]):
        w = dyad_weights(m, f_in, f_out)
        flops = 2 * w * rows
        nbytes = work.BYTES * (rows * (f_in + f_out) + m["held"] * w)
        calls += [(flops, nbytes)] * 3 * (m["layers"] - m["dense_layers"])
    return calls


def train_flash_calls(m: dict, batch: int, seq: int) -> list:
    """(flops, bytes) of the attention kernels of one training step: per
    layer the causal forward (q, k, v read, o written) and its backward
    (twice the FLOPs; q, k, v, o, do read, dq, dk, dv written), q and k
    at the qk width, v, o and do at the v width."""
    T, H = batch * seq, m["heads"]
    qk, v = T * H * (m["nope"] + m["rope"]), T * H * m["v"]
    fwd = attn_core_flops_per_token(m, seq) * T
    return ([(fwd, work.BYTES * (2 * qk + 2 * v))] * m["layers"]
            + [(2 * fwd, work.BYTES * (4 * qk + 4 * v))] * m["layers"])
