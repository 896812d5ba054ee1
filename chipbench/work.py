"""The work a step needs by the model's mathematics: FLOPs and least HBM
bytes, per kernel group and per step.

The counts follow the model, not the implementation, so that whatever
later implements a kernel is held to the same count and no share of a
roofline or of the peak can pass 100%:

* a DYAD projection ``f_in -> f_out`` has ``2 * f_in * f_out / n``
  nonzeros (two block-diagonal components of ``n`` blocks each), so it
  costs ``2 * nnz`` FLOPs per token;
* a dense projection costs ``2 * f_in * f_out`` FLOPs per token;
* causal attention counts half the score matrix: ``2 * S * hd`` FLOPs per
  query and head for QK^T and AV together over a sequence of ``S``; a
  decode query attends its whole context, ``4 * ctx * hd``;
* training is three forward passes (backward = twice forward); remat
  recompute, padding, padded batch lanes and the one-hot embedding matmul
  do not count, nor do norms and activations;
* bytes are each operand read or written once, in the configuration's
  compute dtype (bf16: 2 bytes), weights included (the program reads fp32
  parameters and casts; that cast is the implementation's cost).

``m`` is :func:`chipbench.weights.dims` of a configuration.
"""
from __future__ import annotations

BYTES = 2  # bf16, the configurations' compute dtype


def ff_projections(m: dict) -> list:
    """(f_in, f_out) of every DYAD projection of one layer's ff."""
    d, f = m["d"], m["ff"]
    ups = [(d, f)] if m["opt"] else [(d, f), (d, f)]  # Qwen3: gate and up
    return ups + [(f, d)]


def dyad_nnz(m: dict, f_in: int, f_out: int) -> int:
    return 2 * f_in * f_out // m["n"]


def ff_flops_per_token(m: dict) -> int:
    """One layer's ff, forward."""
    return sum(2 * dyad_nnz(m, i, o) for i, o in ff_projections(m))


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


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline: the larger of compute time at peak and HBM time at peak."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


# -- training: one step of ``batch`` sequences of ``seq`` tokens -------------

def train_dyad_mm_calls(m: dict, batch: int, seq: int) -> list:
    """(flops, bytes) of every DYAD kernel call of one training step: per
    layer and ff projection a forward, a dgrad (dx) and a wgrad (dw)."""
    T = batch * seq
    calls = []
    for f_in, f_out in ff_projections(m):
        w = dyad_nnz(m, f_in, f_out)
        flops = 2 * w * T
        act = BYTES * T * (f_in + f_out)     # x in and y out (or dy, dx)
        calls += [(flops, act + BYTES * w)] * 3 * m["layers"]
    return calls


def train_flash_calls(m: dict, batch: int, seq: int) -> list:
    """(flops, bytes) of the attention kernels of one training step: per
    layer the causal forward (q, k, v read, o written) and its backward
    (twice the FLOPs; q, k, v, o, do read, dq, dk, dv written)."""
    q = batch * seq * m["heads"] * m["hd"]
    kv = batch * seq * m["kv_heads"] * m["hd"]
    fwd = 2 * seq * m["hd"] * m["heads"] * batch * seq
    return ([(fwd, BYTES * (2 * q + 2 * kv))] * m["layers"]
            + [(2 * fwd, BYTES * (4 * q + 4 * kv))] * m["layers"])


# -- serving: one engine step --------------------------------------------------

def serve_ff_calls(m: dict, tokens: int) -> list:
    """The ff megakernel over ``tokens`` rows, once per layer: each DYAD
    weight read once, x read, y written."""
    w = sum(dyad_nnz(m, i, o) for i, o in ff_projections(m))
    flops = 2 * w * tokens
    nbytes = BYTES * (w + 2 * tokens * m["d"])
    return [(flops, nbytes)] * m["layers"]


def paged_decode_calls(m: dict, contexts: list) -> list:
    """Paged decode attention of one step, once per layer: every live
    lane's query against its whole context (K and V read once)."""
    ctx = sum(contexts)
    flops = 4 * ctx * m["hd"] * m["heads"]
    nbytes = BYTES * (2 * ctx * m["kv_heads"] * m["hd"]
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
