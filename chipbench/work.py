"""The rules by which the model modules (``chipbench/models/``) count the
work a step needs by the model's mathematics, FLOPs and least HBM bytes
per kernel call and per step, and what turns a count into a least time.

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
"""
from __future__ import annotations

BYTES = 2  # bf16, the configurations' compute dtype


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline: the larger of compute time at peak and HBM time at peak."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
