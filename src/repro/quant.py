"""Symmetric quantization: ONE codec for weights, KV pages, and gradients.

Per-block weight quantization for DYAD serving (ROADMAP item 3).  The DYAD
3-D tensors ``(n_dyad, d_out, d_in)`` contract ``d_in`` per block, so a
scale per ``(block, out_row)`` — reduced over the contracted axis only —
makes in-kernel dequant EXACT with a single fp32 accumulator: the scale is
constant along k, so

    sum_k x[k] * (q[o, k] * s[o])  ==  (sum_k x[k] * q[o, k]) * s[o]

and the Pallas bodies (:mod:`repro.kernels.dyad_mm`) multiply ``s`` into
the accumulator epilogue per k-step instead of dequantizing the weight
tile.  int8 payloads stream 4x fewer HBM bytes than fp32 (2x vs bf16);
the fp32 scale sidecar is ``1/d_in`` of the payload — noise.

Layout contract (``quantize_params``): quantized leaves ride SIDECAR next
to the retained fp32 originals — ``w1`` keeps its value and ``w1_q``
(int8/fp8, same shape) + ``w1_s`` (fp32, ``(n, d_out)``) appear beside it.
Dispatch sites check :func:`enabled` + sidecar presence; with
``REPRO_KERNEL_QUANT=off`` the sidecars are ignored and every route is
bit-identical to the unquantized build.

KV pages quantize per token-row (scale over the head dim): a page's rows
are written incrementally (decode appends one token at a time), so a true
per-page scalar would depend on future tokens — per-row scales in
page-shaped ``(n_pages, P, K)`` fp32 pools are the finest granularity
that stays exact under incremental writes.

The per-tensor helpers at the bottom are the single codec implementation
the gradient compressor (:mod:`repro.optim.compress`) re-exports.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax.numpy as jnp

_EPS = 1e-12

# dtype name -> (jnp dtype, symmetric max representable value)
_QDTYPES = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
    "float8_e4m3fn": (jnp.float8_e4m3fn, 448.0),
}


def enabled() -> bool:
    """``REPRO_KERNEL_QUANT=off`` disables every quantized route (the
    sidecar leaves are ignored): bit-identical fp32 behavior."""
    return os.environ.get("REPRO_KERNEL_QUANT", "").lower() != "off"


def resolve_dtype(name: str) -> Tuple[jnp.dtype, float]:
    """``(jnp dtype, qmax)`` for a quantization dtype name."""
    if name not in _QDTYPES:
        raise ValueError(f"unknown quantization dtype {name!r} "
                         f"(know {sorted(_QDTYPES)})")
    dtype, qmax = _QDTYPES[name]
    return jnp.dtype(dtype), qmax


def quant_symmetric(g, axis=None, dtype: str = "int8"):
    """Symmetric quantization: ``scale = max|g| / qmax + eps`` reduced over
    ``axis`` (None = per-tensor scalar scale), ``q = round(g / scale)``
    clipped to ±qmax and cast.  Returns ``(q, scale)`` with ``scale``
    keeping the reduced axes SQUEEZED (not kept) — a ``(n, d_out, d_in)``
    weight quantized over ``axis=-1`` yields a ``(n, d_out)`` scale."""
    qd, qmax = resolve_dtype(dtype)
    g = jnp.asarray(g)
    scale = (jnp.max(jnp.abs(g), axis=axis).astype(jnp.float32) / qmax
             + _EPS)
    s_full = scale if axis is None else jnp.expand_dims(scale, axis)
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / s_full), -qmax, qmax)
    return q.astype(qd), scale


def dequant(q, scale, axis=None):
    """Inverse of :func:`quant_symmetric` (fp32): broadcast the squeezed
    scale back over ``axis`` and multiply."""
    s = scale if axis is None else jnp.expand_dims(scale, axis)
    return q.astype(jnp.float32) * s


# -- DYAD weight sidecars -----------------------------------------------------


def quantize_dyad_weight(w, dtype: str = "int8"):
    """One DYAD component ``(n, d_out, d_in)`` -> ``(q, scales)`` with a
    scale per (block, out_row) — reduced over the CONTRACTED ``d_in`` axis
    so the kernels' epilogue-multiply dequant is exact.  A layer-stacked
    ``(n_layers, n, d_out, d_in)`` tensor quantizes the same way (scales
    ``(n_layers, n, d_out)``) — ``lax.scan`` slices the leading axis off
    both leaves before the kernels see them."""
    if w.ndim not in (3, 4):
        raise ValueError(f"expected a [stacked] (n, d_out, d_in) DYAD "
                         f"tensor, got shape {w.shape}")
    return quant_symmetric(w, axis=-1, dtype=dtype)


def _is_dyad_module(node) -> bool:
    return (isinstance(node, dict) and "w1" in node and "w2" in node
            and getattr(node["w1"], "ndim", 0) in (3, 4))


def quantize_params(params, dtype: str = "int8"):
    """Offline pass: walk the param tree and add sidecar quantized leaves
    (``w1_q``/``w1_s``/``w2_q``/``w2_s``) next to every 3-D DYAD module's
    retained fp32 ``w1``/``w2``.  Existing consumers (``"w1" in params``
    checks, shape reads, the ``REPRO_KERNEL_QUANT=off`` escape hatch) keep
    working untouched; quantized dispatch streams the sidecars instead."""
    resolve_dtype(dtype)   # validate before touching the tree

    def walk(node):
        if _is_dyad_module(node):
            out = dict(node)
            for nm in ("w1", "w2"):
                q, s = quantize_dyad_weight(node[nm], dtype)
                out[nm + "_q"], out[nm + "_s"] = q, s
            # nested submodules (none today) would still be walked:
            for k, v in node.items():
                if isinstance(v, dict):
                    out[k] = walk(v)
            return out
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node

    return walk(params)


def module_quantized(params) -> bool:
    """Does this DYAD module dict carry the full quantized sidecar set?"""
    return (isinstance(params, dict)
            and all(k in params for k in
                    ("w1_q", "w1_s", "w2_q", "w2_s")))


def ff_quantized(params) -> bool:
    """Does an ff module tree (``up``/``down``[/``gate``] submodules)
    carry quantized sidecars on every projection?"""
    if not isinstance(params, dict):
        return False
    names = [n for n in ("gate", "up", "down") if n in params]
    return (len(names) >= 2
            and all(module_quantized(params[n]) for n in names))


# -- KV page quantization -----------------------------------------------------


def quantize_kv_rows(x, dtype: str = "int8"):
    """Quantize K/V token rows ``(..., K, h)`` with one scale per
    ``(..., K)`` row (reduced over the head dim — the axis the attention
    dot contracts, so in-kernel dequant-by-row is exact).  Returns
    ``(q, scales)`` with ``scales: (..., K)`` fp32."""
    return quant_symmetric(x, axis=-1, dtype=dtype)


# -- per-tensor codec (re-exported by repro.optim.compress) -------------------


def quant_int8(g):
    """Per-tensor symmetric int8: ``scale = max|g| / 127 + eps``."""
    return quant_symmetric(g, axis=None, dtype="int8")


def dequant_int8(q, scale):
    return q.astype(jnp.float32) * scale
