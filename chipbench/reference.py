"""The plain reference's library: the pieces that the model modules'
forward passes and losses (``chipbench/models/``) are built from, and
AdamW, in straightforward ``jax.numpy``, float32 at the highest matmul
precision, written from the published model descriptions and the DYAD
paper.  It imports nothing of the program and takes nothing the program
made: the model modules draw the weights themselves.

``prec="fp8"`` is the control: the same computation with every matmul
operand rounded to float8_e4m3fn under a per-tensor scale (its largest
magnitude to the format's largest value), and every gradient flowing back
into one rounded to float8_e5m2 the same way: the precision step below the
configurations' bfloat16 compute that would tempt a later change.

A DYAD projection is the sum of its two block-sparse components: the
block-diagonal ``w1`` on the input's contiguous blocks, and ``w2`` on the
strided view of the input (variant IT: input feature ``i * n + g`` feeds
block ``g``) or written to the strided view of the output (OT: block
``g``'s row ``o`` is output feature ``o * n + g``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

def _f8(x, dtype):
    """``x`` rounded to ``dtype`` under a per-tensor scale that maps its
    largest magnitude to the format's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _f8_operand(x):
    return _f8(x, jnp.float8_e4m3fn)


def _f8_fwd(x):
    return _f8_operand(x), None


def _f8_bwd(_, g):
    # the cotangent in e5m2 under its own scale, as fp8 training keeps it
    return (_f8(g, jnp.float8_e5m2),)


_f8_operand.defvjp(_f8_fwd, _f8_bwd)


def _q(x, prec: str):
    """A matmul operand in the reference's precision."""
    return x if prec == "fp32" else _f8_operand(x)


def mm(spec: str, a, b, prec: str):
    return jnp.einsum(spec, _q(a, prec), _q(b, prec),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def dyad(p, x, variant: str, prec: str):
    """``x (..., f_in) -> (..., f_out)`` through a DYAD projection."""
    n, d_out, d_in = p["w1"].shape
    lead = x.shape[:-1]
    blocks = x.reshape(*lead, n, d_in)                   # x[g * d_in + i]
    y1 = mm("...gi,goi->...go", blocks, p["w1"], prec).reshape(*lead, -1)
    if variant == "it":
        strided = jnp.swapaxes(x.reshape(*lead, d_in, n), -1, -2)
        y2 = mm("...gi,goi->...go", strided, p["w2"], prec)
        y2 = y2.reshape(*lead, -1)                       # out g * d_out + o
    else:  # "ot"
        z2 = mm("...gi,goi->...go", blocks, p["w2"], prec)
        y2 = jnp.swapaxes(z2, -1, -2).reshape(*lead, -1)  # out o * n + g
    y = y1 + y2
    return y + p["b"] if "b" in p else y


def dense(p, x, prec: str):
    """``x (..., f_in) -> (..., f_out)`` through a dense ``(f_out, f_in)``
    matrix ``w``, with its bias ``b`` where it has one."""
    y = mm("...i,oi->...o", x, p["w"], prec)
    return y + p["b"] if "b" in p else y


def layernorm(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rmsnorm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def rope(x, pos, theta):
    """Rotate-half rope over the whole head (Qwen3's convention)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv           # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def causal_attention(q, k, v, prec: str, q_block: int):
    """Causal grouped-query attention of one sequence: ``q (S, H, hd)``
    against ``k (S, K, hd)`` and ``v (S, K, dv)`` (query head h reads kv
    head h // (H / K)), in blocks of ``q_block`` queries against the keys
    up to each block's end, so that the score matrix never exists whole.
    Returns ``(S, H * dv)``."""
    S, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    q = q.reshape(S, K, G, hd) / np.sqrt(hd)     # head h = kv head h // G
    outs = []
    for s0 in range(0, S, q_block):
        s1 = min(S, s0 + q_block)
        s = mm("qkgh,tkh->kgqt", q[s0:s1], k[:s1], prec)
        causal = np.arange(s1)[None, :] <= np.arange(s0, s1)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        outs.append(mm("kgqt,tkh->qkgh", w, v[:s1], prec))
    return jnp.concatenate(outs, 0).reshape(S, H * v.shape[-1])


def decays(path) -> bool:
    """AdamW's weight decay reaches every leaf of rank >= 2 in the stacked
    layout (layer biases included) except norm scales and biases."""
    name = "/".join(str(getattr(k, "key", k)) for k in path)
    return "norm" not in name


def adamw_step(params, opt_state, grads, step: int, hp: dict):
    """One AdamW step with global-norm clipping and bias correction.
    Returns (params, state, the clipped gradient)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, hp["clip_norm"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = hp["b1"], hp["b2"]
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    m1 = jax.tree.map(lambda mo, g: b1 * mo + (1 - b1) * g,
                      opt_state["m"], grads)
    v1 = jax.tree.map(lambda vo, g: b2 * vo + (1 - b2) * g * g,
                      opt_state["v"], grads)

    def upd(path, p, mo, vo):
        u = (mo / c1) / (jnp.sqrt(vo / c2) + hp["eps"])
        if p.ndim >= 2 and decays(path):
            u = u + hp["weight_decay"] * p
        return p - hp["lr"] * u

    new = jax.tree_util.tree_map_with_path(upd, params, m1, v1)
    return new, {"m": m1, "v": v1}, grads


def leaf_norms(tree) -> dict:
    """{path: L2 norm} of every leaf (a stacked layer leaf counts as one)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in flat}
