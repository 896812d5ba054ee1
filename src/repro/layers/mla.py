"""Multi-head latent attention (DeepSeek-V2/V3, without q compression),
the training path: no cache.

    q            = x W_q                     (H heads of nope + rope)
    [c_kv, k_pe] = x W_kva                   (kv_lora_rank latent, one rope
                                              key shared by every head)
    [k_nope, v]  = RMSNorm(c_kv) W_kvb       (per head)
    o            = softmax(q kᵀ / sqrt(nope + rope)) v W_o

with rope on ``q_pe`` and ``k_pe`` only, in DeepSeek's convention: the
pairs are stored interleaved and de-interleaved before a rotate-half
rope.  The latent is normalised with eps 1e-6 (the RMSNorm module's
default there; the block norms use the config's ``rms_norm_eps``).  The
projections go through the linear factory with ``site="attn"``.  The
attention runs in the flash kernels with a query/key width of nope + rope
and a value width of ``v_head_dim``, or (off the kernel route) in the
einsum path, which takes the narrower values as they are.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import faults
from repro.core import factory
from repro.kernels import ops as kops
from repro.layers import attention as attn_lib
from repro.layers import norms
from repro.layers.rotary import apply_rope

LATENT_EPS = 1e-6


def init_mla(key, d_model: int, n_heads: int, kv_lora_rank: int,
             qk_nope: int, qk_rope: int, v_dim: int,
             lin_cfg: factory.LinearCfg, *, dtype=jnp.float32):
    ks = jax.random.split(key, 4)

    def proj(k, f_in, f_out):
        return factory.init(k, f_in, f_out, lin_cfg, site="attn", bias=False,
                            dtype=dtype)

    return {
        "wq": proj(ks[0], d_model, n_heads * (qk_nope + qk_rope)),
        "wkv_a": proj(ks[1], d_model, kv_lora_rank + qk_rope),
        "kv_norm": norms.init_rmsnorm(kv_lora_rank, dtype),
        "wkv_b": proj(ks[2], kv_lora_rank, n_heads * (qk_nope + v_dim)),
        "wo": proj(ks[3], n_heads * v_dim, d_model),
    }


def rope_interleaved(x, positions, theta: float):
    """Rope of pairs stored interleaved (``x[2i], x[2i+1]``): de-interleave,
    then rotate half, as DeepSeek-V3 applies it."""
    d = x.shape[-1]
    x = jnp.swapaxes(x.reshape(*x.shape[:-1], d // 2, 2), -1, -2)
    return apply_rope(x.reshape(*x.shape[:-2], d), positions, theta)


def apply_mla(params, x, *, n_heads: int, kv_lora_rank: int, qk_nope: int,
              qk_rope: int, v_dim: int, lin_cfg: factory.LinearCfg,
              rope_theta: float, positions, flash: bool = False):
    """Causal latent attention of ``x (B, S, D)`` at contiguous
    ``positions (S,)``.  Returns ``(B, S, D)``."""
    B, S, _ = x.shape
    H = n_heads

    def proj(name, h):
        return factory.apply(params[name], h, lin_cfg, site="attn")

    q = proj("wq", x).reshape(B, S, H, qk_nope + qk_rope)
    with jax.named_scope("latent"):
        kv_a = proj("wkv_a", x)
        c_kv, k_pe = kv_a[..., :kv_lora_rank], kv_a[..., kv_lora_rank:]
        c_kv = norms.rmsnorm(params["kv_norm"], c_kv, eps=LATENT_EPS)
        kv = proj("wkv_b", c_kv).reshape(B, S, H, qk_nope + v_dim)
    k_nope, v = kv[..., :qk_nope], kv[..., qk_nope:]
    q_pe = rope_interleaved(q[..., qk_nope:], positions, rope_theta)
    k_pe = rope_interleaved(k_pe[:, :, None, :], positions, rope_theta)
    q = jnp.concatenate([q[..., :qk_nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (B, S, H, qk_rope))], axis=-1)
    qg = q[:, :, :, None, :]                     # (B, S, K=H, G=1, qk)
    if flash and kops.attn_route() == "flash":
        o = kops.flash_attention(
            qg, k, v, positions[0], 0, causal=True,
            use_kernel_bwd=getattr(lin_cfg, "use_kernel_bwd", True))
        o = faults.poison(o, "kernel_nan", route="attn_flash")
    else:
        o = attn_lib._naive_sdpa(qg, k, v, positions, jnp.arange(S), True,
                                 None)
    return proj("wo", o.reshape(B, S, H * v_dim))
