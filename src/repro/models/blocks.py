"""Per-layer blocks for every model family.

Each group of like layers uses ONE block kind so the group can be
``jax.lax.scan``-ed over stacked layer params (keeps compiled HLO size O(1) in
depth — required to compile 126-layer models, and the idiomatic TPU pattern).
A family has one group, except a moe stack with leading dense layers
(``ModelCfg.n_dense_layers``: an ``lm`` group, then the ``moe`` group; see
``models.model.layer_groups``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.layers import attention as attn_lib
from repro.layers import mla as mla_lib
from repro.layers import mlp as mlp_lib
from repro.layers import moe as moe_lib
from repro.layers import norms
from repro.layers import ssm as ssm_lib
from repro.models.config import ModelCfg
from repro.sharding import ctx as shard_ctx


def _init_norm(cfg: ModelCfg, dtype):
    if cfg.norm == "layernorm":
        return norms.init_layernorm(cfg.d_model, dtype)
    return norms.init_rmsnorm(cfg.d_model, dtype)


def norm(cfg: ModelCfg, p, x):
    """The config's norm at its eps (the norm's default when unset)."""
    kw = {} if cfg.norm_eps is None else {"eps": cfg.norm_eps}
    if cfg.norm == "layernorm":
        return norms.layernorm(p, x, **kw)
    return norms.rmsnorm(p, x, **kw)


def _apply_norm(cfg: ModelCfg, p, x):
    with jax.named_scope("norm"):
        return norm(cfg, p, x)


def init_block(key, cfg: ModelCfg, kind: str):
    """kind: lm | moe | ssm | hybrid | enc | dec_cross."""
    dtype = cfg.pdtype
    ks = jax.random.split(key, 6)
    p = {"norm1": _init_norm(cfg, dtype)}
    if cfg.mla:
        p["attn"] = mla_lib.init_mla(
            ks[0], cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.linear, dtype=dtype)
    elif kind in ("lm", "moe", "hybrid", "enc", "dec_cross"):
        p["attn"] = attn_lib.init_attention(
            ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.linear,
            qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm, dtype=dtype)
    if kind in ("ssm", "hybrid"):
        skey = ks[1] if kind == "hybrid" else ks[0]
        p["ssm"] = ssm_lib.init_ssm(
            skey, cfg.d_model, cfg.linear, d_state=cfg.ssm_state,
            head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
            n_groups=cfg.ssm_groups, conv_width=cfg.conv_width, dtype=dtype)
    if kind == "hybrid":
        p["bnorm_a"] = norms.init_rmsnorm(cfg.d_model, dtype)
        p["bnorm_s"] = norms.init_rmsnorm(cfg.d_model, dtype)
    if kind == "dec_cross":
        p["xnorm"] = _init_norm(cfg, dtype)
        p["xattn"] = attn_lib.init_attention(
            ks[2], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.linear,
            qkv_bias=cfg.qkv_bias, qk_norm=False, dtype=dtype)
    if kind != "ssm":
        p["norm2"] = _init_norm(cfg, dtype)
        if kind == "moe" and cfg.n_experts_held:
            p["moe"] = moe_lib.init_moe_share(
                ks[3], cfg.d_model, cfg.expert_d_ff, cfg.n_experts,
                cfg.n_experts_held, cfg.linear, n_shared=cfg.n_shared,
                dtype=dtype)
        elif kind == "moe":
            p["moe"] = moe_lib.init_moe(
                ks[3], cfg.d_model, cfg.expert_d_ff, cfg.n_experts, cfg.top_k,
                cfg.linear, n_shared=cfg.n_shared, act=cfg.act,
                n_experts_padded=cfg.e_pad, dtype=dtype)
        else:
            p["mlp"] = mlp_lib.init_mlp(
                ks[3], cfg.d_model, cfg.d_ff, cfg.linear, act=cfg.act,
                bias=cfg.mlp_bias, dtype=dtype)
    return p


def _self_attn(p, cfg: ModelCfg, x, *, causal, cache, positions):
    if cfg.mla:
        if cache is not None or not causal:
            raise NotImplementedError("latent attention has the causal "
                                      "training path only")
        return mla_lib.apply_mla(
            p, x, n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope=cfg.qk_nope_head_dim, qk_rope=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, lin_cfg=cfg.linear,
            rope_theta=cfg.rope_theta, positions=positions,
            flash=cfg.flash_attn), None
    return attn_lib.attention(
        p, x, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
        lin_cfg=cfg.linear,
        rope_theta=cfg.rope_theta if cfg.pos_embed == "rope" else None,
        positions=positions, causal=causal, window=cfg.window,
        chunk=cfg.attn_chunk, flash=cfg.flash_attn, cache=cache)


def _ssm_with_cache(params, cfg: ModelCfg, h, cache, prefill: bool):
    """Cached SSM mixer: one-token recurrent step, or the single-pass
    multi-token prefill (full chunked SSD forward + cache handoff)."""
    if prefill:
        return ssm_lib.ssm_prefill(
            params, h, cache, cfg.linear, d_state=cfg.ssm_state,
            head_dim=cfg.ssm_head_dim, n_groups=cfg.ssm_groups,
            chunk=cfg.ssd_chunk)
    return ssm_lib.ssm_decode_step(
        params, h, cache, cfg.linear, d_state=cfg.ssm_state,
        head_dim=cfg.ssm_head_dim, n_groups=cfg.ssm_groups)


def apply_block(
    params,
    x,
    cfg: ModelCfg,
    kind: str,
    *,
    cache=None,
    enc_out=None,
    positions=None,
    prefill: bool = False,
):
    """Returns (x, new_cache, aux, stats): ``stats`` holds the counters of
    :func:`block_stats` (none but for a chip's-share expert layer).

    ``cache`` selects the cached (serving) path; ``prefill=True`` marks a
    multi-token teacher-forced pass THROUGH the cache (single-pass prefill) —
    attention writes S tokens of K/V at once and the SSM mixer runs the
    chunked SSD forward instead of S recurrent steps.
    """
    new_cache = {} if cache is not None else None
    aux = jnp.zeros((), jnp.float32)
    stats = {}
    causal = kind != "enc"

    x = shard_ctx.constrain_residual(x)
    h = _apply_norm(cfg, params["norm1"], x)
    if kind == "hybrid":
        with jax.named_scope("attn"):
            a, kv = _self_attn(params["attn"], cfg, h, causal=True,
                               cache=cache.get("kv") if cache else None,
                               positions=positions)
        if cache is not None:
            s, sc = _ssm_with_cache(params["ssm"], cfg, h, cache["ssm"],
                                    prefill)
            new_cache = {"kv": kv, "ssm": sc}
        else:
            s = ssm_lib.apply_ssm(
                params["ssm"], h, cfg.linear, d_state=cfg.ssm_state,
                head_dim=cfg.ssm_head_dim, n_groups=cfg.ssm_groups,
                chunk=cfg.ssd_chunk)
        # parallel heads, per-branch output norm, averaged (hymba-style)
        x = x + 0.5 * (norms.rmsnorm(params["bnorm_a"], a) +
                       norms.rmsnorm(params["bnorm_s"], s))
    elif kind == "ssm":
        if cache is not None:
            s, sc = _ssm_with_cache(params["ssm"], cfg, h, cache["ssm"],
                                    prefill)
            new_cache = {"ssm": sc}
        else:
            s = ssm_lib.apply_ssm(
                params["ssm"], h, cfg.linear, d_state=cfg.ssm_state,
                head_dim=cfg.ssm_head_dim, n_groups=cfg.ssm_groups,
                chunk=cfg.ssd_chunk)
        return x + s, new_cache, aux, stats
    else:
        # each sublayer's scope also holds its residual add, so that a
        # fusion XLA roots at that add is named for the sublayer
        with jax.named_scope("attn"):
            a, kv = _self_attn(params["attn"], cfg, h, causal=causal,
                               cache=cache.get("kv") if cache else None,
                               positions=positions)
            x = x + a
        if cache is not None:
            new_cache["kv"] = kv

    if kind == "dec_cross":
        h = _apply_norm(cfg, params["xnorm"], x)
        with jax.named_scope("attn"):
            if cache is not None and "xk" in cache:
                # cross K/V precomputed at prefill; attend directly.
                xa = _cross_from_cache(params["xattn"], cfg, h, cache)
                new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
            else:
                xa, _ = attn_lib.attention(
                    params["xattn"], h, n_heads=cfg.n_heads,
                    n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                    lin_cfg=cfg.linear, rope_theta=None,
                    positions=jnp.arange(h.shape[1]), causal=False,
                    kv_input=enc_out)
            x = x + xa

    h = _apply_norm(cfg, params["norm2"], x)
    with jax.named_scope("ff"):
        if kind == "moe" and cfg.n_experts_held:
            m, stats = moe_lib.apply_moe_share(
                params["moe"], h, cfg.linear, top_k=cfg.top_k,
                first=cfg.expert_first, score=cfg.router_score,
                norm_topk=cfg.norm_topk, routed_scale=cfg.routed_scale,
                chunk=cfg.moe_chunk)
        elif kind == "moe":
            m, aux = moe_lib.apply_moe(
                params["moe"], h, cfg.linear, n_experts=cfg.n_experts,
                top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                act=cfg.act, chunk=cfg.moe_chunk)
        else:
            m = mlp_lib.apply_mlp(params["mlp"], h, cfg.linear, act=cfg.act)
        return x + m, new_cache, aux, stats


def block_stats(cfg: ModelCfg, kind: str) -> dict:
    """Zeroed counters that :func:`apply_block` returns for ``kind``; over
    the layers each is summed or, named ``max_*``, its largest kept."""
    if kind == "moe" and cfg.n_experts_held:
        return {"routed_rows": jnp.zeros((), jnp.float32),
                "max_expert_rows": jnp.zeros((), jnp.float32)}
    return {}


def merge_stats(a: dict, b: dict) -> dict:
    out = {**a, **b}
    for k in a.keys() & b.keys():
        out[k] = (jnp.maximum(a[k], b[k]) if k.startswith("max_")
                  else a[k] + b[k])
    return out


def _cross_from_cache(p, cfg: ModelCfg, q_in, cache):
    """Cross-attention against precomputed encoder K/V (decode path)."""
    from repro.core import factory
    B, S, _ = q_in.shape
    K, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    q = factory.apply(p["wq"], q_in, cfg.linear, site="attn").reshape(
        B, S, cfg.n_heads, cfg.hd)
    if "q_norm" in p:
        q = norms.rmsnorm(p["q_norm"], q)
    qg = q.reshape(B, S, K, G, cfg.hd)
    T = cache["xk"].shape[1]
    o = attn_lib._naive_sdpa(qg, cache["xk"], cache["xv"],
                             jnp.zeros((S,), jnp.int32),
                             jnp.arange(T), False, None)
    o = o.reshape(B, S, cfg.n_heads * cfg.hd)
    return factory.apply(p["wo"], o, cfg.linear, site="attn")


def init_block_cache(cfg: ModelCfg, kind: str, batch: int, max_len: int,
                     dtype=jnp.bfloat16, *, per_slot: bool = False,
                     page_size: int = None, n_pages: int = None):
    """Cache pytree for ONE block (stacked over layers by the model).

    ``per_slot=True`` gives the KV cache a per-batch-row write index so each
    row (continuous-batching slot) can sit at a different sequence position.
    ``page_size``/``n_pages`` swap the dense KV ring for a paged pool +
    block table (see :func:`repro.layers.attention.init_paged_kv_cache`);
    the write index is per-slot by construction there.
    """
    c = {}
    if kind in ("lm", "moe", "hybrid", "dec_cross"):
        if page_size is not None:
            from repro import quant as quant_lib
            kvq = cfg.kv_quant if quant_lib.enabled() else None
            c["kv"] = attn_lib.init_paged_kv_cache(
                batch, max_len, cfg.n_kv_heads, cfg.hd, dtype,
                page_size=page_size, n_pages=n_pages, quant=kvq)
        else:
            # ring buffer when sliding-window attention bounds the reach
            L = min(max_len, cfg.window) if cfg.window else max_len
            c["kv"] = attn_lib.init_kv_cache(batch, L, cfg.n_kv_heads,
                                             cfg.hd, dtype,
                                             per_slot=per_slot)
    if kind in ("ssm", "hybrid"):
        c["ssm"] = ssm_lib.init_ssm_cache(
            batch, cfg.d_model, d_state=cfg.ssm_state,
            head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
            n_groups=cfg.ssm_groups, conv_width=cfg.conv_width,
            dtype=cfg.cdtype)
    if kind == "dec_cross":
        c["xk"] = jnp.zeros((batch, cfg.n_frames, cfg.n_kv_heads, cfg.hd), dtype)
        c["xv"] = jnp.zeros((batch, cfg.n_frames, cfg.n_kv_heads, cfg.hd), dtype)
    return c
