"""Family-dispatched model: init / forward / loss / cache / decode_step.

Layer params are stacked on a leading ``n_layers`` axis and the stack is
``jax.lax.scan``-ed (with optional remat) — HLO size stays O(1) in depth.

Each layer's ops sit under one ``jax.named_scope`` — ``embed``, ``norm``,
``attn``, ``ff``, ``head`` (here and in ``blocks``) and ``optimizer``
(``train.step``) — which lands in every HLO instruction's ``op_name``,
backward and remat ops included, so a device trace splits by layer.  The
scopes are metadata only: they add no op.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.layers import embed as embed_lib
from repro.layers import frontend as frontend_lib
from repro.layers import norms
from repro.models import blocks
from repro.models.config import ModelCfg

_KIND = {
    "lm": "lm",
    "moe": "moe",
    "ssm": "ssm",
    "vlm": "lm",
    "hybrid": "hybrid",
    "encdec": "dec_cross",
}


def block_kind(cfg: ModelCfg) -> str:
    return _KIND[cfg.family]


def _stacked_init(key, n: int, init_one):
    return jax.vmap(init_one)(jax.random.split(key, n))


def init_params(cfg: ModelCfg, key) -> dict:
    ks = jax.random.split(key, 8)
    dtype = cfg.pdtype
    p = {
        "embed": embed_lib.init_embedding(ks[0], cfg.vocab_size, cfg.d_model,
                                          dtype),
        "layers": _stacked_init(
            ks[1], cfg.n_layers, lambda k: blocks.init_block(k, cfg,
                                                             block_kind(cfg))),
        "final_norm": (norms.init_layernorm(cfg.d_model, dtype)
                       if cfg.norm == "layernorm"
                       else norms.init_rmsnorm(cfg.d_model, dtype)),
    }
    if not cfg.tie_embeddings:
        p["head"] = embed_lib.init_embedding(ks[2], cfg.vocab_size,
                                             cfg.d_model, dtype)
    if cfg.pos_embed == "learned":
        p["pos"] = embed_lib.init_embedding(ks[3], cfg.max_position,
                                            cfg.d_model, dtype)
    if cfg.family == "encdec":
        p["enc_layers"] = _stacked_init(
            ks[4], cfg.n_enc_layers, lambda k: blocks.init_block(k, cfg, "enc"))
        p["enc_norm"] = (norms.init_layernorm(cfg.d_model, dtype)
                         if cfg.norm == "layernorm"
                         else norms.init_rmsnorm(cfg.d_model, dtype))
    if cfg.family in ("encdec", "vlm"):
        p["frontend"] = frontend_lib.init_frontend(
            ks[5], cfg.frontend_dim, cfg.d_model, dtype)
    return p


def _final_norm(cfg, p, x):
    with jax.named_scope("norm"):
        if cfg.norm == "layernorm":
            return norms.layernorm(p, x)
        return norms.rmsnorm(p, x)


def _unembed(params, x):
    with jax.named_scope("head"):
        return embed_lib.unembed(params.get("head", params["embed"]), x)


def _run_stack(cfg: ModelCfg, stacked, x, kind: str, *, enc_out=None,
               positions=None, caches=None, prefill: bool = False):
    """scan over stacked layer params (and caches).  Returns (x, caches, aux)."""

    def body(carry, scanned):
        h, aux = carry
        lp = scanned[0] if caches is not None else scanned
        lc = scanned[1] if caches is not None else None
        h, nc, a = blocks.apply_block(lp, h, cfg, kind, cache=lc,
                                      enc_out=enc_out, positions=positions,
                                      prefill=prefill)
        return (h, aux + a), nc

    if cfg.remat and caches is None:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    xs = (stacked, caches) if caches is not None else stacked
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        xs)
    return x, new_caches, aux


def encode(cfg: ModelCfg, params, frames):
    """Encoder pass (encdec family).  frames: (B, n_frames, frontend_dim)."""
    x = frontend_lib.apply_frontend(params["frontend"], frames)
    x = x.astype(cfg.cdtype)
    x, _, _ = _run_stack(cfg, params["enc_layers"], x, "enc",
                         positions=jnp.arange(x.shape[1]))
    return _final_norm(cfg, params["enc_norm"], x)


def _embed_inputs(cfg: ModelCfg, params, batch, offset=0):
    with jax.named_scope("embed"):
        tokens = batch["tokens"]
        x = embed_lib.embed(params["embed"], tokens, iota=cfg.iota_embed)
        if cfg.family == "vlm" and "patches" in batch:
            pe = frontend_lib.apply_frontend(
                params["frontend"], batch["patches"], add_positions=False)
            x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
        S = x.shape[1]
        # offset may be a scalar (homogeneous batch) or a (B,) vector of
        # per-slot positions (continuous batching) -> positions (S,) or
        # (B, S).
        if getattr(offset, "ndim", 0) == 1:
            positions = offset[:, None] + jnp.arange(S)
        else:
            positions = offset + jnp.arange(S)
        if cfg.pos_embed == "learned":
            # pos table stays gathered
            x = x + embed_lib.embed(params["pos"], positions)
        return x.astype(cfg.cdtype), positions


def forward(cfg: ModelCfg, params, batch, *, last_only: bool = False):
    """Full-sequence forward.  Returns (logits_f32, aux).

    ``last_only`` slices to the final position BEFORE the unembedding —
    the production prefill path (a full (B,S,V) fp32 logit tensor at 32k
    sequence x 150k vocab is tens of GB per device)."""
    x, positions = _embed_inputs(cfg, params, batch)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, params, batch["frames"])
    x, _, aux = _run_stack(cfg, params["layers"], x, block_kind(cfg),
                           enc_out=enc_out, positions=positions)
    x = _final_norm(cfg, params["final_norm"], x)
    if cfg.family == "vlm" and "patches" in batch:
        x = x[:, batch["patches"].shape[1]:]     # logits over text positions
    if last_only:
        x = x[:, -1:]
    return _unembed(params, x), aux


def loss_fn(cfg: ModelCfg, params, batch):
    """Next-token cross-entropy (+ router aux).  labels < 0 are masked."""
    logits, aux = forward(cfg, params, batch)
    with jax.named_scope("head"):
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
        # logsumexp - gold_logit form: partitions cleanly over a
        # vocab-sharded logits axis (no full log_softmax materialization
        # on the bwd pass).
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None],
                                   axis=-1)[..., 0]
        nll = lse - gold
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (nll * mask).sum() / denom
        total = loss + cfg.router_aux_coef * aux
        return total, {"loss": loss, "aux": aux,
                       "ppl_proxy": jnp.exp(jnp.minimum(loss, 20.0))}


def init_cache(cfg: ModelCfg, batch: int, max_len: int, dtype=jnp.bfloat16,
               *, per_slot: bool = False, page_size: int = None,
               n_pages: int = None):
    """Stacked (n_layers-leading) decode cache for ``batch`` sequences.

    ``per_slot=True`` gives every leaf a batch axis at position 1 — including
    the KV write index, which becomes (n_layers, batch) so each slot advances
    independently (the continuous-batching layout).  ``page_size``/``n_pages``
    swap the dense KV rings for per-layer page pools + block tables (the
    paged serving layout; every layer gets its own pool slice, so one page id
    addresses the same logical page in all of them)."""
    one = blocks.init_block_cache(cfg, block_kind(cfg), batch, max_len, dtype,
                                  per_slot=per_slot, page_size=page_size,
                                  n_pages=n_pages)
    stacked = jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf, (cfg.n_layers,) + leaf.shape).copy()
        if leaf.ndim > 0 else jnp.zeros((cfg.n_layers,), leaf.dtype), one)
    return stacked


def prefill_cross(cfg: ModelCfg, params, cache, frames):
    """encdec: run the encoder and fill per-layer cross K/V into the cache."""
    from repro.core import factory
    enc_out = encode(cfg, params, frames)
    B, T, _ = enc_out.shape

    def per_layer(lp):
        k = factory.apply(lp["xattn"]["wk"], enc_out, cfg.linear, site="attn")
        v = factory.apply(lp["xattn"]["wv"], enc_out, cfg.linear, site="attn")
        return (k.reshape(B, T, cfg.n_kv_heads, cfg.hd),
                v.reshape(B, T, cfg.n_kv_heads, cfg.hd))

    xk, xv = jax.vmap(per_layer)(params["layers"])
    cache = dict(cache)
    cache["xk"] = xk.astype(cache["xk"].dtype)
    cache["xv"] = xv.astype(cache["xv"].dtype)
    return cache


def prefill(cfg: ModelCfg, params, cache, tokens, *, frames=None,
            last_only: bool = True):
    """Single-pass prefill: ONE full-sequence forward with cache writes.

    tokens: (B, S) int32 prompts; cache: a fresh (or position-consistent)
    pytree from :func:`init_cache`.  Attention layers write all S tokens of
    K/V in one ``dynamic_update_slice``; SSM layers run the chunked SSD dual
    form and hand off the final recurrent state — no per-token Python loop,
    one jitted call per request batch.

    Requires ``pos + S <= cache length`` for full-length KV caches (windowed
    ring caches additionally need ``S <= window`` at prefill).

    Returns ``(logits, new_cache)`` with logits fp32 ``(B, 1, vocab)`` for the
    last position (``last_only=True``, the production path — a full (B,S,V)
    tensor at 32k x 150k is tens of GB) or ``(B, S, vocab)`` otherwise.  The
    returned cache is positioned at S, ready for :func:`decode_step`.
    """
    if cfg.family == "encdec" and frames is not None:
        cache = prefill_cross(cfg, params, cache, frames)
    offset = _cache_pos(cfg, cache)
    x, positions = _embed_inputs(cfg, params, {"tokens": tokens},
                                 offset=offset)
    x, new_cache, _ = _run_stack(cfg, params["layers"], x, block_kind(cfg),
                                 positions=positions, caches=cache,
                                 prefill=True)
    x = _final_norm(cfg, params["final_norm"], x)
    if last_only:
        x = x[:, -1:]
    return _unembed(params, x), new_cache


def decode_step(cfg: ModelCfg, params, cache, tokens):
    """One-token decode.  tokens: (B, 1).  Returns (logits, new_cache)."""
    offset = _cache_pos(cfg, cache)
    x, positions = _embed_inputs(cfg, params, {"tokens": tokens}, offset=offset)
    x, new_cache, _ = _run_stack(cfg, params["layers"], x, block_kind(cfg),
                                 positions=positions, caches=cache)
    x = _final_norm(cfg, params["final_norm"], x)
    return _unembed(params, x), new_cache


def _cache_pos(cfg: ModelCfg, cache):
    kind = block_kind(cfg)
    if kind in ("lm", "moe", "hybrid", "dec_cross"):
        return cache["kv"]["idx"][0]
    return cache.get("pos", jnp.zeros((), jnp.int32))


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def non_embedding_param_count(params) -> int:
    total = param_count(params)
    emb = int(params["embed"]["table"].size)
    if "head" in params:
        emb += int(params["head"]["table"].size)
    if "pos" in params:
        emb += int(params["pos"]["table"].size)
    return total - emb
