"""Family-dispatched model: init / forward / loss / cache / decode_step.

Layer params are stacked on a leading layer axis and each group of like
layers (:func:`layer_groups`: one group, or a moe stack's leading dense
layers and then its expert layers) is ``jax.lax.scan``-ed (with optional
remat) — HLO size stays O(1) in depth.

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


def layer_groups(cfg: ModelCfg) -> tuple:
    """``(params key, block kind, layers)`` of each scanned group of like
    layers, in stack order: ``layers`` alone, or a moe stack's
    ``n_dense_layers`` leading dense layers (``dense_layers``, kind ``lm``)
    and then its expert layers."""
    if cfg.n_dense_layers:
        return (("dense_layers", "lm", cfg.n_dense_layers),
                ("layers", block_kind(cfg),
                 cfg.n_layers - cfg.n_dense_layers))
    return (("layers", block_kind(cfg), cfg.n_layers),)


def _stacked_init(key, n: int, init_one):
    return jax.vmap(init_one)(jax.random.split(key, n))


def init_params(cfg: ModelCfg, key) -> dict:
    ks = jax.random.split(key, 8)
    dtype = cfg.pdtype
    p = {
        "embed": embed_lib.init_embedding(ks[0], cfg.vocab_size, cfg.d_model,
                                          dtype),
        "final_norm": (norms.init_layernorm(cfg.d_model, dtype)
                       if cfg.norm == "layernorm"
                       else norms.init_rmsnorm(cfg.d_model, dtype)),
    }
    for (key, kind, n), k in zip(layer_groups(cfg), (ks[1], ks[6])):
        p[key] = _stacked_init(
            k, n, lambda k, kind=kind: blocks.init_block(k, cfg, kind))
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
        return blocks.norm(cfg, p, x)


def _unembed(params, x):
    with jax.named_scope("head"):
        return embed_lib.unembed(params.get("head", params["embed"]), x)


def _run_stack(cfg: ModelCfg, stacked, x, kind: str, *, enc_out=None,
               positions=None, caches=None, prefill: bool = False):
    """scan over stacked layer params (and caches).  Returns (x, caches,
    aux, stats)."""

    def body(carry, scanned):
        h, aux, stats = carry
        lp = scanned[0] if caches is not None else scanned
        lc = scanned[1] if caches is not None else None
        h, nc, a, st = blocks.apply_block(lp, h, cfg, kind, cache=lc,
                                          enc_out=enc_out,
                                          positions=positions,
                                          prefill=prefill)
        return (h, aux + a, blocks.merge_stats(stats, st)), nc

    if cfg.remat and caches is None:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    xs = (stacked, caches) if caches is not None else stacked
    init = (x, jnp.zeros((), jnp.float32), blocks.block_stats(cfg, kind))
    (x, aux, stats), new_caches = jax.lax.scan(body, init, xs)
    return x, new_caches, aux, stats


def _run_layers(cfg: ModelCfg, params, x, *, enc_out=None, positions=None):
    """Every group of :func:`layer_groups` in turn, no cache.  Returns (x,
    aux, stats)."""
    aux, stats = None, {}
    for key, kind, _ in layer_groups(cfg):
        x, _, a, st = _run_stack(cfg, params[key], x, kind, enc_out=enc_out,
                                 positions=positions)
        aux = a if aux is None else aux + a
        stats = blocks.merge_stats(stats, st)
    return x, aux, stats


def _one_group(cfg: ModelCfg) -> None:
    if len(layer_groups(cfg)) > 1:
        raise NotImplementedError("the cached (serving) paths run one group "
                                  "of like layers")


def encode(cfg: ModelCfg, params, frames):
    """Encoder pass (encdec family).  frames: (B, n_frames, frontend_dim)."""
    x = frontend_lib.apply_frontend(params["frontend"], frames)
    x = x.astype(cfg.cdtype)
    x, _, _, _ = _run_stack(cfg, params["enc_layers"], x, "enc",
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


def _forward(cfg: ModelCfg, params, batch, last_only: bool):
    """(logits_f32, aux, stats): :func:`forward` with the layers'
    counters."""
    x, positions = _embed_inputs(cfg, params, batch)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, params, batch["frames"])
    x, aux, stats = _run_layers(cfg, params, x, enc_out=enc_out,
                                positions=positions)
    x = _final_norm(cfg, params["final_norm"], x)
    if cfg.family == "vlm" and "patches" in batch:
        x = x[:, batch["patches"].shape[1]:]     # logits over text positions
    if last_only:
        x = x[:, -1:]
    return _unembed(params, x), aux, stats


def forward(cfg: ModelCfg, params, batch, *, last_only: bool = False):
    """Full-sequence forward.  Returns (logits_f32, aux).

    ``last_only`` slices to the final position BEFORE the unembedding —
    the production prefill path (a full (B,S,V) fp32 logit tensor at 32k
    sequence x 150k vocab is tens of GB per device)."""
    logits, aux, _ = _forward(cfg, params, batch, last_only)
    return logits, aux


def loss_fn(cfg: ModelCfg, params, batch):
    """Next-token cross-entropy (+ router aux).  labels < 0 are masked.
    The metrics carry the layers' counters (:func:`blocks.block_stats`)."""
    logits, aux, stats = _forward(cfg, params, batch, False)
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
                       "ppl_proxy": jnp.exp(jnp.minimum(loss, 20.0)),
                       **stats}


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
    _one_group(cfg)
    x, new_cache, _, _ = _run_stack(cfg, params["layers"], x,
                                    block_kind(cfg), positions=positions,
                                    caches=cache, prefill=True)
    x = _final_norm(cfg, params["final_norm"], x)
    if last_only:
        x = x[:, -1:]
    return _unembed(params, x), new_cache


def decode_step(cfg: ModelCfg, params, cache, tokens):
    """One-token decode.  tokens: (B, 1).  Returns (logits, new_cache)."""
    offset = _cache_pos(cfg, cache)
    x, positions = _embed_inputs(cfg, params, {"tokens": tokens}, offset=offset)
    _one_group(cfg)
    x, new_cache, _, _ = _run_stack(cfg, params["layers"], x,
                                    block_kind(cfg), positions=positions,
                                    caches=cache)
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
