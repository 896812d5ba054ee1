"""Mixture-of-Experts ff module (top-k routed + shared experts).

Two layers: the GShard capacity layer below, which holds every expert and
drops overflow tokens, and (at the end of this file) the dropless layer of
one chip's share of the experts, selected by ``ModelCfg.n_experts_held``.

Dispatch is GShard-style with **batch rows as capacity groups** and an optional
``lax.scan`` over sequence chunks, chosen so the layer composes with GSPMD
without shard_map:

* tokens stay on their data shard (groups = batch rows, sharded over
  ``data``/``pod``);
* expert weights are sharded over ``model`` on the leading expert axis (EP);
* expert compute is fully local — each (data, model) device processes its
  batch rows against its expert shard;
* the only collective is ONE all-reduce of the combined output over ``model``
  per layer (inserted by GSPMD at the combine einsum) — identical comm to a
  dense TP MLP.

Capacity: ``C = ceil(Sc * top_k * capacity_factor / n_experts)`` per batch row
per chunk; overflow tokens are dropped (standard dropped-token MoE).  Experts
are padded up to a multiple of the mesh ``model`` size; padded experts are
masked to -inf in the router and receive no tokens.

Each expert's FFN goes through the linear factory (``site="ff"``) — DYAD
applies *inside* experts, composing the paper's technique with EP.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import factory, linear
from repro.kernels import dyad_gmm
from repro.kernels import ops as kops
from repro.layers import mlp as mlp_lib
from repro.sharding import ctx as shard_ctx


def init_moe(
    key,
    d_model: int,
    expert_d_ff: int,
    n_experts: int,
    top_k: int,
    lin_cfg: factory.LinearCfg,
    *,
    n_shared: int = 0,
    shared_d_ff: Optional[int] = None,
    act: str = "swiglu",
    n_experts_padded: Optional[int] = None,
    dtype=jnp.float32,
):
    e_pad = n_experts_padded or n_experts
    ks = jax.random.split(key, 3)
    expert_keys = jax.random.split(ks[0], e_pad)
    experts = jax.vmap(
        lambda k: mlp_lib.init_mlp(k, d_model, expert_d_ff, lin_cfg, act=act,
                                   dtype=dtype)
    )(expert_keys)
    p = {
        "router": linear.init(ks[1], d_model, e_pad, bias=False, dtype=dtype),
        "experts": experts,
    }
    if n_shared:
        sk1, sk2 = jax.random.split(ks[2])
        p["shared"] = mlp_lib.init_mlp(
            sk1, d_model, shared_d_ff or n_shared * expert_d_ff, lin_cfg,
            act=act, dtype=dtype)
        p["shared_gate"] = linear.init(sk2, d_model, 1, bias=False, dtype=dtype)
    return p


def _route(params, x, n_experts: int, top_k: int):
    """x: (..., D) -> (weights, idx, probs): top-k renormalized weights."""
    e_pad = params["router"]["w"].shape[0]
    logits = linear.apply(params["router"], x.astype(jnp.float32))
    if e_pad > n_experts:  # mask padded experts
        pad_mask = jnp.arange(e_pad) >= n_experts
        logits = jnp.where(pad_mask, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    weights = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    return weights, idx, probs


def _dispatch_combine(xc, weights, idx, e_pad: int, top_k: int, capacity: int):
    """One chunk: xc (B, Sc, D); returns (expert_in (B,E,C,D), combine (B,Sc,E,C))."""
    # position of each (token, slot) within its expert, per batch row.
    oh = jax.nn.one_hot(idx, e_pad, dtype=jnp.float32)       # (B,Sc,k,E)
    # sequentialize the k slots: slot j sees counts from slots < j.
    pos = jnp.cumsum(oh.reshape(oh.shape[0], -1, e_pad), axis=1).reshape(oh.shape) - oh
    pos = jnp.einsum("bske->bsk", pos * oh)                   # (B,Sc,k) position
    keep = pos < capacity
    w = weights * keep                                        # dropped -> 0
    cap_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    # combine[b,s,e,c] = sum_j w[b,s,j] * oh[b,s,j,e] * cap_oh[b,s,j,c]
    combine = jnp.einsum("bsk,bske,bskc->bsec", w, oh, cap_oh)
    dispatch = (combine > 0).astype(xc.dtype)
    expert_in = jnp.einsum("bsec,bsd->becd", dispatch, xc)
    return expert_in, combine.astype(xc.dtype)


def _expert_ffn(experts, x, act: str):
    """Expert FFN with an EXPLICIT expert axis (no vmap): every intermediate
    carries E so sharding constraints can anchor EP end-to-end (a vmapped
    body hides the E axis from with_sharding_constraint — §Perf B2).

    x: (B, E, C, D).  DYAD experts use the mixed-variant fused form
    (up=IT, down=OT, block-layout hidden) — see DESIGN §7."""
    up = experts["up"]
    if "w1" in up:                                   # dyad experts
        n, d_out, d_in = up["w1"].shape[1:]

        def dyad_up(p):
            lead = x.shape[:-1]
            x1 = x.reshape(*lead, n, d_in)
            x2 = jnp.swapaxes(x.reshape(*lead, d_in, n), -1, -2)
            return (jnp.einsum("becgi,egoi->becgo", x1, p["w1"].astype(x.dtype))
                    + jnp.einsum("becgi,egoi->becgo", x2,
                                 p["w2"].astype(x.dtype)))

        if act == "swiglu":
            h = jax.nn.silu(dyad_up(experts["gate"])) * dyad_up(up)
        else:
            h = getattr(jax.nn, act if act != "gelu" else "gelu")(dyad_up(up))
        h = shard_ctx.constrain_expert_batch(h)       # (B,E,C,n,d_out)
        dn = experts["down"]
        z1 = jnp.einsum("becgi,egoi->becgo", h, dn["w1"].astype(x.dtype))
        z2 = jnp.einsum("becgi,egoi->becgo", h, dn["w2"].astype(x.dtype))
        nd, d2 = z1.shape[-2], z1.shape[-1]
        y = (z1.reshape(*z1.shape[:-2], nd * d2)
             + jnp.swapaxes(z2, -1, -2).reshape(*z2.shape[:-2], nd * d2))
        return y

    # dense experts: (E, f_out, f_in) weights
    if act == "swiglu":
        g = jnp.einsum("becd,efd->becf", x, experts["gate"]["w"].astype(x.dtype))
        u = jnp.einsum("becd,efd->becf", x, up["w"].astype(x.dtype))
        h = jax.nn.silu(g) * u
    else:
        fn = {"gelu": jax.nn.gelu, "relu": jax.nn.relu}[act]
        h = fn(jnp.einsum("becd,efd->becf", x, up["w"].astype(x.dtype)))
    h = shard_ctx.constrain_expert_batch(h)
    return jnp.einsum("becf,edf->becd", h, experts["down"]["w"].astype(x.dtype))


def apply_moe(
    params,
    x,
    lin_cfg: factory.LinearCfg,
    *,
    n_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    act: str = "swiglu",
    chunk: Optional[int] = None,
):
    """x: (B, S, D) -> (y, aux_loss)."""
    B, S, D = x.shape
    e_pad = params["router"]["w"].shape[0]
    weights, idx, probs = _route(params, x, n_experts, top_k)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=(0, 1))                       # (E,)
    fe = jnp.mean(
        jax.nn.one_hot(idx, e_pad, dtype=jnp.float32).sum(-2), axis=(0, 1))
    aux = n_experts * jnp.sum(me * fe) / top_k

    Sc = min(chunk or S, S)
    assert S % Sc == 0, f"seq {S} must divide moe chunk {Sc}"
    capacity = max(1, int(Sc * top_k * capacity_factor / n_experts))

    def run_chunk(xc, wc, ic):
        expert_in, combine = _dispatch_combine(xc, wc, ic, e_pad, top_k, capacity)
        expert_in = shard_ctx.constrain_expert_batch(expert_in)
        eo = _expert_ffn(params["experts"], expert_in, act)
        eo = shard_ctx.constrain_expert_batch(eo)
        return jnp.einsum("bsec,becd->bsd", combine, eo)

    if Sc == S:
        y = run_chunk(x, weights, idx)
    else:
        ns = S // Sc
        xs = (
            x.reshape(B, ns, Sc, D).swapaxes(0, 1),
            weights.reshape(B, ns, Sc, -1).swapaxes(0, 1),
            idx.reshape(B, ns, Sc, -1).swapaxes(0, 1),
        )
        _, ys = jax.lax.scan(lambda c, t: (c, run_chunk(*t)), None, xs)
        y = ys.swapaxes(0, 1).reshape(B, S, D)

    if "shared" in params:
        g = jax.nn.sigmoid(
            linear.apply(params["shared_gate"], x.astype(jnp.float32)))
        y = y + g.astype(x.dtype) * mlp_lib.apply_mlp(
            params["shared"], x, lin_cfg, act=act)
    return y, aux


# -- one chip's share of a dropless expert layer ------------------------------
#
# The layer of an expert-parallel deployment as one chip runs it: the router
# scores every token against ALL ``n_experts`` and picks its ``top_k``, the
# chip computes the part of the result that its own experts (ids
# ``first .. first + n_held``) give, for every token routed to them, and
# the shared experts (which every chip computes alike) are added.  Nothing is
# dropped: the sorted-row buffer is sized for the worst case, every token
# copy routed to a held expert gets a row, and the grouped kernel computes
# only the rows routed here (``kernels/dyad_gmm.py``).  On one chip there is
# no exchange; a deployment would send the other experts' token copies to
# their chips.


def init_moe_share(key, d_model: int, expert_d_ff: int, n_experts: int,
                   n_held: int, lin_cfg: factory.LinearCfg, *,
                   n_shared: int = 0, dtype=jnp.float32):
    """Router over all ``n_experts`` (with the selection bias
    ``score_bias``), ``n_held`` SwiGLU experts and the shared experts as one
    SwiGLU of ``n_shared * expert_d_ff``."""
    ks = jax.random.split(key, 3)
    p = {
        "router": linear.init(ks[0], d_model, n_experts, bias=False,
                              dtype=dtype),
        "score_bias": jnp.zeros((n_experts,), dtype),
        "experts": jax.vmap(lambda k: mlp_lib.init_mlp(
            k, d_model, expert_d_ff, lin_cfg, act="swiglu", dtype=dtype))(
                jax.random.split(ks[1], n_held)),
    }
    if n_shared:
        p["shared"] = mlp_lib.init_mlp(ks[2], d_model, n_shared * expert_d_ff,
                                       lin_cfg, act="swiglu", dtype=dtype)
    return p


def route_scores(params, x, *, top_k: int, score: str, norm_topk: bool,
                 routed_scale: float):
    """``x (T, D)`` -> (weights ``(T, top_k)`` float32, expert ids).  The
    router runs in float32 at full precision.  Sigmoid scores pick their
    top_k by score + ``score_bias`` and weigh by the score alone."""
    logits = jnp.einsum("td,ed->te", x.astype(jnp.float32),
                        params["router"]["w"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if score == "sigmoid":
        s = jax.nn.sigmoid(logits)
        choice = s + jax.lax.stop_gradient(
            params["score_bias"].astype(jnp.float32))
    else:
        s = choice = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * routed_scale, idx


def share_layout(idx, *, first: int, n_held: int, row_tile: int):
    """Where each token copy routed to a held expert goes in the sorted-row
    buffer.  Returns ``dest`` ``(T, top_k)`` (the buffer size ``M`` for a
    copy routed elsewhere), ``row_copy`` ``(M,)`` (the flat copy index
    ``t * top_k + j`` of each row, ``T * top_k`` for a row that holds
    none), the group ``starts`` ``(n_held + 1,)``, ``ends`` and ``sizes``
    ``(n_held,)``."""
    T, k = idx.shape
    local = (idx - first).reshape(-1)
    held = (local >= 0) & (local < n_held)
    group = jnp.where(held, local, 0)
    onehot = ((group[:, None] == jnp.arange(n_held)) & held[:, None]
              ).astype(jnp.int32)
    sizes = onehot.sum(0)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0), group[:, None],
                               axis=1)[:, 0] - 1
    starts = dyad_gmm.group_starts(sizes, row_tile)
    M = dyad_gmm.buffer_rows(T * min(k, n_held), n_held, row_tile)
    dest = jnp.where(held, starts[group] + rank, M)
    row_copy = jnp.full((M,), T * k, jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32), mode="drop")
    return (dest.reshape(T, k), row_copy, starts, starts[:n_held] + sizes,
            sizes)


def _rows_at(rows, dest):
    """``rows[dest]`` for one column of ``dest`` ``(T,)``, zero where it is
    past the buffer (a copy routed to another chip's expert).  The
    buffer's rows outside every group are never read."""
    M = rows.shape[0]
    got = rows[jnp.minimum(dest, M - 1)]
    return jnp.where((dest < M)[:, None], got, 0)


# Dispatch and combine move rows by gathers in both directions (their
# autodiff would scatter-add); each sums over the top_k copies one column
# at a time, so that no (T, top_k, D) array exists.


@jax.custom_vjp
def _dispatch(x, row_copy, dest):
    """Token rows ``x (T, D)`` -> sorted rows ``(M, D)`` (token 0's row
    where a row holds no copy)."""
    k = dest.shape[1]
    return x[jnp.where(row_copy < x.shape[0] * k, row_copy // k, 0)]


def _dispatch_fwd(x, row_copy, dest):
    return _dispatch(x, row_copy, dest), (row_copy, dest)


def _dispatch_bwd(res, g):
    row_copy, dest = res
    dx = sum(_rows_at(g, dest[:, j]).astype(jnp.float32)
             for j in range(dest.shape[1]))
    return dx.astype(g.dtype), kops._int_zero(row_copy), kops._int_zero(dest)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, w, row_copy, dest):
    """``y[t] = Σ_j w[t, j] · rows[dest[t, j]]`` over the copies held here,
    summed in float32, in the rows' dtype."""
    y = sum(_rows_at(rows, dest[:, j]).astype(jnp.float32) * w[:, j, None]
            for j in range(dest.shape[1]))
    return y.astype(rows.dtype)


def _combine_fwd(rows, w, row_copy, dest):
    return _combine(rows, w, row_copy, dest), (rows, w, row_copy, dest)


def _combine_bwd(res, g):
    rows, w, row_copy, dest = res
    T, k = w.shape
    has = row_copy < T * k
    c = jnp.where(has, row_copy, 0)
    scale = w.reshape(-1)[c].astype(g.dtype)
    drows = jnp.where(has[:, None], g[c // k] * scale[:, None], 0)
    g32 = g.astype(jnp.float32)
    dw = jnp.stack([jnp.sum(_rows_at(rows, dest[:, j]).astype(jnp.float32)
                            * g32, axis=-1) for j in range(k)], axis=1)
    return (drows.astype(rows.dtype), dw, kops._int_zero(row_copy),
            kops._int_zero(dest))


_combine.defvjp(_combine_fwd, _combine_bwd)


def apply_moe_share(params, x, lin_cfg: factory.LinearCfg, *, top_k: int,
                    first: int, score: str = "sigmoid",
                    norm_topk: bool = True, routed_scale: float = 1.0,
                    chunk: Optional[int] = None,
                    row_tile: int = dyad_gmm.ROW_TILE):
    """``x (B, S, D)`` -> (this chip's part of the layer's output, counters
    ``routed_rows`` (token copies computed here) and ``max_expert_rows``
    (those of the busiest held expert)).  The held experts are DYAD-IT
    projections run by the grouped kernel.  ``chunk`` tokens at a time
    (a divisor of B * S) go through the sorted-row buffer, each chunk
    recomputed in the backward, so that the buffer, sized for the worst
    case of a dropless layer, is a chunk's."""
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    ex = params["experts"]
    n_held = ex["up"]["w1"].shape[0]
    with jax.named_scope("router"):
        w, idx = route_scores(params, xf, top_k=top_k, score=score,
                              norm_topk=norm_topk, routed_scale=routed_scale)

    def routed(xc, wc, ic):
        with jax.named_scope("router"):
            dest, row_copy, starts, ends, sizes = share_layout(
                ic, first=first, n_held=n_held, row_tile=row_tile)
        with jax.named_scope("experts"):
            rows = _dispatch(xc, row_copy, dest)

            def proj(name, h):
                return kops.dyad_gmm_it(h, ex[name]["w1"], ex[name]["w2"],
                                        starts, ends, row_tile=row_tile)

            h = jax.nn.silu(proj("gate", rows)) * proj("up", rows)
            rows = proj("down", h)
        with jax.named_scope("router"):
            return _combine(rows, wc, row_copy, dest), sizes

    C = min(chunk or T, T)
    if T % C:
        raise ValueError(f"moe chunk {C} does not divide {T} tokens")
    if C == T:
        y, sizes = routed(xf, w, idx)
    else:
        y, sizes = jax.lax.map(
            jax.checkpoint(lambda a: routed(*a)),
            (xf.reshape(T // C, C, D), w.reshape(T // C, C, top_k),
             idx.reshape(T // C, C, top_k)))
        y, sizes = y.reshape(B * S, D), sizes.sum(0)
    y = y.reshape(B, S, D)
    if "shared" in params:
        with jax.named_scope("shared"):
            y = y + mlp_lib.apply_mlp(params["shared"], x, lin_cfg,
                                      act="swiglu")
    stats = {"routed_rows": sizes.sum().astype(jnp.float32),
             "max_expert_rows": sizes.max().astype(jnp.float32)}
    return y, stats
