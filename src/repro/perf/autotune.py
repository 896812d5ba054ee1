"""Pallas block-size autotuner with a persistent JSON cache.

The fused DYAD kernel (:mod:`repro.kernels.dyad_mm`) tiles its grid with
``(block_b, block_o, block_k)``.  The right tile depends on the operand
shapes, dtype, and backend — a fixed default leaves MXU utilization on the
table for every shape it wasn't hand-picked for.  This module sweeps
candidate tiles per ``(op, shape, dtype, backend)`` key, times the real
kernel, and persists the winner:

* user cache   — ``~/.cache/repro_perf/blocks.json`` (override the directory
  with ``REPRO_PERF_CACHE_DIR``); written atomically, corrupt files are
  treated as empty and rewritten on the next ``put``;
* repo defaults — ``src/repro/perf/tuned/defaults.json``, shipped with the
  package so fresh checkouts start from tuned tiles for the shapes the
  benchmarks exercise.

``get_tuned_blocks`` is the lookup the kernel wrappers call at trace time
(shapes are concrete then); explicit ``block_*`` arguments always win, so
the tuner itself times candidates without consulting the cache.

Batch sizes are bucketed to the next power of two: decode steps see
``B = batch`` while prefill sees ``B = batch * seq``, and tile choice is
insensitive to B within a bucket (the b-axis tile clamps to the bucket).
"""
from __future__ import annotations

import contextlib
import contextvars
import json
import os
import sys
import time
import warnings
from typing import Dict, Iterable, List, Optional, Tuple

from repro import obs
from repro.perf.record import backend_name as _backend
from repro.perf.record import time_us as _time_us

Blocks = Dict[str, int]

DEFAULT_BLOCKS: Blocks = {"block_b": 256, "block_o": 256, "block_k": 512}

# the ff megakernel tiles a 4th axis: block_j tiles the hidden (d_ff/n)
# feature dim that never leaves VMEM.
DEFAULT_FF_BLOCKS: Blocks = {"block_b": 256, "block_o": 256,
                             "block_k": 512, "block_j": 512}

# op keys that resolve 4-axis ff tiles (and carry d_mid in their cache key).
# The ``_w8`` variants are the quantized-weight-stream bodies: their key's
# dtype field carries the PAYLOAD dtype (int8/float8_e4m3fn) — quantized
# tiles stream 2-4x fewer bytes, so wider tiles fit the same VMEM budget
# and the tuned entries must never collide with the unquantized ones.
FF_OPS = ("dyad_ff_fused", "dyad_ff_fused_swiglu",
          "dyad_ff_fused_w8", "dyad_ff_fused_swiglu_w8")

# flash-attention op keys: ``block_b`` tiles q positions, ``block_k`` tiles
# the streamed key axis; ``block_o`` is carried but unused (the head dim is
# never tiled).  Their key names the layer-natural dims
# (B=q rows|batch, n=KV heads, k=head_dim, o=kv length) and carries the
# GQA ratio G as ``d_mid`` — G scales the resident q/acc rows (bQ*G), so
# tiles tuned for one grouping must not collide with another.  The paged
# decode op additionally carries the page size as ``d_page``: its key tile
# is clamped to a divisor of the page, so tiles tuned for one page size
# must not collide with another.
ATTN_OPS = ("flash_prefill", "flash_decode", "flash_decode_paged")

DEFAULT_ATTN_BLOCKS: Blocks = {"block_b": 256, "block_o": 128,
                               "block_k": 512}

# VMEM is ~16 MB/core on TPU v4/v5; leave headroom for double-buffered
# pipelines (factor 2 on streamed operands) and the fp32 accumulator(s).
VMEM_BUDGET_BYTES = 12 * 2 ** 20

_DEFAULTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "tuned", "defaults.json")


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length() if x > 1 else 1


# Tensor-parallel shard tag.  kernels/tp.py sets this around shard_map
# invocations (the body traces eagerly inside the outer jit trace, so
# trace-time ``get_tuned_blocks`` lookups in the per-shard kernels see it),
# and ``ensure_tuned_for_model`` sets it while sweeping per-shard shapes.
# Keys gain a ``|tp{N}`` suffix only for N > 1: a per-shard shape that
# happens to equal a single-device global shape (e.g. d_ff/tp at tp=2 vs a
# half-width model at tp=1) must not collide — their VMEM/ICI trade-offs
# differ — while every committed tp=1 cache entry stays valid unchanged.
_TP: "contextvars.ContextVar[int]" = contextvars.ContextVar(
    "repro_autotune_tp", default=1)


@contextlib.contextmanager
def tp_shards(n: int):
    """Tag autotune cache keys with a tensor-parallel shard count."""
    tok = _TP.set(max(int(n), 1))
    try:
        yield
    finally:
        _TP.reset(tok)


def current_tp() -> int:
    return _TP.get()


def tune_key(op: str, B: int, n: int, d_in: int, d_out: int,
             dtype: str = "float32", backend: Optional[str] = None,
             d_mid: Optional[int] = None,
             d_page: Optional[int] = None,
             tp: Optional[int] = None) -> str:
    """Canonical cache key; B is bucketed to the next power of two.
    ``d_mid`` (the ff megakernel's hidden width d_ff/n) extends the key for
    ops whose tiling couples three weight tensors — omitted (and absent
    from the key) for the single-matmul ops.  ``d_page`` extends it again
    for the paged decode op (key tiles clamp to the page size).  ``tp``
    defaults to the ambient :func:`tp_shards` count and suffixes the key
    with ``|tp{N}`` when the shape is a per-shard slice (N > 1)."""
    backend = backend or _backend()
    tp = current_tp() if tp is None else max(int(tp), 1)
    mid = f"|j{d_mid}" if d_mid is not None else ""
    page = f"|p{d_page}" if d_page is not None else ""
    shard = f"|tp{tp}" if tp > 1 else ""
    return (f"{op}|B{max(_next_pow2(B), 8)}|n{n}|k{d_in}|o{d_out}{mid}{page}"
            f"{shard}|{dtype}|{backend}")


class BlockCache:
    """Two-layer persistent cache: user file over packaged defaults."""

    def __init__(self, user_path: Optional[str] = None,
                 defaults_path: str = _DEFAULTS_PATH):
        if user_path is None:
            root = os.environ.get(
                "REPRO_PERF_CACHE_DIR",
                os.path.join(os.path.expanduser("~"), ".cache", "repro_perf"))
            user_path = os.path.join(root, "blocks.json")
        self.user_path = user_path
        self.defaults_path = defaults_path
        self._user: Optional[dict] = None
        self._defaults: Optional[dict] = None

    def _load(self, path: str) -> dict:
        try:
            with open(path) as f:
                doc = json.load(f)
            if not isinstance(doc, dict):
                raise ValueError("top-level JSON is not an object")
            return doc
        except FileNotFoundError:
            return {}
        except (json.JSONDecodeError, ValueError, OSError) as e:
            warnings.warn(f"repro.perf: ignoring corrupt block cache "
                          f"{path}: {e}")
            return {}

    @property
    def user(self) -> dict:
        if self._user is None:
            self._user = self._load(self.user_path)
        return self._user

    @property
    def defaults(self) -> dict:
        if self._defaults is None:
            self._defaults = self._load(self.defaults_path)
        return self._defaults

    def get(self, key: str) -> Optional[Blocks]:
        for layer in (self.user, self.defaults):
            entry = layer.get(key)
            if isinstance(entry, dict) and isinstance(
                    entry.get("blocks"), dict):
                b = entry["blocks"]
                if all(isinstance(b.get(f), int) and b[f] > 0
                       for f in ("block_b", "block_o", "block_k")):
                    out = {f: b[f] for f in
                           ("block_b", "block_o", "block_k")}
                    if isinstance(b.get("block_j"), int) and b["block_j"] > 0:
                        out["block_j"] = b["block_j"]
                    return out
        return None

    def get_entry(self, key: str) -> Optional[dict]:
        for layer in (self.user, self.defaults):
            if key in layer:
                return layer[key]
        return None

    def put(self, key: str, blocks: Blocks, **meta) -> None:
        self.user[key] = {"blocks": dict(blocks), **meta}
        os.makedirs(os.path.dirname(self.user_path) or ".", exist_ok=True)
        tmp = self.user_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.user, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.user_path)
        _memo_clear()          # new tiles must be visible to the next trace

    def invalidate(self) -> None:
        self._user = None
        self._defaults = None
        _memo_clear()


_CACHE: Optional[BlockCache] = None

# trace-time memo over get_tuned_blocks: a jitted model trace resolves tiles
# once per DYAD call site, and a 48-layer model traces hundreds of sites —
# without this each one re-walks the (possibly file-backed) JSON cache.
# Invalidated by put()/invalidate()/reset_cache().
_MEMO: Dict[str, Blocks] = {}
_MEMO_COUNTS = {"hits": 0, "misses": 0}


def _memo_clear() -> None:
    _MEMO.clear()


def memo_counts() -> Dict[str, int]:
    """Copy of the get_tuned_blocks memo hit/miss counters (observability +
    tests; counters survive _memo_clear so rates stay meaningful)."""
    return dict(_MEMO_COUNTS)


def resolved_blocks() -> Dict[str, Blocks]:
    """The tiles resolved since the memo was last cleared, by tune key:
    what the kernels traced in this process actually asked for."""
    return {k: dict(v) for k, v in _MEMO.items()}


def get_cache() -> BlockCache:
    global _CACHE
    if _CACHE is None:
        _CACHE = BlockCache()
    return _CACHE


def reset_cache(cache: Optional[BlockCache] = None) -> None:
    """Swap / drop the process-wide cache (tests, env-var changes)."""
    global _CACHE
    _CACHE = cache
    _memo_clear()


def get_tuned_blocks(op: str, B: int, n: int, d_in: int, d_out: int,
                     dtype: str = "float32",
                     backend: Optional[str] = None,
                     d_mid: Optional[int] = None,
                     d_page: Optional[int] = None) -> Blocks:
    """Tuned blocks for this key, else the hardcoded defaults (the 4-axis
    ff defaults for the megakernel ops, which also pass ``d_mid``).  Called
    by the kernel wrappers at trace time; memoized in-process so repeated
    jit traces don't re-consult the JSON-backed cache per call site."""
    key = tune_key(op, B, n, d_in, d_out, dtype, backend, d_mid=d_mid,
                   d_page=d_page)
    hit = _MEMO.get(key)
    if hit is not None:
        _MEMO_COUNTS["hits"] += 1
        return dict(hit)
    _MEMO_COUNTS["misses"] += 1
    default = (DEFAULT_FF_BLOCKS if op in FF_OPS
               else DEFAULT_ATTN_BLOCKS if op in ATTN_OPS
               else DEFAULT_BLOCKS)
    found = get_cache().get(key)
    if found is None:
        out = dict(default)
    else:
        # tuned entries may predate a new tile axis: fill from the default
        # (and drop axes this op does not tile)
        out = {f: found.get(f, default[f]) for f in default}
    _MEMO[key] = dict(out)
    return out


# -- candidate generation -----------------------------------------------------


def _dtype_bytes(dtype: str) -> int:
    """Bytes per element for VMEM budgeting.  Unknown dtypes RAISE: a
    silent 4-byte default would let a quantized sweep admit tiles that
    blow the real budget (or reject tiles that fit)."""
    table = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
             "float8_e4m3fn": 1, "float8_e5m2": 1}
    try:
        return table[dtype]
    except KeyError:
        raise ValueError(f"_dtype_bytes: unknown dtype {dtype!r} "
                         f"(know {sorted(table)})") from None


def vmem_estimate(bb: int, bo: int, bk: int, dtype: str,
                  n_acc: int = 1, wgrad: bool = False,
                  w_dtype: Optional[str] = None) -> int:
    """Double-buffered VMEM footprint of one grid step.

    Forward/dgrad tile roles: two (bb, bk) activation tiles + two (bo, bk)
    weight tiles streamed, n_acc (bb, bo) output tiles, fp32 accumulators of
    the same shape.  wgrad contracts the BATCH axis instead: two (bb, bk) x
    tiles + two (bb, bo) z tiles streamed, and the outputs/accumulators are
    weight-shaped (bo, bk).

    ``w_dtype`` (quantized forward only) prices the weight tiles at the
    PAYLOAD dtype and adds the two double-buffered fp32 (bo,) scale tiles —
    int8 streams admit wider tiles under the same budget."""
    ib = _dtype_bytes(dtype)
    wb = ib if w_dtype is None else _dtype_bytes(w_dtype)
    if wgrad:
        stream = 2 * (2 * bb * bk + 2 * bb * bo + n_acc * bo * bk) * ib
        acc = 4 * n_acc * bo * bk
    else:
        stream = 2 * (2 * bb * bk * ib + 2 * bo * bk * wb
                      + n_acc * bb * bo * ib)
        if w_dtype is not None:
            stream += 2 * 2 * bo * 4
        acc = 4 * n_acc * bb * bo
    return stream + acc


def vmem_estimate_ff(bb: int, bo: int, bk: int, bj: int, dtype: str,
                     gated: bool = False,
                     w_dtype: Optional[str] = None) -> int:
    """Double-buffered VMEM footprint of one ff-megakernel grid step.

    Streams: two (bb, bk) input tiles, the up (and, gated, gate) weight
    tiles (bj, bk), two down weight tiles (bo, bj), two (bb, bo) output
    tiles.  Resident fp32 accumulators: the (bb, bj) hidden tile (two when
    gated) plus the two (bb, bo) down tiles — three weight tensors and the
    in-VMEM hidden now share ONE budget, which is exactly why the ff ops
    tune separately from the single-matmul kernels.

    ``w_dtype`` (the ``_w8`` ops) prices every weight tile at the PAYLOAD
    dtype and adds the fp32 scale tiles ((bj,) per up tensor, (bo,) per
    down)."""
    ib = _dtype_bytes(dtype)
    wb = ib if w_dtype is None else _dtype_bytes(w_dtype)
    n_up = 4 if gated else 2
    stream = 2 * (2 * bb * bk * ib + n_up * bj * bk * wb
                  + 2 * bo * bj * wb + 2 * bb * bo * ib)
    if w_dtype is not None:
        stream += 2 * (n_up * bj + 2 * bo) * 4
    acc = 4 * ((2 if gated else 1) * bb * bj + 2 * bb * bo)
    return stream + acc


def vmem_estimate_attn(bq: int, bk: int, h: int, g: int,
                       dtype: str) -> int:
    """Double-buffered VMEM footprint of one flash grid step.

    Streams: the (bq*g, h) q tile, two (bk, h) K/V tiles, the (bq*g, h)
    output tile.  Resident fp32 softmax state: m and l (bq*g, 128 lanes
    each) plus the (bq*g, h) output accumulator; the transient (bq*g, bk)
    score/probability tile lives through the softmax update and the P·V
    dot on the same step, so it budgets like a resident buffer."""
    ib = _dtype_bytes(dtype)
    rows = bq * g
    stream = 2 * (rows * h + 2 * bk * h + rows * h) * ib
    state = 4 * (2 * rows * 128 + rows * h)
    scores = 4 * 2 * rows * bk            # s + p in flight
    return stream + state + scores


def candidate_blocks_attn(S: int, T: int, h: int, g: int,
                          dtype: str = "float32", decode: bool = False,
                          max_candidates: int = 24) -> List[Blocks]:
    """Power-of-two (block_b = q positions, block_k = keys) sweep for the
    flash ops, largest tiles first, filtered by :func:`vmem_estimate_attn`.
    Decode has a single q row per head group: only block_k sweeps."""
    bqs = ([1] if decode else
           [b for b in (1024, 512, 256, 128, 64)
            if b <= max(_next_pow2(S), 64)])
    bks = [b for b in (1024, 512, 256, 128)
           if b <= max(_next_pow2(T), 128)]
    out: List[Blocks] = []
    base = dict(DEFAULT_ATTN_BLOCKS)
    cands = ([] if decode else [base]) + [
        {"block_b": bq, "block_o": 128, "block_k": bk}
        for bq in bqs for bk in bks]
    seen = set()
    for cand in cands:
        sig = (cand["block_b"], cand["block_k"])
        if sig in seen:
            continue
        seen.add(sig)
        if vmem_estimate_attn(cand["block_b"], cand["block_k"], h, g,
                              dtype) > VMEM_BUDGET_BYTES:
            continue
        out.append(dict(cand))
        if len(out) >= max_candidates:
            break
    return out


def candidate_blocks_ff(B: int, n: int, d_in: int, d_out: int, d_ff: int,
                        dtype: str = "float32", gated: bool = False,
                        max_candidates: int = 32,
                        w_dtype: Optional[str] = None) -> List[Blocks]:
    """Power-of-two 4-axis sweep for the ff megakernel, largest tiles first
    (fewer grid steps), filtered by :func:`vmem_estimate_ff` (quant sweeps
    pass the payload ``w_dtype`` so the shrunken streams admit wider
    tiles)."""
    bbs = [b for b in (512, 256, 128, 64) if b <= max(_next_pow2(B), 64)]
    bos = [b for b in (512, 256, 128) if b <= max(_next_pow2(d_out), 128)]
    bks = [b for b in (512, 256, 128) if b <= max(_next_pow2(d_in), 128)]
    bjs = [b for b in (1024, 512, 256, 128)
           if b <= max(_next_pow2(d_ff), 128)]
    out: List[Blocks] = []
    seen = set()
    for cand in ([DEFAULT_FF_BLOCKS]
                 + [{"block_b": bb, "block_o": bo, "block_k": bk,
                     "block_j": bj}
                    for bj in bjs for bb in bbs for bo in bos for bk in bks]):
        sig = (cand["block_b"], cand["block_o"], cand["block_k"],
               cand["block_j"])
        if sig in seen:
            continue
        seen.add(sig)
        if vmem_estimate_ff(cand["block_b"], cand["block_o"],
                            cand["block_k"], cand["block_j"], dtype,
                            gated=gated,
                            w_dtype=w_dtype) > VMEM_BUDGET_BYTES:
            continue
        out.append(dict(cand))
        if len(out) >= max_candidates:
            break
    return out


def candidate_blocks(B: int, n: int, d_in: int, d_out: int,
                     dtype: str = "float32", n_acc: int = 1,
                     wgrad: bool = False,
                     max_candidates: int = 32,
                     w_dtype: Optional[str] = None) -> List[Blocks]:
    """Power-of-two tile sweep clamped to the (bucketed) dims and filtered
    by the VMEM budget.  Always contains the hardcoded default."""
    bbs = [b for b in (64, 128, 256, 512) if b <= max(_next_pow2(B), 64)]
    bos = [b for b in (128, 256, 512) if b <= max(_next_pow2(d_out), 128)]
    bks = [b for b in (128, 256, 512, 1024) if b <= max(_next_pow2(d_in), 128)]
    out: List[Blocks] = []
    seen = set()
    for cand in ([DEFAULT_BLOCKS]
                 + [{"block_b": bb, "block_o": bo, "block_k": bk}
                    for bb in bbs for bo in bos for bk in bks]):
        sig = (cand["block_b"], cand["block_o"], cand["block_k"])
        if sig in seen:
            continue
        seen.add(sig)
        if vmem_estimate(*sig, dtype=dtype, n_acc=n_acc, wgrad=wgrad,
                         w_dtype=w_dtype) > VMEM_BUDGET_BYTES:
            continue
        out.append(dict(cand))
        if len(out) >= max_candidates:
            break
    return out


# -- the sweep ----------------------------------------------------------------


def autotune_dyad(op: str, B: int, n: int, d_in: int, d_out: int,
                  dtype: str = "float32", *,
                  candidates: Optional[Iterable[Blocks]] = None,
                  iters: int = 3, warmup: int = 1,
                  cache: Optional[BlockCache] = None,
                  force: bool = False,
                  d_mid: Optional[int] = None,
                  d_page: Optional[int] = None,
                  act: str = "gelu") -> Tuple[Blocks, float]:
    """Sweep block sizes for one kernel shape; persist and return the winner.

    ``op`` is one of ``"dyad_mm_blocks"`` / ``"dyad_mm_blocks_two"`` (the
    forward kernels), ``"dyad_mm_dgrad"`` / ``"dyad_mm_dgrad_two"`` /
    ``"dyad_mm_wgrad"`` (the backward kernels — dgrad contracts d_out and
    produces d_in, so its ``block_o`` tiles d_in and ``block_k`` tiles
    d_out; wgrad contracts the batch axis), ``"dyad_ff_fused"`` /
    ``"dyad_ff_fused_swiglu"`` (the whole-ff megakernel — pass the hidden
    width d_ff/n as ``d_mid``; ``act`` picks the timed epilogue), or
    ``"dense_bmm"`` (the baseline).  ``(B, n, d_in, d_out)`` always names
    the LAYER-natural dims, the same key the trace-time lookup uses.

    The ``_w8`` suffix on a forward op (``dyad_mm_blocks[_two]_w8``,
    ``dyad_ff_fused[_swiglu]_w8``) sweeps the quantized-weight-stream body:
    ``dtype`` then names the PAYLOAD dtype ("int8"/"float8_e4m3fn" — the
    field the kernel wrappers key on) while activations run in bf16, the
    serving compute dtype.
    Returns ``(blocks, best_us)``.  A cache hit short-circuits the sweep
    unless ``force=True``.
    """
    import jax
    import jax.numpy as jnp

    cache = cache or get_cache()
    if op in FF_OPS and d_mid is None:
        raise ValueError(f"{op} needs d_mid (the hidden width d_ff/n)")
    if op in ATTN_OPS and d_mid is None:
        raise ValueError(f"{op} needs d_mid (the GQA ratio G)")
    if op == "flash_decode_paged" and d_page is None:
        raise ValueError(f"{op} needs d_page (the KV page size)")
    key = tune_key(op, B, n, d_in, d_out, dtype, d_mid=d_mid, d_page=d_page)
    if not force:
        hit = cache.get(key)
        if hit is not None:
            entry = cache.get_entry(key) or {}
            return hit, float(entry.get("us", 0.0))

    if op in ATTN_OPS:
        # flash attention: (B, n, d_in, d_out) = (q rows|batch, KV heads,
        # head_dim, kv length); d_mid is the GQA ratio G.
        import jax
        import jax.numpy as jnp

        from repro.kernels import flash_attn
        from repro.kernels.dyad_mm import _plan_axis
        from repro.kernels.ops import _interpret

        g = d_mid
        kd = jnp.dtype(dtype)
        kx = jax.random.PRNGKey(0)
        interpret = _interpret()
        decode = op in ("flash_decode", "flash_decode_paged")
        if op == "flash_decode_paged":
            # worst-case admitted state: every slot holds a full-length
            # sequence, each in its own pages (plus the scratch page 0)
            P = d_page
            nb = -(-d_out // P)
            q = jax.random.normal(kx, (B, n, g, d_in), kd)
            pk = jax.random.normal(jax.random.fold_in(kx, 1),
                                   (1 + B * nb, P, n, d_in), kd)
            pv = jax.random.normal(jax.random.fold_in(kx, 2),
                                   (1 + B * nb, P, n, d_in), kd)
            bt = 1 + jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
            idx = jnp.full((B,), d_out - 1, jnp.int32)   # full-cache step
            kernel = lambda **c: flash_attn.flash_decode_paged(
                q, pk, pv, bt, idx, l_real=d_out, block_k=c["block_k"],
                interpret=interpret)
        elif decode:
            q = jax.random.normal(kx, (B, n, g, d_in), kd)
            k = jax.random.normal(jax.random.fold_in(kx, 1),
                                  (B, d_out, n, d_in), kd)
            v = jax.random.normal(jax.random.fold_in(kx, 2),
                                  (B, d_out, n, d_in), kd)
            idx = jnp.full((B,), d_out - 1, jnp.int32)   # full-cache step
            kernel = lambda **c: flash_attn.flash_decode(
                q, k, v, idx, block_k=c["block_k"], interpret=interpret)
        else:
            q = jax.random.normal(kx, (1, B, n, g, d_in), kd)
            k = jax.random.normal(jax.random.fold_in(kx, 1),
                                  (1, d_out, n, d_in), kd)
            v = jax.random.normal(jax.random.fold_in(kx, 2),
                                  (1, d_out, n, d_in), kd)
            kernel = lambda **c: flash_attn.flash_prefill(
                q, k, v, 0, 0, causal=True, block_q=c["block_b"],
                block_k=c["block_k"], interpret=interpret)[0]
        cands = (list(candidates) if candidates is not None
                 else candidate_blocks_attn(B, d_out, d_in, g, dtype,
                                            decode=decode))
        seen_plans = set()
        deduped = []
        for cand in cands:
            if op == "flash_decode_paged":
                # the wrapper clamps the key tile to a page divisor:
                # distinct requests collapsing to one effective tile would
                # only measure noise twice
                from repro.kernels.dyad_mm import _largest_divisor
                plan = _largest_divisor(d_page,
                                        max(min(cand["block_k"], d_page), 1))
            else:
                plan = (_plan_axis(B, cand["block_b"], 8),
                        _plan_axis(d_out, cand["block_k"], 128))
            if plan in seen_plans:
                continue
            seen_plans.add(plan)
            deduped.append(cand)
        best, best_us = _time_candidates(kernel, deduped, key, iters, warmup)
        cache.put(key, best, us=round(best_us, 2), op=op,
                  candidates=len(deduped))
        return best, best_us

    quant = op.endswith("_w8")
    kd = jnp.dtype(jnp.bfloat16) if quant else jnp.dtype(dtype)
    kx = jax.random.PRNGKey(0)
    x1 = jax.random.normal(kx, (B, n, d_in), kd)
    x2 = jax.random.normal(jax.random.fold_in(kx, 1), (B, n, d_in), kd)
    w1 = jax.random.normal(jax.random.fold_in(kx, 2), (n, d_out, d_in), kd)
    w2 = jax.random.normal(jax.random.fold_in(kx, 3), (n, d_out, d_in), kd)
    if quant:
        from repro import quant as quant_lib
        quant_lib.resolve_dtype(dtype)    # payload name must be quantizable

    if op == "dense_bmm":
        # the baseline has no tile knobs; record its time under the default
        # key so compare tables can show fused-vs-dense per shape.
        f = jax.jit(lambda: jnp.einsum("bgk,gok->bgo", x1, w1)
                    + jnp.einsum("bgk,gok->bgo", x2, w2))
        us = _time_us(f, iters=iters, warmup=warmup)
        blocks = dict(DEFAULT_BLOCKS)
        cache.put(key, blocks, us=round(us, 2), op=op)
        return blocks, us

    from repro.kernels import dyad_mm
    from repro.kernels.ops import _interpret

    n_acc = 1 if op in ("dyad_mm_blocks", "dyad_mm_blocks_w8",
                        "dyad_mm_dgrad") else 2
    interpret = _interpret()

    if op in FF_OPS:
        gated = "swiglu" in op
        kact = "swiglu" if gated else act
        wu1 = jax.random.normal(jax.random.fold_in(kx, 4), (n, d_mid, d_in),
                                kd)
        wu2 = jax.random.normal(jax.random.fold_in(kx, 5), (n, d_mid, d_in),
                                kd)
        wd1 = jax.random.normal(jax.random.fold_in(kx, 6), (n, d_out, d_mid),
                                kd)
        wd2 = jax.random.normal(jax.random.fold_in(kx, 7), (n, d_out, d_mid),
                                kd)
        gates = {}
        if gated:
            gates = {"wg1": jax.random.normal(jax.random.fold_in(kx, 8),
                                              (n, d_mid, d_in), kd),
                     "wg2": jax.random.normal(jax.random.fold_in(kx, 9),
                                              (n, d_mid, d_in), kd)}
        if quant:
            (wu1, su1), (wu2, su2), (wd1, sd1), (wd2, sd2) = (
                quant_lib.quantize_dyad_weight(w, dtype)
                for w in (wu1, wu2, wd1, wd2))
            if gated:
                wg1, sg1 = quant_lib.quantize_dyad_weight(gates["wg1"],
                                                          dtype)
                wg2, sg2 = quant_lib.quantize_dyad_weight(gates["wg2"],
                                                          dtype)
                gates = {"wg1": wg1, "wg2": wg2, "sg1": sg1, "sg2": sg2}
            kernel = lambda **c: dyad_mm.dyad_ff_fused_q(
                x1, x2, wu1, wu2, wd1, wd2, su1, su2, sd1, sd2, act=kact,
                interpret=interpret, **gates, **c)
        else:
            kernel = lambda **c: dyad_mm.dyad_ff_fused(
                x1, x2, wu1, wu2, wd1, wd2, act=kact, interpret=interpret,
                **gates, **c)
        cands = (list(candidates) if candidates is not None
                 else candidate_blocks_ff(
                     B, n, d_in, d_out, d_mid,
                     str(kd) if quant else dtype, gated=gated,
                     w_dtype=dtype if quant else None))
        seen_plans = set()
        deduped = []
        for cand in cands:
            plan = dyad_mm.plan_ff_tiles(B, d_out, d_mid, d_in,
                                         cand["block_b"], cand["block_o"],
                                         cand["block_j"], cand["block_k"])
            if plan in seen_plans:
                continue
            seen_plans.add(plan)
            deduped.append(cand)
        best, best_us = _time_candidates(kernel, deduped, key, iters, warmup)
        cache.put(key, best, us=round(best_us, 2), op=op,
                  candidates=len(deduped))
        return best, best_us

    if op in ("dyad_mm_dgrad", "dyad_mm_dgrad_two"):
        # dgrad consumes per-component cotangents (B, n, d_out)
        z1 = jax.random.normal(jax.random.fold_in(kx, 4), (B, n, d_out), kd)
        z2 = jax.random.normal(jax.random.fold_in(kx, 5), (B, n, d_out), kd)
        kfn = {"dyad_mm_dgrad": dyad_mm.dyad_mm_dgrad,
               "dyad_mm_dgrad_two": dyad_mm.dyad_mm_dgrad_two}[op]
        kernel = lambda **c: kfn(z1, z2, w1, w2, interpret=interpret, **c)
        # produced axis is d_in, contracted is d_out: swap the feature dims
        # for candidate clamping and effective-tile dedup
        plan_dims = (B, d_in, d_out)
        cand_dims = (d_out, d_in)
    elif op == "dyad_mm_wgrad":
        z1 = jax.random.normal(jax.random.fold_in(kx, 4), (B, n, d_out), kd)
        z2 = jax.random.normal(jax.random.fold_in(kx, 5), (B, n, d_out), kd)
        kernel = lambda **c: dyad_mm.dyad_mm_wgrad(
            x1, x2, z1, z2, interpret=interpret, **c)
        plan_dims = (B, d_out, d_in)
        cand_dims = (d_in, d_out)
    elif quant:
        kfn = {"dyad_mm_blocks_w8": dyad_mm.dyad_mm_blocks_q,
               "dyad_mm_blocks_two_w8": dyad_mm.dyad_mm_blocks_two_q}[op]
        w1q, s1 = quant_lib.quantize_dyad_weight(w1, dtype)
        w2q, s2 = quant_lib.quantize_dyad_weight(w2, dtype)
        kernel = lambda **c: kfn(x1, x2, w1q, w2q, s1, s2,
                                 interpret=interpret, **c)
        plan_dims = (B, d_out, d_in)
        cand_dims = (d_in, d_out)
    else:
        kfn = {"dyad_mm_blocks": dyad_mm.dyad_mm_blocks,
               "dyad_mm_blocks_two": dyad_mm.dyad_mm_blocks_two}[op]
        kernel = lambda **c: kfn(x1, x2, w1, w2, interpret=interpret, **c)
        plan_dims = (B, d_out, d_in)
        cand_dims = (d_in, d_out)

    cands = list(candidates) if candidates is not None else candidate_blocks(
        B, n, cand_dims[0], cand_dims[1], str(kd) if quant else dtype,
        n_acc=n_acc, wgrad=(op == "dyad_mm_wgrad"),
        w_dtype=dtype if quant else None)
    # distinct requested blocks can clamp to identical EFFECTIVE tiles for
    # this concrete shape — timing those again only measures noise
    seen_plans = set()
    deduped = []
    for cand in cands:
        plan = dyad_mm.plan_tiles(*plan_dims, cand["block_b"],
                                  cand["block_o"], cand["block_k"])
        if plan in seen_plans:
            continue
        seen_plans.add(plan)
        deduped.append(cand)
    cands = deduped
    best, best_us = _time_candidates(kernel, cands, key, iters, warmup)
    cache.put(key, best, us=round(best_us, 2), op=op,
              candidates=len(cands))
    return best, best_us


def _time_candidates(kernel, cands: List[Blocks], key: str, iters: int,
                     warmup: int) -> Tuple[Blocks, float]:
    """Time every candidate and return the winner.

    Long sweeps used to be completely silent (a deep-model ``--autotune``
    looks like a hang): with ``REPRO_OBS_VERBOSE=1`` — or whenever the
    tracer is enabled — each candidate prints a progress line, and every
    measurement lands in the trace as an ``autotune_candidate`` span."""
    best: Optional[Blocks] = None
    best_us = float("inf")
    n = len(cands)
    chatty = obs.verbose()
    t_sweep = time.perf_counter()
    with obs.span("autotune_sweep", cat="autotune", key=key, candidates=n):
        for i, cand in enumerate(cands):
            with obs.span("autotune_candidate", cat="autotune", key=key,
                          i=i, **cand) as sp:
                try:
                    us = _time_us(lambda c=cand: kernel(**c),
                                  iters=iters, warmup=warmup)
                except Exception as e:   # invalid tiling for backend/shape
                    # skipped, never silently: a candidate the compiler
                    # refuses (too much VMEM, an illegal block) is printed
                    # every time, verbose or not
                    print(f"[autotune] {key}: {i + 1}/{n} {cand} FAILED "
                          f"({type(e).__name__}: {str(e)[:300]})",
                          file=sys.stderr, flush=True)
                    continue
                sp.set(us=round(us, 2))
            if chatty:
                print(f"[autotune] {key}: {i + 1}/{n} {cand} -> {us:.1f}us"
                      f"{'  <- best' if us < best_us else ''}", flush=True)
            if us < best_us:
                best, best_us = cand, us
    if best is None:
        raise RuntimeError(f"autotune: every candidate failed for {key}")
    if chatty:
        print(f"[autotune] {key}: winner {best} {best_us:.1f}us "
              f"({n} candidates in "
              f"{time.perf_counter() - t_sweep:.2f}s)", flush=True)
    return best, best_us


def model_dyad_shapes(cfg) -> List[Tuple[int, int, int]]:
    """Distinct ``(n_dyad, d_in_per_block, d_out_per_block)`` kernel shapes a
    model config routes through the fused kernel (ff site today)."""
    lin = getattr(cfg, "linear", None)
    if lin is None or not getattr(lin, "use_kernel", False):
        return []
    from repro.core import dyad

    shapes = set()
    pairs = []
    if lin.dyad_at("ff"):
        pairs += [(cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)]
    if lin.dyad_at("attn"):
        # hd is the RESOLVED head dim (the raw head_dim field defaults to 0)
        hd = getattr(cfg, "hd", None) or getattr(cfg, "head_dim", 0)
        q = cfg.n_heads * hd
        kv = cfg.n_kv_heads * hd
        pairs += [(cfg.d_model, q), (cfg.d_model, kv), (q, cfg.d_model)]
    for f_in, f_out in pairs:
        if f_in <= 0 or f_out <= 0:
            continue
        n = dyad.resolve_n_dyad(f_in, f_out, lin.n_dyad)
        shapes.add((n, f_in // n, f_out // n))
    return sorted(shapes)


def model_ff_fused_shape(cfg) -> Optional[Tuple[int, int, int]]:
    """``(n_dyad, d_in_per_block, d_ff_per_block)`` when the config routes
    its ff modules through the megakernel (``fuse_ff_kernel``), else None.
    The down output width per block equals d_in_per_block (ff maps
    d_model -> d_ff -> d_model).  Mirrors ``layers.mlp._ff_kernel_ready``:
    biased ff modules (``mlp_bias=True``, e.g. OPT) and unsupported
    epilogue activations fall back to the per-projection kernels, so
    sweeping megakernel tiles for them would burn minutes tuning an op
    that is never dispatched (and every candidate would fail for an
    unknown act)."""
    lin = getattr(cfg, "linear", None)
    if (lin is None or not getattr(lin, "fuse_ff_kernel", False)
            or not getattr(lin, "use_kernel", False)
            or not lin.dyad_at("ff")
            or getattr(cfg, "mlp_bias", False)):
        return None
    from repro.kernels.ref import ACTS

    if getattr(cfg, "act", "gelu") not in set(ACTS) | {"swiglu"}:
        return None
    from repro.core import dyad

    n = dyad.resolve_n_dyad(cfg.d_model, cfg.d_ff, lin.n_dyad)
    return (n, cfg.d_model // n, cfg.d_ff // n)


def bwd_ops_for_variant(variant: str) -> List[str]:
    """The backward kernel ops a DYAD variant routes through: OT's two dx
    components share a layout (ONE fused dgrad accumulator); IT/DT emit the
    components separately.  wgrad is variant-independent."""
    dgrad = "dyad_mm_dgrad" if variant == "ot" else "dyad_mm_dgrad_two"
    return [dgrad, "dyad_mm_wgrad"]


def model_attn_shape(cfg) -> Optional[Tuple[int, int, int]]:
    """``(n_kv_heads, gqa_ratio, head_dim)`` when the config routes its
    attention through the flash kernels (``flash_attn``), else None."""
    if not getattr(cfg, "flash_attn", False):
        return None
    heads, kv = getattr(cfg, "n_heads", 0), getattr(cfg, "n_kv_heads", 0)
    if heads <= 0 or kv <= 0:
        return None
    hd = getattr(cfg, "hd", None) or getattr(cfg, "head_dim", 0)
    if not hd:
        return None
    return kv, heads // kv, hd


def mesh_shard_counts(mesh=None, model_axis: str = "model"
                      ) -> Tuple[int, int]:
    """``(tp, dp)`` shard counts for a mesh: tp = the model-axis size,
    dp = every other axis folded together (the batch-sharding product).
    ``mesh=None`` consults the ambient activation-sharding context
    (:mod:`repro.sharding.ctx`); no mesh/ctx -> ``(1, 1)``."""
    if mesh is None:
        from repro.sharding import ctx as shard_ctx

        actx = shard_ctx.current()
        if actx is None:
            return 1, 1
        mesh, model_axis = actx.mesh, actx.model
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = max(int(sizes.get(model_axis, 1)), 1)
    total = 1
    for s in sizes.values():
        total *= int(s)
    return tp, max(total // tp, 1)


def ensure_tuned_for_model(cfg, tokens: int, *, dtype: Optional[str] = None,
                           iters: int = 2, include_bwd: bool = False,
                           seq_len: Optional[int] = None,
                           kv_len: Optional[int] = None,
                           page_size: Optional[int] = None,
                           mesh=None, model_axis: str = "model"
                           ) -> Dict[str, Blocks]:
    """Pre-tune every fused-kernel shape a model will hit with ``tokens``
    rows (decode: batch; prefill: batch*seq; train: batch*seq).  Serving
    calls this at engine construction — and ``launch/train.py --autotune``
    calls it with ``include_bwd=True`` — so the first jit trace already
    picks tuned tiles (a ``value_and_grad`` trace resolves the dgrad/wgrad
    tiles at trace time too).  No-op (empty dict) for configs that don't
    use the Pallas kernel.

    ``seq_len`` additionally tunes the ``flash_prefill`` tiles for that
    sequence length and ``kv_len`` the ``flash_decode`` tiles for a cache
    of that length (``tokens`` = decode batch rows; window-bounded ring
    caches clamp it) — both only for ``cfg.flash_attn`` configs.  A paged
    engine passes ``page_size`` too, which swaps the decode op for
    ``flash_decode_paged`` (the page size rides in its cache key).

    ``dtype`` defaults to the config's COMPUTE dtype — ops.py casts weights
    to the activation dtype, so that is the dtype trace-time lookups use.

    ``mesh`` (or, when None, the ambient activation-sharding context) makes
    the sweep tensor-parallel-aware: the ff megakernel and flash ops run
    per-shard under :mod:`repro.kernels.tp`, so their tiles are tuned at
    per-shard dims (hidden ``j/tp``, KV heads ``kvh/tp``, rows
    ``tokens/dp``) under :func:`tp_shards` — the ``|tp{N}`` keys the
    shard_map body will look up at trace time.  Non-divisible shards fall
    back to the einsum route in the layers, so their sweep is skipped.  The
    single-matmul dyad ops dispatch at global shapes (GSPMD partitions
    them), so they keep un-suffixed keys."""
    if dtype is None:
        dtype = getattr(cfg, "compute_dtype", None) or "float32"
    tp, dp = mesh_shard_counts(mesh, model_axis)
    tokens_shard = max(tokens // dp, 1)
    tuned: Dict[str, Blocks] = {}
    attn = model_attn_shape(cfg)
    if attn is not None:
        # sweep only when dispatch will actually consult the tiles
        # (PR-4 precedent: never burn minutes tuning an op that is never
        # dispatched — off-TPU the flash route needs REPRO_KERNEL_ATTN)
        from repro.kernels.ops import attn_route

        if attn_route() != "flash":
            attn = None
    if attn is not None and tp > 1:
        from repro.kernels import tp as ktp

        if not ktp.tp_enabled() or attn[0] % tp != 0:
            attn = None  # layer falls back to einsum attention under TP
    if attn is not None:
        kvh, g, hd = attn
        kvh //= tp
        with tp_shards(tp):
            if seq_len is not None and seq_len > 1:
                blocks, _ = autotune_dyad("flash_prefill", seq_len, kvh, hd,
                                          seq_len, dtype, d_mid=g,
                                          iters=iters)
                tuned[tune_key("flash_prefill", seq_len, kvh, hd, seq_len,
                               dtype, d_mid=g)] = blocks
            if kv_len is not None:
                win = getattr(cfg, "window", None)
                L = min(kv_len, win) if win else kv_len
                rows = max(tokens_shard if tp > 1 else tokens, 1)
                if page_size is not None:
                    blocks, _ = autotune_dyad(
                        "flash_decode_paged", rows, kvh, hd, L, dtype,
                        d_mid=g, d_page=page_size, iters=iters)
                    tuned[tune_key("flash_decode_paged", rows, kvh,
                                   hd, L, dtype, d_mid=g,
                                   d_page=page_size)] = blocks
                else:
                    blocks, _ = autotune_dyad("flash_decode", rows,
                                              kvh, hd, L, dtype, d_mid=g,
                                              iters=iters)
                    tuned[tune_key("flash_decode", rows, kvh, hd, L,
                                   dtype, d_mid=g)] = blocks
    variant = getattr(cfg.linear, "variant", "it")
    # quantized serving tunes the _w8 op keys too: their key dtype is the
    # PAYLOAD dtype (the field the kernel wrappers resolve on)
    qdt = None
    if getattr(cfg.linear, "quant", None):
        from repro import quant as quant_lib

        if quant_lib.enabled():
            qdt = str(quant_lib.resolve_dtype(cfg.linear.quant)[0])
    for n, d_in, d_out in model_dyad_shapes(cfg):
        ops = ["dyad_mm_blocks" if variant == "it" else "dyad_mm_blocks_two"]
        if qdt is not None:
            ops.append(ops[0] + "_w8")
        if include_bwd:
            ops += bwd_ops_for_variant(variant)
        for op in ops:
            dt = qdt if op.endswith("_w8") else dtype
            blocks, _ = autotune_dyad(op, tokens, n, d_in, d_out, dt,
                                      iters=iters)
            tuned[tune_key(op, tokens, n, d_in, d_out, dt)] = blocks
    ff = model_ff_fused_shape(cfg)
    if ff is not None and tp > 1:
        from repro.kernels import tp as ktp

        if not ktp.tp_enabled() or ff[2] % tp != 0:
            ff = None  # layer falls back to the einsum ff route under TP
    if ff is not None:
        n, k, j = ff
        j //= tp
        mact = getattr(cfg, "act", "gelu")
        op = "dyad_ff_fused_swiglu" if mact == "swiglu" else "dyad_ff_fused"
        with tp_shards(tp):
            rows = tokens_shard if tp > 1 else tokens
            blocks, _ = autotune_dyad(op, rows, n, k, k, dtype, d_mid=j,
                                      act=mact, iters=iters)
            tuned[tune_key(op, rows, n, k, k, dtype, d_mid=j)] = blocks
            if qdt is not None:
                blocks, _ = autotune_dyad(op + "_w8", rows, n, k, k, qdt,
                                          d_mid=j, act=mact, iters=iters)
                tuned[tune_key(op + "_w8", rows, n, k, k, qdt,
                               d_mid=j)] = blocks
            if include_bwd:
                # the megakernel VJP composes the existing bwd kernels; the
                # main loop above already tunes them at both ff shapes
                # except the OT-fused down dgrad (d_in = d_ff/n,
                # d_out = d_model/n)
                blocks, _ = autotune_dyad("dyad_mm_dgrad", rows, n, j, k,
                                          dtype, iters=iters)
                tuned[tune_key("dyad_mm_dgrad", rows, n, j, k,
                               dtype)] = blocks
    return tuned
