"""The name each Pallas kernel carries into the device trace.

A profiler trace names a kernel's device event after the innermost name
scope around its ``pallas_call``: the call's ``name=`` when it has one,
else the jitted wrapper that holds it (``%_mm_impl.38 = ...``).  The
benchmark's readers (``chipbench/metrics/``) find the training and serving
kernels by their wrapper's name, so those calls carry no ``name=`` (it
would rename the event) and their wrappers keep their names; the decode
kernels no reader matches carry a fixed ``name=``.  Each case traces one
entry point at a small shape and reads the name from its jaxpr.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dyad_gmm, dyad_mm, flash_attn

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


def _sds(*shapes):
    return [jax.ShapeDtypeStruct(s, d) for s, d in shapes]


def _kernels(jaxpr, wrapper=None):
    """``(wrapper, name)`` of every ``pallas_call`` in ``jaxpr``: the name
    of the innermost jitted function around it and its ``name=``."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((wrapper, eqn.params.get("name")))
            continue
        inner = eqn.params.get("name") if eqn.primitive.name in (
            "pjit", "jit") else wrapper
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    out += _kernels(sub, inner)
    return out


# (B tokens, n_dyad, d_in/n, d_out/n)
B, N, DI, DO = 64, 4, 128, 256
X, Z = ((B, N, DI), BF16), ((B, N, DO), BF16)
W, WT = ((N, DO, DI), BF16), ((N, DI, DO), BF16)
# attention: (B, S, K kv heads, G q heads per kv head, head dim)
AB, S, K, G, H = 2, 128, 2, 2, 64
Q, KV = ((AB, S, K, G, H), BF16), ((AB, S, K, H), BF16)
# paged decode: 16-token pages, 8 blocks a slot
P, NB = 16, 8
POOL = ((1 + AB * NB, P, K, H), BF16)

# grouped expert rows: 4 experts' DYAD blocks over 256 sorted rows
GE, GM = 4, 256
GX, GZ = ((N, GM, DI), BF16), ((N, GM, DO), BF16)
GW = ((GE, N, DO, DI), BF16)
STARTS, ENDS = ((GE + 1,), I32), ((GE,), I32)

CASES = {
    "dyad_gmm": (dyad_gmm.gmm, [GX, GX, GW, GW, STARTS, ENDS]),
    "dyad_gmm_dgrad": (dyad_gmm.gmm_dgrad, [GZ, GW, GW, STARTS, ENDS]),
    "dyad_gmm_wgrad": (dyad_gmm.gmm_wgrad, [GX, GX, GZ, STARTS, ENDS]),
    "dyad_mm": (dyad_mm.dyad_mm_blocks, [X, X, W, W]),
    "dyad_mm_dgrad": (dyad_mm.dyad_mm_dgrad, [Z, Z, W, W]),
    "dyad_wgrad": (dyad_mm.dyad_mm_wgrad, [X, X, Z, Z]),
    "dyad_ff_fused": (
        lambda x1, x2, wu1, wu2, wd1, wd2: dyad_mm.dyad_ff_fused(
            x1, x2, wu1, wu2, wd1, wd2, act="relu"),
        [X, X, W, W, WT, WT]),
    "flash_fwd": (lambda q, k, v: flash_attn.flash_prefill(q, k, v),
                  [Q, KV, KV]),
    "flash_grads": (flash_attn.flash_prefill_grads,
                    [Q, KV, KV, Q, ((AB, K, S * G), F32), Q]),
    "flash_decode": (flash_attn.flash_decode,
                     [((AB, 1, K, G, H), BF16), KV, KV, ((AB,), I32)]),
    "flash_decode_paged": (
        flash_attn.flash_decode_paged,
        [((AB, 1, K, G, H), BF16), POOL, POOL, ((AB, NB), I32),
         ((AB,), I32)]),
    "flash_decode_paged_q": (
        lambda q, pk, pv, bt, idx, sk, sv: flash_attn.flash_decode_paged(
            q, pk, pv, bt, idx, scales_k=sk, scales_v=sv),
        [((AB, 1, K, G, H), BF16), POOL[:1] + (jnp.int8,),
         POOL[:1] + (jnp.int8,), ((AB, NB), I32), ((AB,), I32),
         ((1 + AB * NB, P, K), F32), ((1 + AB * NB, P, K), F32)]),
}

# entry point -> the (wrapper, name=) of each kernel it runs
WANT = {
    "dyad_gmm": [("_gmm_impl", None)],
    "dyad_gmm_dgrad": [("_gmm_impl", None)],
    "dyad_gmm_wgrad": [("_gmm_wgrad_impl", None)],
    "dyad_mm": [("_mm_impl", None)],
    "dyad_mm_dgrad": [("_mm_impl", None)],
    "dyad_wgrad": [("_wgrad_impl", None)],
    "dyad_ff_fused": [("_ff_impl", None)],
    "flash_fwd": [("_prefill_impl", None)],
    "flash_grads": [("_dq_impl", None), ("_dkv_impl", None)],
    "flash_decode": [("_decode_impl", "flash_decode")],
    "flash_decode_paged": [("_decode_paged_impl", None)],
    "flash_decode_paged_q": [("_decode_paged_q_impl",
                              "flash_decode_paged_q")],
}


@pytest.mark.parametrize("entry", sorted(CASES))
def test_kernel_name_in_jaxpr(entry):
    fn, shapes = CASES[entry]
    jaxpr = jax.make_jaxpr(fn)(*_sds(*shapes)).jaxpr
    assert sorted(_kernels(jaxpr), key=str) == sorted(WANT[entry], key=str)


def _reader(name):
    from chipbench import common
    return common.load_module(f"{common.BENCH}/metrics/{name}.py")


@pytest.mark.parametrize("entry", ["dyad_gmm", "dyad_gmm_dgrad",
                                   "dyad_gmm_wgrad"])
def test_grouped_kernels_escape_the_other_readers(entry):
    """A trace event of a grouped kernel (``%_gmm_impl.3 = ...``) is counted
    by the grouped reader only, not by the DYAD (``%_mm_impl``,
    ``%_wgrad_impl``) or the flash (``%_prefill_impl``, ``%_dq_impl``,
    ``%_dkv_impl``) reader."""
    for wrapper, _ in WANT[entry]:
        event = f"%{wrapper}.3 = bf16[2,256,8]{{2,1,0}} custom-call(...)"
        assert not _reader("dyad_mm_roofline").match(event)
        assert not _reader("flash_attn_roofline").match(event)
        assert _reader("expert_gmm_roofline").match(event)
