"""Compile the main-path Pallas kernels for a TPU v5e, with no chip attached.

The TPU compiler is installed beside the CPU backend and compiles for a
described topology.  Interpret-mode tests cannot see what Mosaic refuses
(block shapes off the (8, 128) tiling, too much VMEM), so each case here
lowers one kernel at a published model width with the default tiles, the
tiles a chip run resolves, and checks that the compiled program holds the
Mosaic kernel (``tpu_custom_call``).  Nothing runs: shapes only.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dyad_mm, flash_attn

F32, BF16 = jnp.float32, jnp.bfloat16

# (B tokens, n_dyad, d_model/n, d_ff/n, dtype): the ff shapes of the two
# chip configurations — OPT-125m trained at batch 8 x seq 512 in fp32, and
# Qwen3-0.6B served in bf16 (a 512-token prefill and a 4-slot decode).
OPT_FF = (4096, 4, 192, 768, F32)
QWEN_PREFILL_FF = (512, 4, 256, 768, BF16)
QWEN_DECODE_FF = (4, 4, 256, 768, BF16)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip at ``(shape, dtype)`` args."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _mm_case(op):
    """(fn, arg shapes) for one DYAD mm kernel at OPT-125m's up (d_model/n
    -> d_ff/n) and down (d_ff/n -> d_model/n) projections."""
    B, n, d, f, dt = OPT_FF

    def shapes(d_in, d_out):
        x, w, z = (B, n, d_in), (n, d_out, d_in), (B, n, d_out)
        if op in ("dgrad", "dgrad_two"):
            return [(z, dt), (z, dt), (w, dt), (w, dt)]
        if op == "wgrad":
            return [(x, dt), (x, dt), (z, dt), (z, dt)]
        return [(x, dt), (x, dt), (w, dt), (w, dt)]

    fn = {"fwd": dyad_mm.dyad_mm_blocks,
          "fwd_two": dyad_mm.dyad_mm_blocks_two,
          "dgrad": dyad_mm.dyad_mm_dgrad,
          "dgrad_two": dyad_mm.dyad_mm_dgrad_two,
          "wgrad": dyad_mm.dyad_mm_wgrad}[op]
    return fn, shapes(d, f), shapes(f, d)


@pytest.mark.parametrize("op", ["fwd", "fwd_two", "dgrad", "dgrad_two",
                                "wgrad"])
def test_dyad_mm_compiles(one_chip, op):
    fn, up, down = _mm_case(op)
    for shapes in (up, down):
        assert "tpu_custom_call" in _compile_text(fn, one_chip, *shapes)


def _ff_shapes(B, n, d, f, dt, gated, w_dtype=None):
    wdt = w_dtype or dt
    x = [((B, n, d), dt)] * 2
    ups = [((n, f, d), wdt)] * (4 if gated else 2)
    downs = [((n, d, f), wdt)] * 2
    return x, ups, downs


@pytest.mark.parametrize("width,act", [(OPT_FF, "relu"),
                                       (QWEN_PREFILL_FF, "swiglu"),
                                       (QWEN_DECODE_FF, "swiglu")])
def test_dyad_ff_fused_compiles(one_chip, width, act):
    """The megakernel at default tiles; compiling also proves its VMEM
    (double-buffered operand tiles plus fp32 accumulators) fits."""
    gated = act == "swiglu"
    x, ups, downs = _ff_shapes(*width, gated)

    def fn(x1, x2, *ws):
        if gated:
            wg1, wg2, wu1, wu2, wd1, wd2 = ws
            return dyad_mm.dyad_ff_fused(x1, x2, wu1, wu2, wd1, wd2, wg1=wg1,
                                         wg2=wg2, act=act)
        return dyad_mm.dyad_ff_fused(x1, x2, *ws, act=act)

    assert "tpu_custom_call" in _compile_text(fn, one_chip,
                                              *(x + ups + downs))


@pytest.mark.parametrize("width,act", [(OPT_FF, "relu"),
                                       (QWEN_PREFILL_FF, "swiglu"),
                                       (QWEN_DECODE_FF, "swiglu")])
def test_dyad_ff_fused_int8_compiles(one_chip, width, act):
    """The int8 weight-stream twin, with its (n, rows) fp32 scales."""
    B, n, d, f, dt = width
    gated = act == "swiglu"
    x, ups, downs = _ff_shapes(B, n, d, f, BF16, gated, w_dtype=jnp.int8)
    s_ups = [((n, f), F32)] * len(ups)
    s_downs = [((n, d), F32)] * 2

    def fn(x1, x2, *rest):
        k = len(ups)
        w_up, (wd1, wd2) = rest[:k], rest[k:k + 2]
        s_up, (sd1, sd2) = rest[k + 2:2 * k + 2], rest[2 * k + 2:]
        gate = {}
        if gated:
            gate = dict(wg1=w_up[0], wg2=w_up[1], sg1=s_up[0], sg2=s_up[1])
            w_up, s_up = w_up[2:], s_up[2:]
        return dyad_mm.dyad_ff_fused_q(x1, x2, *w_up, wd1, wd2, *s_up, sd1,
                                       sd2, act=act, **gate)

    assert "tpu_custom_call" in _compile_text(
        fn, one_chip, *(x + ups + downs + s_ups + s_downs))


# flash attention: (B, S, K kv heads, G q heads per kv head, head dim,
# dtype) — OPT-125m's training batch, and one Qwen3-0.6B prefill
OPT_ATTN = (8, 512, 12, 1, 64, F32)
QWEN_ATTN = (1, 512, 8, 2, 128, BF16)


@pytest.mark.parametrize("width", [OPT_ATTN, QWEN_ATTN])
def test_flash_prefill_with_lse_compiles(one_chip, width):
    B, S, K, G, h, dt = width
    fn = lambda q, k, v: flash_attn.flash_prefill(q, k, v, save_lse=True)
    text = _compile_text(fn, one_chip, ((B, S, K, G, h), dt),
                         ((B, S, K, h), dt), ((B, S, K, h), dt))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [OPT_ATTN, QWEN_ATTN])
def test_flash_prefill_grads_compiles(one_chip, width):
    B, S, K, G, h, dt = width
    q, kv = ((B, S, K, G, h), dt), ((B, S, K, h), dt)
    fn = lambda q, k, v, o, lse, do: flash_attn.flash_prefill_grads(
        q, k, v, o, lse, do)
    text = _compile_text(fn, one_chip, q, kv, kv, q,
                         ((B, K, S * G), F32), q)
    assert "tpu_custom_call" in text


def test_flash_decode_compiles(one_chip):
    """Qwen3-0.6B ring-cache decode: 4 slots over 512 + 32 positions."""
    B, L, K, G, h = 4, 544, 8, 2, 128
    fn = lambda q, k, v, idx: flash_attn.flash_decode(q, k, v, idx)
    text = _compile_text(fn, one_chip, ((B, 1, K, G, h), BF16),
                         ((B, L, K, h), F32), ((B, L, K, h), F32),
                         ((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quant", [False, True])
def test_flash_decode_paged_compiles(one_chip, quant):
    """Qwen3-0.6B paged decode: 4 slots, 16-token pages, 34 blocks each
    (544 positions), fp32 or int8 pools."""
    B, P, NB, K, G, h = 4, 16, 34, 8, 2, 128
    n_pages = 1 + B * NB
    pool = ((n_pages, P, K, h), jnp.int8 if quant else F32)
    shapes = [((B, 1, K, G, h), BF16), pool, pool, ((B, NB), jnp.int32),
              ((B,), jnp.int32)]
    if quant:
        shapes += [((n_pages, P, K), F32)] * 2
        fn = lambda q, pk, pv, bt, idx, sk, sv: flash_attn.flash_decode_paged(
            q, pk, pv, bt, idx, scales_k=sk, scales_v=sv)
    else:
        fn = lambda q, pk, pv, bt, idx: flash_attn.flash_decode_paged(
            q, pk, pv, bt, idx)
    assert "tpu_custom_call" in _compile_text(fn, one_chip, *shapes)
