"""SPMD integration on a small fake-device mesh (subprocess: device count is
locked at first jax init, so multi-device tests must re-exec)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 570):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    """The SAME train step under a (2,4) mesh must produce the same loss and
    params as unsharded execution — the SPMD-correctness contract."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.optim import AdamW, schedule
from repro.train import init_train_state, make_train_step
from repro.sharding import MeshRules, state_shardings, batch_shardings
from repro.data import SyntheticLM

cfg = configs.get("qwen3_0_6b", smoke=True).replace(
    vocab_size=256, compute_dtype="float32")
opt = AdamW(lr=schedule.constant(1e-3))
data = SyntheticLM(vocab_size=256, seq_len=16, global_batch=8)
batch = data.batch(0)
state = init_train_state(cfg, opt, jax.random.PRNGKey(0))
step = make_train_step(cfg, opt)

ref_state, ref_m = jax.jit(step)(state, batch)

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
rules = MeshRules(model="model", dp=("data",), fsdp=("data",))
st_sh = state_shardings(mesh, jax.eval_shape(lambda: state), rules)
b_sh = batch_shardings(mesh, jax.eval_shape(lambda: batch), rules)
sharded = jax.jit(step, in_shardings=(st_sh, b_sh),
                  out_shardings=(st_sh, NamedSharding(mesh, P())))
sp_state, sp_m = sharded(state, batch)
assert abs(float(ref_m["loss"]) - float(sp_m["loss"])) < 1e-3, (
    float(ref_m["loss"]), float(sp_m["loss"]))
for a, b in zip(jax.tree.leaves(ref_state["params"]),
                jax.tree.leaves(sp_state["params"])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
print("SPMD == single-device OK, loss", float(sp_m["loss"]))
""")
    assert "OK" in out


def test_compressed_psum_shard_map():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compress import compressed_psum
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("dp",))
x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4) / 7.0

def f(xs):
    return compressed_psum(xs[0], "dp")

# check_vma=False: the all-gather+sum result is replicated by construction
# but the varying-axes checker cannot infer that through the int8 round-trip
y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                          check_vma=False))(x)
expect = np.asarray(x).sum(0)
np.testing.assert_allclose(np.asarray(y), expect, rtol=0.02, atol=0.02)
print("compressed_psum OK")
""")
    assert "OK" in out


def test_tp_ff_fused_forward_and_grad_match_fallback():
    """The shard_map TP megakernel route (kernels.tp.dyad_ff_tp) must be
    numerically equivalent to both the einsum fallback (REPRO_KERNEL_TP=off)
    and unsharded execution — forward and jax.grad — across tp=2, tp=4 and
    dp-x-tp meshes, with ZERO tp_fallback dispatches on the fused runs."""
    out = _run("""
import os
os.environ["REPRO_KERNEL_FF"] = "fused"
import jax, jax.numpy as jnp, numpy as np
from repro import configs, obs
from repro.launch.mesh import make_mesh
from repro.layers import mlp
from repro.sharding import ctx as shard_ctx

lin = configs.linear_cfg("dyad_it_4_kernel_ffused")
d, dff = 128, 512
params = mlp.init_mlp(jax.random.PRNGKey(0), d, dff, lin, act="swiglu")
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, d))

def loss(p, x):
    return jnp.sum(mlp.apply_mlp(p, x, lin, act="swiglu") ** 2)

ref = jax.jit(lambda p, x: mlp.apply_mlp(p, x, lin, act="swiglu"))(params, x)
g_ref = jax.jit(jax.grad(loss))(params, x)

for shape in ((4, 2), (2, 4)):          # dp x tp: tp=2 and tp=4
    mesh = make_mesh(shape)
    with shard_ctx.activation_sharding(mesh, dp=("data",), model="model"):
        obs.reset_route_counts()
        out = jax.jit(lambda p, x: mlp.apply_mlp(p, x, lin,
                                                 act="swiglu"))(params, x)
        g_tp = jax.jit(jax.grad(loss))(params, x)
        counts = obs.route_counts()
        assert counts.get(("ff_tp", "tp_fallback"), 0) == 0, counts
        assert counts.get(("ff_tp", "tp_fused"), 0) > 0, counts
        os.environ["REPRO_KERNEL_TP"] = "off"
        try:
            fb = jax.jit(lambda p, x: mlp.apply_mlp(p, x, lin,
                                                    act="swiglu"))(params, x)
            g_fb = jax.jit(jax.grad(loss))(params, x)
        finally:
            del os.environ["REPRO_KERNEL_TP"]
        counts = obs.route_counts()
        assert counts.get(("ff_tp", "tp_fallback"), 0) > 0, counts
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fb), atol=2e-5)
    for a, b, c in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_tp),
                       jax.tree.leaves(g_fb)):
        scale = max(float(jnp.max(jnp.abs(a))), 1.0)
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale, atol=2e-6)
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(c) / scale, atol=2e-6)
    print("tp", shape, "OK")
print("ff TP fused == fallback == single-device OK")
""")
    assert "ff TP fused == fallback == single-device OK" in out


def test_tp_flash_kernels_match_single_device():
    """The shard_map flash wrappers (KV-head axis per shard, GQA groups
    intact, scalar-prefetch machinery per device) must be exact vs the
    single-device kernels: prefill fwd+grad, ring decode, paged decode."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.sharding import ctx as shard_ctx
from repro.kernels import ops as kops, tp as ktp

key = jax.random.PRNGKey(0)
B, S, K, G, h, T = 4, 16, 4, 2, 32, 16
q = jax.random.normal(key, (B, S, K, G, h))
k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, K, h))
v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, K, h))

mesh = make_mesh((2, 4))
ref = jax.jit(lambda q, k, v: kops.flash_attention(q, k, v, 0, 0))(q, k, v)
gref = jax.jit(jax.grad(
    lambda q, k, v: jnp.sum(kops.flash_attention(q, k, v, 0, 0) ** 2),
    argnums=(0, 1, 2)))(q, k, v)
with shard_ctx.activation_sharding(mesh, dp=("data",), model="model"):
    ctx = shard_ctx.current()
    out = jax.jit(lambda q, k, v: ktp.flash_attention_tp(
        q, k, v, 0, 0, ctx=ctx))(q, k, v)
    gtp = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(ktp.flash_attention_tp(
            q, k, v, 0, 0, ctx=ctx) ** 2), argnums=(0, 1, 2)))(q, k, v)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
for a, b in zip(gref, gtp):
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4)

L = 32
kc = jax.random.normal(jax.random.fold_in(key, 3), (B, L, K, h))
vc = jax.random.normal(jax.random.fold_in(key, 4), (B, L, K, h))
idx = jnp.array([5, 9, 13, 17], jnp.int32)
qd = jax.random.normal(jax.random.fold_in(key, 5), (B, 1, K, G, h))
refd = jax.jit(lambda q, k, v, i: kops.flash_decode(q, k, v, i))(
    qd, kc, vc, idx)
with shard_ctx.activation_sharding(mesh, dp=("data",), model="model"):
    ctx = shard_ctx.current()
    outd = jax.jit(lambda q, k, v, i: ktp.flash_decode_tp(
        q, k, v, i, ctx=ctx))(qd, kc, vc, idx)
np.testing.assert_array_equal(np.asarray(outd), np.asarray(refd))

P_, NP = 8, 17
pk = jax.random.normal(jax.random.fold_in(key, 6), (NP, P_, K, h))
pv = jax.random.normal(jax.random.fold_in(key, 7), (NP, P_, K, h))
bt = jnp.array([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 9, 0],
                [10, 11, 12, 13]], jnp.int32)
refp = jax.jit(lambda q, pk, pv, bt, i: kops.flash_decode_paged(
    q, pk, pv, bt, i))(qd, pk, pv, bt, idx)
with shard_ctx.activation_sharding(mesh, dp=("data",), model="model"):
    ctx = shard_ctx.current()
    outp = jax.jit(lambda q, pk, pv, bt, i: ktp.flash_decode_paged_tp(
        q, pk, pv, bt, i, ctx=ctx))(qd, pk, pv, bt, idx)
np.testing.assert_array_equal(np.asarray(outp), np.asarray(refp))
print("flash TP == single-device OK")
""")
    assert "OK" in out


def test_tp_engine_decode_token_equality():
    """End-to-end: Engine decode under a dp-x-tp mesh with the fused TP
    kernels must emit EXACTLY the tokens of the einsum fallback
    (REPRO_KERNEL_TP=off), with zero tp_fallback dispatches."""
    out = _run("""
import os
os.environ["REPRO_KERNEL_FF"] = "fused"
os.environ["REPRO_KERNEL_ATTN"] = "flash"
import jax, jax.numpy as jnp, numpy as np
from repro import configs, obs
from repro.launch.mesh import make_mesh
from repro.serve import Engine
from repro.sharding import ctx as shard_ctx

cfg = configs.get("qwen3_0_6b", smoke=True,
                  linear=configs.linear_cfg("dyad_it_4_kernel_ffused"))
cfg = cfg.replace(vocab_size=256, compute_dtype="float32")
key = jax.random.PRNGKey(0)
from repro.models import model
params = model.init_params(cfg, key)
prompts = jax.random.randint(jax.random.fold_in(key, 1), (4, 8), 0, 256)

mesh = make_mesh((2, 2))   # dp=2 x tp=2 (kv heads = 2 divide)
with shard_ctx.activation_sharding(mesh, dp=("data",), model="model"):
    obs.reset_route_counts()
    eng = Engine(cfg, params, max_len=16)
    toks_tp = np.asarray(eng.generate(prompts, 8))
    counts = obs.route_counts()
assert counts.get(("ff_tp", "tp_fallback"), 0) == 0, counts
assert counts.get(("attn_tp", "tp_fallback"), 0) == 0, counts
assert counts.get(("ff_tp", "tp_fused"), 0) > 0, counts
assert counts.get(("attn_tp", "tp_fused"), 0) > 0, counts

os.environ["REPRO_KERNEL_TP"] = "off"
with shard_ctx.activation_sharding(mesh, dp=("data",), model="model"):
    obs.reset_route_counts()
    eng_fb = Engine(cfg, params, max_len=16)
    toks_fb = np.asarray(eng_fb.generate(prompts, 8))
    counts = obs.route_counts()
assert counts.get(("ff_tp", "tp_fused"), 0) == 0, counts
np.testing.assert_array_equal(toks_tp, toks_fb)
print("engine decode tokens TP fused == fallback OK", toks_tp[:, :4].tolist())
""")
    assert "OK" in out


def test_tp_paged_pool_shardings():
    """cache_shardings on a paged cache: the page-pool axis (one pool
    shared by every slot) must NOT shard over dp, KV heads shard over
    model when divisible, block tables shard their batch axis over dp."""
    out = _run("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.sharding import MeshRules
from repro.sharding.rules import cache_shardings

mesh = make_mesh((2, 4))
rules = MeshRules(model="model", dp=("data",))
specs = {
    "pages_k": jax.ShapeDtypeStruct((2, 18, 8, 4, 16), jnp.float32),
    "pages_v": jax.ShapeDtypeStruct((2, 18, 8, 4, 16), jnp.float32),
    "block_table": jax.ShapeDtypeStruct((2, 4, 2), jnp.int32),
    "idx": jax.ShapeDtypeStruct((2, 4), jnp.int32),
}
sh = cache_shardings(mesh, specs, rules)
assert sh["pages_k"].spec == P(None, None, None, "model", None), sh["pages_k"].spec
assert sh["pages_v"].spec == P(None, None, None, "model", None), sh["pages_v"].spec
assert sh["block_table"].spec[1] == "data", sh["block_table"].spec
assert sh["idx"].spec == P(), sh["idx"].spec
print("paged pool shardings OK")
""")
    assert "OK" in out


def test_dryrun_entrypoint_smoke_cell():
    """End-to-end dryrun CLI on ONE real cell (512 fake devices) — proves the
    production path works exactly as documented."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen3_0_6b",
         "--shape", "decode_32k", "--mesh", "multi", "--outdir",
         "/tmp/dryrun_pytest"],
        capture_output=True, text=True, timeout=570, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "all requested dry-run cells passed" in r.stdout
    f = "/tmp/dryrun_pytest/multi/qwen3_0_6b__decode_32k__dyad_it_4.json"
    res = json.load(open(f))
    assert res["mesh"] == {"pod": 2, "data": 16, "model": 16}
    assert res["flops_per_device"] > 0
    assert res["bottleneck"] in ("compute", "memory", "collective")
