"""tp_scaling suite: the fused TP route (shard_map megakernel +
psum_scatter, ``kernels.tp``) vs the einsum fallback
(``REPRO_KERNEL_TP=off`` block-layout ff) at tp = 1 / 2 / 4.

On CPU each tp cell re-execs in a subprocess: the forced host device
count is locked at first jax init, so a (1, tp) ``("data", "model")`` mesh
needs its own process.  On an accelerator the cells run in-process on the
first tp real devices (a chip belongs to one process).  Both routes run
the SAME ``layers.mlp.apply_mlp`` under the SAME activation-sharding
context — the only difference is the dispatch ``_ff_kernel_ready``
picks, verified via the ``ff_tp`` route counters.

On CPU both routes execute interpret-mode Pallas, so (as everywhere in
this repo) absolute wall-clock is NOT a TPU number; each record therefore
also carries the roofline-modeled per-device time ``bound_us`` (constants
from ``launch.roofline``): compute/HBM bound + ICI wire time, where the
fused route deletes the per-shard hidden HBM round-trip (``hidden_mb`` =
0) and halves the wire (reduce-scatter with the re-gather deferred to the
next consumer, vs the fallback's full all-reduce).  ``bound_speedup`` on
the fused cells (fallback bound / fused bound) is the deliverable — it
must exceed 1 at tp > 1.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit
from repro import perf
from repro.launch.roofline import HBM_BW, ICI_BW, PEAK_FLOPS

TOKENS = 512
D, DFF = 256, 1024
N_DYAD = 4
ACT = "relu"
TPS = (1, 2, 4)


def _measure(tp: int) -> dict:
    """Time the fused and fallback routes on a (1, tp) mesh over this
    process's first ``tp`` devices; the ``ff_tp`` route counters of the
    fused run ride along."""
    import jax

    from repro import configs, obs
    from repro.launch.mesh import make_mesh
    from repro.layers import mlp
    from repro.perf.record import time_us
    from repro.sharding import ctx as shard_ctx

    lin = configs.linear_cfg("dyad_it_4_kernel_ffused")
    params = mlp.init_mlp(jax.random.PRNGKey(0), D, DFF, lin, act=ACT)
    x = jax.random.normal(jax.random.PRNGKey(1), (TOKENS, D))
    mesh = make_mesh((1, tp))
    saved = {k: os.environ.pop(k, None)
             for k in ("REPRO_KERNEL_FF", "REPRO_KERNEL_TP")}
    os.environ["REPRO_KERNEL_FF"] = "fused"
    res = {}
    try:
        with shard_ctx.activation_sharding(mesh, dp=("data",),
                                           model="model"):
            obs.reset_route_counts()
            fused = jax.jit(lambda p, x: mlp.apply_mlp(p, x, lin, act=ACT))
            res["fused_us"] = time_us(fused, params, x, iters=3, warmup=1)
            res["routes"] = obs.routes_snapshot()
            os.environ["REPRO_KERNEL_TP"] = "off"
            fb = jax.jit(lambda p, x: mlp.apply_mlp(p, x, lin, act=ACT))
            res["fallback_us"] = time_us(fb, params, x, iters=3, warmup=1)
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None)
            if v is not None:
                os.environ[k] = v
    return res


def _run_cell(tp: int) -> dict:
    """One tp cell.  On an accelerator the cells run in THIS process on
    its real devices: a chip belongs to one process, and this one already
    holds them.  On CPU each cell needs ``tp`` virtual devices, which the
    host device count fixes at backend start, so it runs in a child that
    sets the count before touching a device."""
    import jax

    if jax.default_backend() != "cpu":
        have = len(jax.devices())
        if tp > have:
            raise RuntimeError(f"tp_scaling cell tp{tp} needs {tp} devices; "
                               f"this host has {have}")
        return _measure(tp)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("REPRO_KERNEL_TP", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    script = (
        "import os, json\n"
        "os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={tp}'\n"
        "from benchmarks.bench_tp_scaling import _measure\n"
        f"print('CELL' + json.dumps(_measure({tp})))\n")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=570, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"tp{tp} cell failed:\n{r.stderr[-2000:]}")
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("CELL")][-1]
    return json.loads(line[len("CELL"):])


def _bound_us(tp: int, *, fused: bool) -> float:
    """Roofline-modeled per-device microseconds for one ff call."""
    fp = 4  # fp32 bytes
    flops = 8 * TOKENS * D * DFF / N_DYAD / tp
    w_bytes = 4 * (D * DFF / N_DYAD) * fp / tp
    y_bytes = TOKENS * D * fp / (tp if fused else 1)
    hidden = 0 if fused else 2 * TOKENS * DFF * fp / tp
    hbm = TOKENS * D * fp + y_bytes + w_bytes + hidden
    wire = (tp - 1) / tp * TOKENS * D * fp * (1 if fused else 2)
    return (max(flops / PEAK_FLOPS, hbm / HBM_BW) + wire / ICI_BW) * 1e6


@perf.register("tp_scaling")
def run():
    for tp in TPS:
        cell = _run_cell(tp)
        shape = (TOKENS, D, DFF)
        hidden_mb = round(TOKENS * DFF * 4 / tp / 2 ** 20, 2)
        b_fused = _bound_us(tp, fused=True)
        b_fb = _bound_us(tp, fused=False)
        fused_count = cell["routes"].get("ff_tp:tp_fused", 0)
        fb_count = cell["routes"].get("ff_tp:tp_fallback", 0)
        emit(f"tp_scaling_tp{tp}_fallback", cell["fallback_us"], shape=shape,
             hidden_mb=hidden_mb, bound_us=round(b_fb, 3))
        emit(f"tp_scaling_tp{tp}_fused", cell["fused_us"], shape=shape,
             hidden_mb=0.0, bound_us=round(b_fused, 3),
             bound_speedup=round(b_fb / b_fused, 3),
             wall_vs_fallback=round(cell["fallback_us"] / cell["fused_us"],
                                    3),
             tp_fused_events=fused_count, tp_fallback_events=fb_count)


if __name__ == "__main__":
    run()
