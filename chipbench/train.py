"""The training runner: the program's ``make_train_step`` under ``jax.jit``
with the state donated, as a trainer that logs every few steps runs it.

Set-up builds one compiled step and its state from the seed, and drives it
through its first three steps on the first three batches of the ring, the
same call and feed the window uses.  The numbers the reference checks are
read from that state on the way (the first gradient as AdamW's first
moment holds it after step 1, the parameters' change after step 3), and
the same state goes on into the window.  In the window the host dispatches
steps without a per-step sync, blocking only on the loss of the step
``log_every`` steps back; the window closes on ``block_until_ready`` of
the last step dispatched before the clock ran out.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import common, models, reference


def _optimizer(hp: dict):
    from repro.optim import AdamW, schedule

    return AdamW(lr=schedule.constant(hp["lr"]), b1=hp["b1"], b2=hp["b2"],
                 eps=hp["eps"], weight_decay=hp["weight_decay"],
                 clip_norm=hp["clip_norm"])


def _norms_host(tree_norms) -> dict:
    return {k: float(v) for k, v in tree_norms.items()}


def _first_moment(params, opt):
    """AdamW's first moment in the program's train state."""
    return opt["m"]


class Program:
    """The compiled train step of a cell and what reads its state."""

    def __init__(self, cell: dict):
        import jax

        from repro.train import make_train_step

        self.conf, self.wl = conf, wl = cell["config"], cell["workload"]
        self.spec, hp = wl["traffic"], wl["optimizer"]
        self.model = model = models.of(conf)
        self.m = model.dims(conf)
        self.batches = common.load_module(
            f"{common.BENCH}/traffic/{self.spec['kind']}.py")
        opt = _optimizer(hp)
        cfg = common.program_cfg(conf)

        def init(kd):
            params = model.make(conf, kd)
            return dict(params=params, opt=opt.init(params))

        self.init = jax.jit(init)
        self.step = jax.jit(make_train_step(cfg, opt), donate_argnums=0)
        self.m_norms = jax.jit(lambda st: reference.leaf_norms(jax.tree.map(
            lambda x: x / (1.0 - hp["b1"]), _first_moment(**st))))
        self.dp_norms = jax.jit(lambda st, kd: reference.leaf_norms(
            jax.tree.map(lambda a, b: a - b, st["params"],
                         model.make(conf, kd))))

    def first_steps(self, seed: int):
        """State, ring and the program's readings after three steps."""
        import jax

        kd = common.key_data(seed)
        state = self.init(kd)
        ring = self.batches.ring(self.spec, self.m["vocab"], seed)
        losses, g1 = [], None
        for i in range(3):
            with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                state, mt = self.step(state, ring[i])
            losses.append(float(mt["loss"]))
            if i == 0:
                g1 = _norms_host(self.m_norms(state))
        dp = _norms_host(self.dp_norms(state, kd))
        return state, ring, {"loss": losses, "g1": g1, "dp": dp}


def run(cell: dict, args, devices, t_process: float):
    import jax

    from repro import obs

    from chipbench import tracing

    prog = Program(cell)
    wl, spec, model, m = prog.wl, prog.spec, prog.model, prog.m
    step = prog.step
    state, ring, readings = prog.first_steps(args.seed)
    common.log(f"losses of steps 1-3: {readings['loss']}")

    counter = common.CompileCounter()
    capture = tracing.Capture() if args.trace else None
    trace = None
    log_every, R = wl["log_every"], len(ring)
    tokens_per_step = spec["batch"] * spec["seq"]
    w0 = time.perf_counter()
    setup_s = w0 - t_process
    mid = w0 + 0.5 * args.seconds
    trace_at = (mid - wl["trace_s"] / 2, mid + wl["trace_s"] / 2)
    counter.armed = True
    pending, logged, n, i = [], [], 0, 3
    while True:
        now = time.perf_counter()
        if capture is not None:
            if capture.dir is None and trace is None and now >= trace_at[0]:
                capture.start()
            elif capture.dir is not None and now >= trace_at[1]:
                trace = capture.stop()
                capture.dir = None
        if now >= w0 + args.seconds:
            break
        with jax.profiler.TraceAnnotation("chipbench.dispatch"):
            state, mt = step(state, ring[i % R])
        i, n = i + 1, n + 1
        pending.append(mt["loss"])
        if len(pending) > log_every:
            with jax.profiler.TraceAnnotation("chipbench.block"):
                logged.append(float(pending.pop(0)))
    with jax.profiler.TraceAnnotation("chipbench.block"):
        jax.block_until_ready(state)
    w1 = time.perf_counter()
    counter.armed = False
    if capture is not None and capture.dir is not None:
        trace = capture.stop()
    logged += [float(x) for x in pending]
    routes = obs.routes_snapshot()
    common.log(f"routes {routes}")
    common.log(f"compilations inside the window: {counter.count} "
               f"{counter.names}")
    common.log(f"{n} steps in the window; loss {logged[0] if logged else None}"
               f" -> {logged[-1] if logged else None}")
    peak = common.memory_peak(devices[:1])
    counter.close()
    del state, mt, ring, step, pending, prog
    gc.collect()

    rec = {"w0": w0, "w1": w1, "setup_s": setup_s, "model": model, "m": m,
           "steps": n, "tokens": n * tokens_per_step, "seq": spec["seq"],
           "batch": spec["batch"], "trace": trace}
    problems = []
    if counter.count:
        problems.append(f"{counter.count} compilations in the window")
    try:
        common.check_routes(routes)
    except common.Incorrect as e:
        problems.append(str(e))
    if not all(np.isfinite(logged)):
        problems.append("a loss in the window is not finite")
    ref = reference_readings(cell["config"], wl, args.seed)
    checks, ok = compare(wl["check"], readings, ref)
    return rec, {"attempted": n, "failed": 0, "memory_peak_bytes": peak,
                 "problems": problems, "checks": checks, "ok": ok}


def reference_readings(conf: dict, wl: dict, seed: int, prec: str = "fp32",
                       rows=None) -> dict:
    """The reference's three AdamW steps from the same weights and batches:
    each step's loss, the first clipped gradient's leaf norms, and the
    leaf norms of the parameters' change after three steps.  ``rows``
    keeps only those rows of each batch (a planted fault)."""
    import jax
    import jax.numpy as jnp

    spec, hp = wl["traffic"], wl["optimizer"]
    model = models.of(conf)
    m = model.dims(conf)
    batches_mod = common.load_module(
        f"{common.BENCH}/traffic/{spec['kind']}.py")
    p0 = models.make_jit(conf, seed)
    batches = batches_mod.ring(spec, m["vocab"], seed, count=3)
    blk = wl["check"]["rows"]
    vg = jax.jit(jax.value_and_grad(
        lambda p, t, y: model.nll_sum(m, p, t, y, prec)))
    update = jax.jit(lambda p, s, g, k: reference.adamw_step(p, s, g, k, hp))
    params = p0
    opt_state = {"m": jax.tree.map(jnp.zeros_like, p0),
                 "v": jax.tree.map(jnp.zeros_like, p0)}
    losses, g1 = [], None
    for k in range(3):
        tok = np.asarray(batches[k]["tokens"])
        lab = np.asarray(batches[k]["labels"])
        if rows is not None:
            tok, lab = tok[rows], lab[rows]
        total, grads = 0.0, None
        for r in range(0, tok.shape[0], blk):
            v, g = vg(params, tok[r:r + blk], lab[r:r + blk])
            total += float(v)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        T = tok.size
        grads = jax.tree.map(lambda g: g / T, grads)
        losses.append(total / T)
        params, opt_state, clipped = update(params, opt_state, grads,
                                            jnp.float32(k + 1))
        if k == 0:
            g1 = _norms_host(jax.jit(reference.leaf_norms)(clipped))
    dp = _norms_host(jax.jit(lambda a, b: reference.leaf_norms(
        jax.tree.map(lambda x, y: x - y, a, b)))(params, p0))
    return {"loss": losses, "g1": g1, "dp": dp}


def gaps(prog: dict, ref: dict) -> dict:
    """The three numbers compared.  ``loss_gap``: the worst step's
    |program - reference| / reference.  ``grad_gap`` and ``update_gap``:
    the worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of ``update_gap``."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                       ref["loss"]))
    med_g = common.median(list(ref["g1"].values()))
    grad_gap = max(abs(prog["g1"][k] - v) / max(v, med_g)
                   for k, v in ref["g1"].items())
    moved = [k for k, v in ref["g1"].items() if v >= 1e-3 * med_g]
    med_d = common.median([ref["dp"][k] for k in moved])
    update_gap = max(abs(prog["dp"][k] - ref["dp"][k])
                     / max(ref["dp"][k], med_d) for k in moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap}


def compare(limits: dict, prog: dict, ref: dict):
    g = gaps(prog, ref)
    checks = {k: {"value": v, "limit": limits[k]} for k, v in g.items()}
    return checks, all(v <= limits[k] for k, v in g.items())
