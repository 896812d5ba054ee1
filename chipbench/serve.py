"""The serving runner: decoder models through the program's
``ContinuousBatchingEngine`` on its paged KV cache, fed by a traffic
module (``traffic/<kind>.py``) from one thread.

Every output token is stamped with the host clock as the ``engine.step()``
that emitted it returns.  A request's first-token time is measured from
when it was due in the schedule (open loop) or submitted (closed loop), so
a stalled step delays every request queued behind it.  After the window
closes no new request arrives; the engine steps on (for at
most ``DRAIN_S``) until every request due in the window has its first
token and some request has finished, the device memory peak is read, the program's state is freed, and
the reference checks a sample of the requests that finished.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import common, models

DRAIN_S = 60.0


class Feeder:
    """Submits the traffic's requests, steps the engine, stamps tokens."""

    def __init__(self, engine):
        import jax

        self.eng, self.jax = engine, jax
        self.trace = None           # the traced part of the window
        self.reqs: dict = {}        # uid -> record
        self.live: dict = {}        # uid -> (engine Request, record)
        self.steps: list = []       # per step: t0, t1, contexts, prefill
        self.late: list = []        # seconds each submission ran late

    def submit(self, item: dict, now: float) -> None:
        due = item.get("due_abs", now)
        with self.jax.profiler.TraceAnnotation("chipbench.submit"):
            uid = self.eng.submit(item["prompt"], item["max_new"])
        self.late.append(now - due)
        req = next(r for r in [*self.eng.queue,
                               *self.eng.slots.active.values()]
                   if r.uid == uid)
        rec = {"uid": uid, "due": due, "prompt": item["prompt"],
               "max_new": item["max_new"], "client": item.get("client"),
               "times": [], "tokens": None, "reason": None}
        self.reqs[uid] = rec
        self.live[uid] = (req, rec)

    def step(self) -> list:
        """One engine step; returns the records that finished in it."""
        eng = self.eng
        pt = eng.metrics.counter("prefill_tokens").value
        pc = eng.stats["prefill_chunks"]
        t0 = time.perf_counter()
        with self.jax.profiler.TraceAnnotation("chipbench.step",
                                               step=len(self.steps)):
            eng.step()
        t1 = time.perf_counter()
        contexts, done = [], []
        for uid, (req, rec) in list(self.live.items()):
            new = len(req.tokens) - len(rec["times"])
            for k in range(len(rec["times"]), len(req.tokens)):
                if k > 0:   # token k came from a decode at context P + k
                    contexts.append(len(rec["prompt"]) + k)
            rec["times"] += [t1] * new
            reason = req.retire_reason
            if reason is not None and reason.value != "preempted":
                rec["tokens"] = list(req.tokens)
                rec["reason"] = reason.value
                done.append(rec)
                del self.live[uid]
        eng.finished.clear()
        self.steps.append({
            "i": len(self.steps), "t0": t0, "t1": t1, "contexts": contexts,
            "prefill_tokens": eng.metrics.counter("prefill_tokens").value - pt,
            "prefill_chunks": eng.stats["prefill_chunks"] - pc})
        return done

    @property
    def busy(self) -> bool:
        return bool(self.eng.queue or self.eng.slots.active)


def drive(feeder: Feeder, traffic, until: float, capture=None,
          trace_at=None) -> None:
    """Feed and step until the clock passes ``until`` (perf_counter)."""
    while True:
        now = time.perf_counter()
        if capture is not None and trace_at is not None:
            if capture.dir is None and now >= trace_at[0]:
                capture.start()
            elif capture.dir is not None and now >= trace_at[1]:
                feeder.trace = capture.stop()
                capture.dir, trace_at = None, None
        if now >= until:
            return
        for item in traffic.due(now):
            feeder.submit(item, now)
        if feeder.busy:
            for rec in feeder.step():
                for item in traffic.finished(rec, time.perf_counter()):
                    feeder.submit(item, time.perf_counter())
        else:
            nxt = traffic.next_due()
            time.sleep(max(0.0, min(0.002, (nxt or until) - now)))


def run(cell: dict, args, devices, t_process: float):
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.serve import ContinuousBatchingEngine

    conf, wl = cell["config"], cell["workload"]
    model = models.of(conf)
    m = model.dims(conf)
    cfg = common.program_cfg(conf)
    params = models.make_jit(conf, args.seed)
    eng = ContinuousBatchingEngine(
        cfg, params, n_slots=wl["slots"], max_len=wl["max_len"],
        page_size=wl["page_size"], prefill_chunk=wl["prefill_chunk"],
        cache_dtype=jnp.bfloat16, seed=0, log_fn=common.log)
    mod = common.load_module(f"{common.BENCH}/traffic/{wl['traffic']['kind']}.py")
    # warm every program the traffic can reach: one request per prefill
    # chunk length it can produce, then the traffic itself for warm_s
    warm_rng = common.rng(args.seed, 99)
    for n in wl["warm_chunks"]:
        eng.submit(warm_rng.integers(0, m["vocab"], n, dtype=np.int32), 2)
    eng.run()
    eng.finished.clear()
    t_warm = time.perf_counter()
    traffic = mod.Traffic(wl["traffic"], args.seed, args.seconds,
                          m["vocab"], t_warm)
    feeder = Feeder(eng)
    for item in traffic.initial(t_warm):
        feeder.submit(item, t_warm)
    drive(feeder, traffic, t_warm + traffic.warm_s)

    counter = common.CompileCounter()
    w0 = time.perf_counter()
    setup_s = w0 - t_process
    w1 = w0 + args.seconds
    traffic.window = (w0, w1)
    capture = trace_at = None
    if args.trace:
        from chipbench import tracing

        capture = tracing.Capture()
        mid = w0 + 0.5 * args.seconds
        trace_at = (mid - wl["trace_s"] / 2, mid + wl["trace_s"] / 2)
    h0 = eng.metrics.histogram("decode_step_s").count
    n0 = len(feeder.steps)
    q0 = len(eng.queue)
    counter.armed = True
    drive(feeder, traffic, w1, capture, trace_at)
    counter.armed = False
    if capture is not None and capture.dir is not None:
        feeder.trace = capture.stop()
    h1 = eng.metrics.histogram("decode_step_s").count
    n1 = len(feeder.steps)
    q1 = len(eng.queue)
    common.log(f"queue {q0} -> {q1} requests waiting for a slot over the "
               "window")
    # after the window no request arrives; step on until every request due
    # in it has its first token (for at most DRAIN_S)
    traffic.closed = True
    t_drain = time.perf_counter()
    waiting = [r for r in feeder.reqs.values()
               if w0 <= r["due"] <= w1 and not r["times"]]

    def pending() -> bool:
        # a first token owed, or no request finished yet to check
        return (any(not r["times"] for r in waiting)
                or not any(r["tokens"] for r in feeder.reqs.values()))

    while (pending() and feeder.busy
           and time.perf_counter() - t_drain < DRAIN_S):
        feeder.step()
    t_end = time.perf_counter()
    drain_s = t_end - t_drain
    routes = obs.routes_snapshot()
    common.log(f"routes {routes}")
    common.log(f"compilations inside the window: {counter.count} "
               f"{counter.names}")
    late = feeder.late or [0.0]
    common.log(f"generator lateness: max {max(late)!r} s, p95 "
               f"{common.percentile(late, 95)!r} s over {len(late)} "
               "submissions")
    common.log(f"drain {drain_s!r} s; {len(feeder.live)} requests still "
               "running are left unfinished")
    peak = common.memory_peak(devices[:1])
    decode_ms = [1e3 * s for s in
                 eng.metrics.histogram("decode_step_s").samples[h0:h1]]
    counter.close()
    del eng, params, feeder.eng
    gc.collect()

    rec = {"w0": w0, "w1": w1, "t_end": t_end, "setup_s": setup_s,
           "model": model, "m": m,
           "reqs": feeder.reqs, "steps": feeder.steps[n0:n1],
           "decode_ms": decode_ms, "trace": feeder.trace,
           "open_loop": traffic.open_loop, "queue": (q0, q1)}
    window_reqs = [r for r in feeder.reqs.values()
                   if w0 <= r["due"] <= w1]
    # a request fails when it never produced a token, or ended otherwise
    # than at its token budget or EOS; one still running is not failed
    failed = [r for r in window_reqs if not r["times"]
              or r["reason"] not in (None, "max_new", "eos")]
    problems = []
    if counter.count:
        problems.append(f"{counter.count} compilations in the window")
    try:
        common.check_routes(routes)
    except common.Incorrect as e:
        problems.append(str(e))
    checks, ok = check(conf, wl, args.seed, feeder.reqs, m)
    return rec, {"attempted": len(window_reqs), "failed": len(failed),
                 "memory_peak_bytes": peak, "problems": problems,
                 "checks": checks, "ok": ok}


def sample(reqs: dict, seed: int, budget: int, most: int) -> list:
    """Finished requests to check: the one with the most served tokens,
    then others drawn from the seed until ``budget`` served tokens or
    ``most`` requests."""
    done = [r for r in reqs.values() if r["tokens"]]
    done.sort(key=lambda r: (-len(r["tokens"]), r["uid"]))
    if not done:
        return []
    out, rest = [done[0]], done[1:]
    order = common.rng(seed, 7).permutation(len(rest))
    for i in order:
        if sum(len(r["tokens"]) for r in out) >= budget or len(out) >= most:
            break
        out.append(rest[i])
    return out


def token_gaps(conf: dict, m: dict, seed: int, recs: list, seq_len: int,
               served_len: int, control: bool = False) -> list:
    """For every served token of ``recs``: how far its logit in the float32
    reference lies below the reference's best.  With ``control`` the
    token judged is the one the float8 reference puts first instead."""
    import jax
    import jax.numpy as jnp

    model = models.of(conf)
    params = models.make_jit(conf, seed)

    def gaps(p, seq, where, served):
        z = model.logits_at(m, p, seq, where, "fp32")
        if control:
            served = jnp.argmax(model.logits_at(m, p, seq, where, "fp8"), -1)
        pick = jnp.take_along_axis(z, served[:, None], -1)[:, 0]
        return jnp.max(z, -1) - pick

    fn = jax.jit(gaps)
    out = []
    for r in recs:
        toks = np.asarray(r["tokens"], np.int32)
        seq = np.concatenate([r["prompt"], toks[:-1]]).astype(np.int32)
        # one shape for every request: the tail padding cannot reach the
        # served positions through causal attention
        padded = np.zeros(seq_len, np.int32)
        padded[:len(seq)] = seq
        P = len(r["prompt"])
        W = served_len
        where = np.full(W, P - 1, np.int32)
        where[:len(toks)] = np.arange(P - 1, P - 1 + len(toks))
        served = np.zeros(W, np.int32)
        served[:len(toks)] = toks
        g = fn(params, padded, where, served)
        out += [float(x) for x in np.asarray(g)[:len(toks)]]
    return out


def check(conf, wl, seed, reqs, m):
    """The widest gap by which a served token's logit lies below the
    float32 reference's best, over a sample of finished requests."""
    ck = wl["check"]
    recs = sample(reqs, seed, ck["tokens"], ck["requests"])
    if not recs:
        return {"token_gap": {"value": None, "limit": ck["token_gap"]}}, False
    g = token_gaps(conf, m, seed, recs, wl["max_len"], ck["served"])
    value = max(g)
    common.log(f"checked {len(recs)} requests, {len(g)} served tokens")
    return ({"token_gap": {"value": value, "limit": ck["token_gap"]}},
            value <= ck["token_gap"])


def flops_in_window(rec: dict) -> float:
    """Model FLOPs of the work the window completed: every decode token
    emitted in it at its context, and the whole prefill of every request
    whose first token came in it."""
    model, m, w0, w1 = rec["model"], rec["m"], rec["w0"], rec["w1"]
    total = 0.0
    for r in rec["reqs"].values():
        P = len(r["prompt"])
        for k, t in enumerate(r["times"]):
            if not w0 <= t <= w1:
                continue
            if k == 0:
                total += model.serve_step_flops(
                    m, {"chunks": [(0, P, True)], "contexts": []})
            else:
                total += model.serve_step_flops(
                    m, {"chunks": [], "contexts": [P + k]})
    return total
