"""Observability subsystem: tracer round-trip, disabled-path overhead,
metric primitives, engine/trainer telemetry invariants, route-dispatch
counters, and the timeline replay-diff."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.data import SyntheticLM
from repro.models import model
from repro.obs.trace import Tracer
from repro.optim import AdamW, schedule
from repro.perf import timeline
from repro.serve import ContinuousBatchingEngine, Engine
from repro.train import Trainer, init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


@pytest.fixture
def tracer():
    """A fresh process-global tracer, removed again afterwards (the rest of
    the suite must keep running with tracing off)."""
    obs.disable()
    t = obs.enable()
    yield t
    obs.disable()


def _small_model():
    cfg = configs.get("qwen3_0_6b", smoke=True)
    return cfg, model.init_params(cfg, KEY)


# -- tracer ------------------------------------------------------------------


def test_trace_export_roundtrip_and_nesting(tracer, tmp_path):
    """Spans export as valid Chrome-trace JSON; a child span's interval is
    time-contained in its parent's (how Perfetto reconstructs nesting)."""
    with obs.span("outer", cat="test", batch=4):
        time.sleep(0.002)
        with obs.span("inner", cat="test", arr=np.arange(3)) as sp:
            sp.set(result=7)
            time.sleep(0.002)
        time.sleep(0.002)
    obs.instant("marker", cat="test", reason="x")

    path = str(tmp_path / "t.json")
    obs.export(path)
    doc = json.load(open(path))
    assert "traceEvents" in doc and doc["displayTimeUnit"] == "ms"
    ev = {e["name"]: e for e in doc["traceEvents"]}
    outer, inner, mark = ev["outer"], ev["inner"], ev["marker"]
    assert outer["ph"] == "X" and inner["ph"] == "X"
    # containment: inner starts after outer and ends before it
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["dur"] >= inner["dur"]
    assert mark["ph"] == "i" and mark["s"] == "t"
    # args survive; non-scalars are stringified at export, mid-span set()
    # updates land
    assert outer["args"]["batch"] == 4
    assert inner["args"]["result"] == 7
    assert isinstance(inner["args"]["arr"], str)
    json.dumps(doc)  # fully serializable


def test_trace_ring_buffer_bounded():
    t = Tracer(capacity=10)
    for i in range(25):
        t.instant(f"e{i}")
    assert len(t) == 10
    assert t.dropped == 15
    names = [e["name"] for e in t.to_chrome_trace()["traceEvents"]]
    assert names == [f"e{i}" for i in range(15, 25)]  # newest kept


def test_disabled_tracer_is_shared_noop_and_cheap():
    """Tracing off: span() must return the one shared null span (no
    allocation, no clock read) — the instrumented hot paths rely on it."""
    obs.disable()
    assert not obs.enabled()
    s1 = obs.span("a", cat="serve", batch=4)
    s2 = obs.span("b")
    assert s1 is s2
    with s1 as s:
        s.set(anything=1)   # no-op, no error
    t0 = time.perf_counter()
    for _ in range(100_000):
        with obs.span("hot", batch=1):
            pass
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"disabled span too slow: {dt:.3f}s / 100k"


def _profiled_host_spans(tmp_path, body):
    """``repro.*`` events on the host planes of a profiler trace of
    ``body()``, as ``{name: args}``."""
    import glob

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        found[e.name] = dict(e.stats)
    return found


@pytest.mark.parametrize("ring_buffer", [False, True])
def test_span_lands_on_the_profiler_trace(tmp_path, ring_buffer):
    """While a profiler session runs, a span also writes a
    ``TraceAnnotation`` ``repro.<name>`` with its args, the ring buffer on
    or off; afterwards the disabled path is the shared no-op again."""
    obs.disable()
    t = obs.enable() if ring_buffer else None

    def body():
        with obs.span("decode_step", cat="serve", batch=4) as s:
            s.set(tokens=7)
            jnp.ones(3).block_until_ready()

    try:
        found = _profiled_host_spans(tmp_path, body)
    finally:
        obs.disable()
    assert found["repro.decode_step"] == {"batch": 4, "tokens": 7}
    if ring_buffer:
        assert [e["name"] for e in t.to_chrome_trace()["traceEvents"]] == [
            "decode_step"]
    assert obs.span("a") is obs.span("b")


def test_verbose_gate(tracer, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_VERBOSE", "0")
    assert not obs.verbose()          # explicit off wins over enabled tracer
    monkeypatch.setenv("REPRO_OBS_VERBOSE", "1")
    assert obs.verbose()
    monkeypatch.delenv("REPRO_OBS_VERBOSE")
    assert obs.verbose()              # tracer enabled implies verbose
    obs.disable()
    assert not obs.verbose()


# -- metrics -----------------------------------------------------------------


def test_metric_primitives():
    m = obs.MetricsRegistry()
    m.counter("c").inc()
    m.counter("c").inc(4)
    assert m.counter("c").value == 5
    g = m.gauge("g")
    g.set(3)
    g.set(7)
    g.set(2)
    assert g.value == 2 and g.max == 7
    h = m.histogram("h")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100 and h.mean == pytest.approx(50.5)
    assert h.percentile(50) == pytest.approx(50.0, abs=1)
    assert h.percentile(99) == pytest.approx(99.0, abs=1)
    assert h.summary()["p90"] == pytest.approx(90.0, abs=1)


def test_registry_snapshot_json(tmp_path):
    m = obs.MetricsRegistry()
    m.counter("tokens_generated").inc(10)
    m.gauge("queue_depth").set(3)
    m.histogram("ttft_s").observe(0.25)
    path = str(tmp_path / "m.json")
    m.write_json(path)
    snap = json.load(open(path))
    assert snap["counters"]["tokens_generated"] == 10
    assert snap["gauges"]["queue_depth"] == {"value": 3, "max": 3}
    assert snap["histograms"]["ttft_s"]["count"] == 1
    line = obs.format_serving_line(m)
    assert "tok=10" in line and "ttft_ms" in line


# -- engine telemetry invariants ---------------------------------------------


def test_continuous_engine_metric_invariants():
    """Mixed-length run through slot retirement: every finished request has
    a TTFT sample, token counts match outputs, queue/active drain to 0."""
    cfg, p = _small_model()
    cbe = ContinuousBatchingEngine(cfg, p, n_slots=2, max_len=24)
    prompts = jax.random.randint(KEY, (5, 4), 0, cfg.vocab_size)
    uids = [cbe.submit(np.asarray(prompts[i]), 3 + i % 3) for i in range(5)]
    results = cbe.run()
    snap = cbe.metrics_summary()
    c, g, h = snap["counters"], snap["gauges"], snap["histograms"]
    assert c["requests_submitted"] == 5
    assert c["requests_finished"] == 5
    assert h["ttft_s"]["count"] == 5          # every request reached a token
    assert h["ttft_s"]["p50"] > 0
    assert c["tokens_generated"] == sum(len(results[u]) for u in uids)
    assert h["decode_step_s"]["count"] >= 1
    assert g["queue_depth"]["value"] == 0
    assert g["active_slots"]["value"] == 0
    assert g["active_slots"]["max"] == 2      # both slots were busy at peak
    assert "itl_s" in h                       # multi-token requests observed
    assert obs.format_serving_line(cbe.metrics).startswith("reqs=5 ")


def test_paged_engine_page_pool_and_prefix_metrics():
    """Paged + prefix mode: pool occupancy returns to zero after drain (with
    a positive high-water mark) and shared-prefix admissions are counted."""
    cfg, p = _small_model()
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    tails = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
             for n in (3, 5)]
    eng = ContinuousBatchingEngine(cfg, p, n_slots=2, max_len=20,
                                   cache_dtype=jnp.float32, page_size=4,
                                   prefix_cache=True)
    for tail in tails:
        eng.submit(np.concatenate([shared, tail]), 3)
    eng.run()
    snap = eng.metrics_summary()
    c, g = snap["counters"], snap["gauges"]
    assert c["prefix_hits"] == 1
    assert c["prefix_tokens_skipped"] == 8    # two shared 4-token pages
    assert g["page_pool_used"]["max"] > 0
    assert g["page_pool_used"]["value"] == 0  # all pages back after drain
    # first prompt prefills fully; the second's shared 8 tokens are skipped
    assert c["prefill_tokens"] == (len(shared) + len(tails[0])) + len(tails[1])


def test_admission_reject_counted():
    cfg, p = _small_model()
    cbe = ContinuousBatchingEngine(cfg, p, n_slots=1, max_len=8)
    with pytest.raises(ValueError):
        cbe.submit(np.zeros(9, np.int32), 4)
    assert cbe.metrics_summary()["counters"]["admission_rejects"] == 1


def test_continuous_engine_trace_spans(tracer, tmp_path):
    """The engine's step phases all land in the exported trace."""
    cfg, p = _small_model()
    cbe = ContinuousBatchingEngine(cfg, p, n_slots=2, max_len=16)
    prompts = jax.random.randint(KEY, (3, 4), 0, cfg.vocab_size)
    for i in range(3):
        cbe.submit(np.asarray(prompts[i]), 3)
    cbe.run()
    path = str(tmp_path / "serve.json")
    obs.export(path)
    names = {e["name"] for e in json.load(open(path))["traceEvents"]}
    assert {"admit", "prefill", "decode_step", "retire"} <= names


def test_batch_engine_metrics():
    cfg, p = _small_model()
    eng = Engine(cfg, p, max_len=16)
    prompts = jax.random.randint(KEY, (2, 4), 0, cfg.vocab_size)
    eng.generate(prompts, 6)
    snap = eng.metrics.snapshot()
    assert snap["counters"]["tokens_generated"] == 12
    assert snap["counters"]["requests_finished"] == 2
    assert snap["histograms"]["ttft_s"]["count"] == 1
    assert snap["histograms"]["itl_s"]["count"] == 1


# -- trainer telemetry -------------------------------------------------------


def test_trainer_metrics_and_log_line(capsys):
    tiny = configs.get("opt125m", smoke=True)
    opt = AdamW(lr=schedule.constant(1e-3))
    data = SyntheticLM(vocab_size=tiny.vocab_size, seq_len=8, global_batch=4)
    step = jax.jit(make_train_step(tiny, opt))
    lines = []
    t = Trainer(step, init_train_state(tiny, opt, KEY), data, log_every=3,
                log_fn=lambda s: lines.append(s))
    t.run(6)
    snap = t.metrics.snapshot()
    assert snap["histograms"]["step_time_s"]["count"] == 6
    assert snap["counters"]["tokens_trained"] == 6 * 4 * 8
    assert snap["gauges"]["tokens_per_s"]["value"] > 0
    assert snap["gauges"]["loss"]["value"] > 0
    # the periodic log line carries throughput + running-median step time
    assert any("tok/s=" in ln and "step_ms_med=" in ln for ln in lines)


# -- route-dispatch counters --------------------------------------------------


def test_route_counts_and_trace_instants(tracer, tmp_path):
    obs.reset_route_counts()
    obs.route_event("ff", "fused")
    obs.route_event("ff", "fused")
    obs.route_event("attn", "xla")
    assert obs.route_counts() == {("ff", "fused"): 2, ("attn", "xla"): 1}
    path = str(tmp_path / "r.json")
    obs.export(path)
    names = [e["name"] for e in json.load(open(path))["traceEvents"]]
    assert names.count("route:ff=fused") == 2
    obs.reset_route_counts()
    assert obs.route_counts() == {}


def test_engine_records_attn_route():
    """Building a decode step makes the attention routing decision visible."""
    obs.reset_route_counts()
    cfg, p = _small_model()
    eng = Engine(cfg, p, max_len=16)
    prompts = jax.random.randint(KEY, (1, 4), 0, cfg.vocab_size)
    eng.generate(prompts, 2)
    counts = obs.route_counts()
    assert any(op == "attn" for op, _ in counts), counts


# -- timeline replay-diff ------------------------------------------------------


def _trace_doc(spans):
    """Chrome-trace doc from [(name, ts_us, dur_us), ...]."""
    return {"traceEvents": [
        {"name": n, "cat": "t", "ph": "X", "pid": 1, "tid": 1,
         "ts": ts, "dur": dur} for n, ts, dur in spans]}


def test_timeline_localizes_injected_slowdown(tmp_path, capsys):
    base = _trace_doc([("decode_step", i * 100, 80) for i in range(10)]
                      + [("prefill", 0, 500), ("sync", 0, 40)])
    cur = _trace_doc([("decode_step", i * 100, 800) for i in range(10)]
                     + [("prefill", 0, 500), ("sync", 0, 40)])
    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    json.dump(base, open(a, "w"))
    json.dump(cur, open(b, "w"))
    rows = timeline.diff_timelines(timeline.load_timeline(a),
                                   timeline.load_timeline(b))
    assert rows[0].name == "decode_step"          # top row IS the culprit
    assert rows[0].mean_ratio == pytest.approx(10.0)
    bad = timeline.attribute(rows)
    assert [r.name for r in bad] == ["decode_step"]
    # CLI: prints the localization and gates with --fail-on-regress
    rc = timeline.main([a, b, "--fail-on-regress"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "REGRESSION localized to span 'decode_step'" in out


def test_timeline_self_diff_is_clean(tmp_path, capsys):
    doc = _trace_doc([("x", 0, 100), ("y", 100, 300)])
    a = str(tmp_path / "a.json")
    json.dump(doc, open(a, "w"))
    assert timeline.main([a, a, "--fail-on-regress"]) == 0
    assert "no span regressed" in capsys.readouterr().out


def test_timeline_loads_bench_documents(tmp_path):
    """A committed BENCH_*.json diffs against a trace via us_per_call."""
    bench = {"suite": "smoke", "results": [
        {"name": "ff dense", "us_per_call": 120.0},
        {"name": "ff dyad", "us_per_call": 60.0}]}
    p = str(tmp_path / "BENCH_smoke.json")
    json.dump(bench, open(p, "w"))
    stats = timeline.load_timeline(p)
    assert stats["ff dyad"].total_us == 60.0
    assert stats["ff dense"].count == 1
    with pytest.raises(ValueError):
        q = str(tmp_path / "junk.json")
        json.dump({"nope": 1}, open(q, "w"))
        timeline.load_timeline(q)


def test_timeline_json_report(tmp_path):
    a = str(tmp_path / "a.json")
    json.dump(_trace_doc([("x", 0, 100)]), open(a, "w"))
    out = str(tmp_path / "diff.json")
    timeline.main([a, a, "--json", out])
    doc = json.load(open(out))
    assert doc["rows"][0]["name"] == "x"
    assert doc["rows"][0]["regressed"] is False


# -- perf.check --json ---------------------------------------------------------


def test_check_json_report(tmp_path, monkeypatch, capsys):
    """--json writes a machine-readable verdict (no-baseline case: pass,
    per-file report with baseline=None)."""
    from repro.perf import check
    bench = {"suite": "smoke", "results": [
        {"name": "cell", "us_per_call": 10.0}]}
    p = str(tmp_path / "BENCH_smoke.json")
    json.dump(bench, open(p, "w"))
    monkeypatch.chdir(tmp_path)   # not a git repo -> no committed baseline
    out = str(tmp_path / "report.json")
    rc = check.main([p, "--json", out])
    assert rc == 0
    doc = json.load(open(out))
    assert doc["pass"] is True
    assert doc["regressed_cells"] == []
    assert doc["files"][0]["suite"] == "smoke"
    assert doc["files"][0]["baseline"] is None
