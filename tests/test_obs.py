"""Observability layer (DESIGN.md §12): metrics registry unit tests,
span-tracer unit tests (including the Chrome-trace CLI round trip), the
EnergyMeter -> span joule-attribution contract, and the end-to-end
serve-loop integration test: a scripted 3-request continuous paged run
with prefix sharing must produce TTFT/TPOT/e2e histograms, SLO
attainment counts, a tuner drift histogram, live-share attn keyspaces
(``attn=paged-p8-sX.XX``), and per-span joules that sum to the energy
report's totals.
"""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.configs import get_smoke_config
from repro.models import init_model
from repro.obs import MetricsRegistry, Tracer, validate_trace
from repro.obs.metrics import (Counter, Gauge, Histogram, default_registry,
                               null_registry)
from repro.obs.trace import (attribute_energy, load_events,
                             main as trace_main)
from repro.power import EnergyMeter, EnergyReport
from repro.serve import ServeConfig


# ---------------------------------------------------------------- metrics --

def test_histogram_bucket_edges():
    """Bucket e is [2**e, 2**(e+1)); non-positive lands in zero."""
    assert Histogram.bucket_of(1.0) == 0
    assert Histogram.bucket_of(1.999) == 0
    assert Histogram.bucket_of(2.0) == 1
    assert Histogram.bucket_of(0.5) == -1
    assert Histogram.bucket_of(0.0) is None
    assert Histogram.bucket_of(-3.0) is None
    assert Histogram.bucket_bounds(3) == (8.0, 16.0)
    rng = np.random.default_rng(0)
    for v in rng.uniform(1e-6, 1e6, size=200):
        lo, hi = Histogram.bucket_bounds(Histogram.bucket_of(v))
        assert lo <= v < hi


def test_histogram_observe_and_quantiles():
    h = Histogram("h")
    vals = [0.7, 1.5, 3.0, 3.5, 12.0, 100.0]
    for v in vals:
        h.observe(v)
    d = h.to_dict()
    assert d["count"] == len(vals)
    assert d["sum"] == pytest.approx(sum(vals))
    assert d["min"] == 0.7 and d["max"] == 100.0
    # quantiles clamp to recorded extremes, interpolate within 2x inside
    assert h.quantile(1.0) == 100.0
    assert h.quantile(0.0) >= 0.7
    p50 = h.quantile(0.5)
    assert 1.5 <= p50 < 8.0            # lands in the [2,4) bucket's reach
    # zero bucket: non-positive observations quantile to 0.0
    z = Histogram("z")
    z.observe(0.0)
    z.observe(-3.0)
    z.observe(5.0)
    assert z.zero == 2
    assert z.quantile(0.5) == 0.0
    assert z.quantile(1.0) == 5.0


def test_histogram_merge_equals_union():
    rng = np.random.default_rng(1)
    a_vals = rng.uniform(0.01, 1e4, size=57).tolist() + [0.0]
    b_vals = rng.uniform(0.01, 1e4, size=43).tolist()
    union = Histogram("u")
    for v in a_vals + b_vals:
        union.observe(v)
    a, b = Histogram("a"), Histogram("b")
    for v in a_vals:
        a.observe(v)
    for v in b_vals:
        b.observe(v)
    merged = a.merge(b).to_dict()
    expect = union.to_dict()
    # summation order differs between the merged and union paths
    assert merged.pop("sum") == pytest.approx(expect.pop("sum"))
    assert merged == expect


def test_counter_and_gauge():
    c = Counter("c")
    c.inc()
    c.inc(5)
    assert c.to_dict() == {"type": "counter", "value": 6}
    g = Gauge("g")
    g.set(3.0)
    g.set(1.0)
    g.set(2.0)
    assert g.to_dict() == {"type": "gauge", "value": 2.0,
                           "min": 1.0, "max": 3.0}


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.histogram("x")


def test_snapshot_deterministic_across_insertion_order():
    def feed(reg, order):
        for name in order:
            if name == "a.count":
                reg.counter(name).inc(3)
            elif name == "b.gauge":
                reg.gauge(name).set(7.0)
            else:
                reg.histogram(name).observe(4.2)

    r1, r2 = MetricsRegistry(), MetricsRegistry()
    feed(r1, ["a.count", "b.gauge", "c.hist_ms"])
    feed(r2, ["c.hist_ms", "a.count", "b.gauge"])
    assert json.dumps(r1.snapshot(), sort_keys=True) == \
        json.dumps(r2.snapshot(), sort_keys=True)
    snap = r1.snapshot()
    assert snap["kind"] == "repro-obs-metrics"
    assert snap["schema_version"] >= 1


def test_disabled_registry_is_metric_free():
    reg = MetricsRegistry(enabled=False)
    c, g, h = reg.counter("a"), reg.gauge("b"), reg.histogram("c")
    assert c is g is h                  # one shared null instrument
    c.inc(10)
    g.set(1.0)
    h.observe(2.0)
    assert reg.snapshot()["series"] == {}
    assert null_registry().snapshot()["series"] == {}


def test_registry_write_round_trip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n").inc(2)
    reg.histogram("h_ms").observe(1.5)
    p = tmp_path / "metrics.json"
    reg.write(str(p))
    assert json.loads(p.read_text()) == \
        json.loads(json.dumps(reg.snapshot()))


# ------------------------------------------------------------------ trace --

def test_span_nesting_depth_and_containment():
    tr = Tracer(enabled=True)
    with tr.span("outer", phase="o") as args:
        args["extra"] = 1
        with tr.span("inner"):
            time.sleep(0.001)
    inner, outer = tr.events          # exit order: inner completes first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert outer["depth"] == 0 and inner["depth"] == 1
    assert outer["args"] == {"phase": "o", "extra": 1}
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert validate_trace(tr.to_chrome()) == []


def test_async_spans_and_validation_errors():
    tr = Tracer(enabled=True)
    tr.begin_async("request", 7, prompt_tokens=4)
    tr.instant("preempt", req=7)
    tr.end_async("request", 7, tokens=6)
    doc = tr.to_chrome()
    assert validate_trace(doc) == []
    assert doc["traceEvents"][0]["id"] == "7"     # ids stringified

    bad_ph = {"traceEvents": [{"ph": "Q", "name": "x", "ts": 0.0}]}
    assert any(".ph" in e for e in validate_trace(bad_ph))
    no_dur = {"traceEvents": [{"ph": "X", "name": "x", "ts": 0.0}]}
    assert any(".dur" in e for e in validate_trace(no_dur))
    unclosed = Tracer(enabled=True)
    unclosed.begin_async("request", 1)
    assert any("unclosed" in e
               for e in validate_trace(unclosed.to_chrome()))
    orphan = Tracer(enabled=True)
    orphan.end_async("request", 1)
    assert any("without begin" in e
               for e in validate_trace(orphan.to_chrome()))
    with pytest.raises(ValueError, match="invalid trace"):
        validate_trace(bad_ph, strict=True)


def test_trace_cli_round_trip(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("work"):
        pass
    tr.begin_async("request", 0)
    tr.end_async("request", 0)
    src = tmp_path / "trace.jsonl"
    out = tmp_path / "trace.json"
    tr.write_jsonl(str(src))
    assert trace_main([str(src), "-o", str(out), "--validate"]) == 0
    doc = json.loads(out.read_text())
    assert validate_trace(doc) == []
    assert doc["traceEvents"] == tr.to_chrome()["traceEvents"]
    # idempotent load: the converted document reads back unchanged
    assert load_events(str(out))["traceEvents"] == doc["traceEvents"]
    # a corrupt trace exits non-zero
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"ph": "X", "name": "x", "ts": -1}\n')
    assert trace_main([str(bad), "--validate"]) == 1


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("a"), tr.span("b"):
        pass
    tr.begin_async("request", 0)
    tr.end_async("request", 0)
    tr.instant("i")
    assert tr.events == []


def _host_events(profile_dir) -> dict:
    """{name: [(line, start_ns, end_ns)]} of a profile's host planes."""
    from jax.profiler import ProfileData

    out: dict = {}
    for path in Path(profile_dir).rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        s = float(ev.start_ns)
                        out.setdefault(ev.name, []).append(
                            (line.name, s, s + float(ev.duration_ns)))
    return out


def test_spans_land_in_the_profilers_host_plane(tmp_path):
    """An enabled tracer's span is a host event of a running profiler
    trace, nested in the annotation it was opened under, on the same
    thread; a disabled tracer's span adds nothing."""
    on, off = Tracer(enabled=True), Tracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.outer"):
            with on.span("test.span", step=1):
                time.sleep(0.001)
            with off.span("test.disabled_span"):
                pass
    finally:
        jax.profiler.stop_trace()
    host = _host_events(tmp_path)
    (outer,), (span,) = host["test.outer"], host["test.span"]
    assert span[0] == outer[0]
    assert outer[1] <= span[1] < span[2] <= outer[2]
    assert span[2] - span[1] >= 1e6
    assert "test.disabled_span" not in host
    assert [ev["name"] for ev in on.events] == ["test.span"]
    assert off.events == []


def test_energy_attribution_lands_on_innermost_span():
    """Top-level meter readings attach joules to the enclosing span;
    nested readings ride inside their parent (no double count), so span
    joules equal the reporter's totals exactly."""
    assert attribute_energy(1.0) is False          # no open span: no-op
    rep = EnergyReport(backend="test")
    tr = Tracer(enabled=True)
    with tr.span("phase") as args:
        with EnergyMeter("outer", reporter=rep), \
                EnergyMeter("inner", reporter=rep):
            np.dot(np.ones((64, 64)), np.ones((64, 64)))
        with EnergyMeter("second", reporter=rep):
            pass
    assert args["joules"] == pytest.approx(rep.totals()["joules"])
    assert args["metered_s"] > 0.0
    ev = tr.events[-1]
    assert ev["name"] == "phase" and ev["args"]["joules"] == args["joules"]


# ------------------------------------------------- serve-loop integration --

@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen3_1_7b")


@pytest.fixture(scope="module")
def params(cfg):
    return init_model(cfg, jax.random.PRNGKey(0))


def test_serve_loop_observability(cfg, params, tmp_path, monkeypatch):
    """The ISSUE's acceptance run: scripted 3-request continuous paged
    serve with prefix sharing.  req0 is short; req1 and req2 share a
    prompt, with req2 queued behind a full pool so it clones req1's live
    pages on admission -- driving ``min(share) < 1``, the live-share
    attn re-resolution (``attn=paged-p8-sX.XX`` keyspace) and a COW
    fork on req2's first decode write."""
    from repro.launch.serve import ServeLoop

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_TUNE_MEASURE", "1")
    reg = default_registry()
    reg.reset()
    tracer = Tracer(enabled=True)
    sc = ServeConfig(slots=2, cache_len=64, layout="paged", page_size=8,
                     mode="continuous", prefill_budget=8,
                     objective="energy", latency_slo_ms=1e6)
    loop = ServeLoop(cfg, params, sc, metrics=reg, tracer=tracer)
    short = list(range(40, 48))            # 1 page
    shared = list(range(60, 76))           # 2 pages
    loop.submit(0, short)
    loop.submit(1, shared)
    loop.submit(2, list(shared))
    out = loop.run(max_new=4)
    assert all(len(out[r]) > 0 for r in (0, 1, 2))

    # live-share feedback (satellite 2): min share dropped below 1 and
    # the attn winner was re-resolved under the share-tagged keyspace
    assert loop._min_share < 1.0
    assert loop._share_tag is not None
    cache_keys = json.loads(
        (tmp_path / "tune.json").read_text())["entries"].keys()
    assert any("attn=paged-p8-s0." in k for k in cache_keys), \
        sorted(cache_keys)

    snap = reg.snapshot()["series"]
    for name in ("serve.ttft_ms", "serve.tpot_ms", "serve.e2e_ms",
                 "serve.step_ms", "serve.prefill_tokens",
                 "serve.queue.depth", "serve.pool.occupancy",
                 "serve.prefix.hit_ratio", "serve.attn.min_share",
                 "serve.requests.submitted", "serve.requests.finished",
                 "serve.preemptions", "serve.cow_forks",
                 "serve.pages.scrubbed", "serve.pages.revived",
                 "serve.slo.met", "serve.slo.violations"):
        assert name in snap, f"missing series {name}"
    assert snap["serve.requests.submitted"]["value"] == 3
    assert snap["serve.requests.finished"]["value"] == 3
    assert snap["serve.ttft_ms"]["count"] == 3
    assert snap["serve.tpot_ms"]["count"] == 3
    assert snap["serve.e2e_ms"]["count"] == 3
    assert snap["serve.cow_forks"]["value"] >= 1
    assert snap["serve.attn.min_share"]["min"] == \
        pytest.approx(loop._min_share)
    # generous SLO: all requests meet it
    assert snap["serve.slo.met"]["value"] == 3
    assert snap["serve.slo.violations"]["value"] == 0
    # tuner telemetry landed in the same registry
    assert snap["tune.drift.time_ratio"]["count"] >= 1
    assert any(k.startswith("tune.cache.miss.attn") for k in snap)

    # latency summary: exact percentiles + SLO attainment
    lat = loop.latency_summary()
    for series in ("ttft_ms", "tpot_ms", "e2e_ms"):
        for q in ("p50", "p95", "p99"):
            assert lat[series][q] > 0.0
    assert lat["slo"]["met"] == 3 and lat["slo"]["attainment"] == 1.0
    assert loop.energy.meta["latency"]["slo"]["met"] == 3

    # trace: schema-valid, per-request nested lifecycle spans
    doc = tracer.to_chrome()
    assert validate_trace(doc) == []
    for rid in ("0", "1", "2"):
        evs = sorted((e for e in doc["traceEvents"]
                      if e.get("id") == rid), key=lambda e: e["ts"])
        names = [(e["name"], e["ph"]) for e in evs]
        assert names[0] == ("request", "b")
        assert names[1] == ("request.queued", "b")
        assert names[-1] == ("request", "e")
        order = [n for n, ph in names if ph == "b"]
        assert order.index("request.queued") < \
            order.index("request.prefill") < order.index("request.decode")

    # energy attribution (satellite 1 + tentpole): span joules and the
    # per-request token-weighted split both sum to the meter totals
    total_j = loop.energy.totals()["joules"]
    span_j = sum(e["args"].get("joules", 0.0)
                 for e in doc["traceEvents"] if e["ph"] == "X")
    assert span_j == pytest.approx(total_j, rel=0.01)
    assert sum(loop.request_joules.values()) == \
        pytest.approx(total_j, rel=0.01)
    assert all(j > 0.0 for j in loop.request_joules.values())

    # round-trip the artifacts the CLI would write
    src = tmp_path / "serve-trace.jsonl"
    tracer.write_jsonl(str(src))
    assert trace_main([str(src), "--validate"]) == 0


def test_serve_loop_slo_violations_counted(cfg, params):
    """A microsecond SLO target makes every request a violation, in
    both the counters and the latency summary."""
    from repro.launch.serve import ServeLoop

    reg = MetricsRegistry()
    sc = ServeConfig(slots=2, cache_len=64, layout="paged", page_size=8,
                     mode="continuous", prefill_budget=8,
                     latency_slo_ms=1e-3)
    loop = ServeLoop(cfg, params, sc, metrics=reg,
                     tracer=Tracer(enabled=False))
    for r in range(3):
        loop.submit(r, list(range(10 + 4 * r, 18 + 4 * r)))
    loop.run(max_new=2)
    snap = reg.snapshot()["series"]
    assert snap["serve.slo.violations"]["value"] == 3
    assert snap["serve.slo.met"]["value"] == 0
    lat = loop.latency_summary()
    assert lat["slo"]["violations"] == 3 and lat["slo"]["attainment"] == 0.0


def test_serve_loop_obs_disabled_is_metric_free(cfg, params):
    """ServeConfig(obs=False) binds the null registry + disabled tracer:
    same outputs, no recorded series, no trace events."""
    from repro.launch.serve import ServeLoop

    sc = ServeConfig(slots=1, cache_len=32, layout="paged", page_size=8,
                     mode="continuous", prefill_budget=8, obs=False)
    loop = ServeLoop(cfg, params, sc)
    loop.submit(0, [5, 6, 7, 8])
    out = loop.run(max_new=2)
    assert len(out[0]) == 6
    assert loop.metrics.snapshot()["series"] == {}
    assert loop.tracer.events == []
    # lifecycle accounting still works without instruments
    assert loop.latency_summary()["ttft_ms"]["count"] == 1


@pytest.mark.slow
def test_obs_overhead_under_gate(monkeypatch):
    """The CI contract: full obs layer costs < 5% per serve step
    (measured on one loop instance, paired-median estimator)."""
    monkeypatch.setenv("REPRO_BENCH_SMOKE", "1")
    root = Path(__file__).resolve().parents[1]
    monkeypatch.syspath_prepend(str(root))
    from benchmarks.bench_obs_overhead import _serve_step_us

    on, off, diff = _serve_step_us(slots=2, cache_len=64, max_new=2,
                                   reps=60)
    assert off > 0.0
    assert diff / off < 0.05, (on, off, diff)
