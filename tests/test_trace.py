"""Request-lifecycle tracing + expert-routing telemetry
(repro.serving.trace).

Two contracts under test:

* **Determinism** — the wall-clock-free projection of the event stream
  (``deterministic_jsonl``) must be *bit-identical* across replays of
  the same trace, under horizon ∈ {1, 4, 8} × preemption modes ×
  offload budgets — the event-stream extension of the
  ``ServingMetrics.counters()`` replay contract. And the trace level
  must be invisible to the metrics: serving with tracing off produces
  byte-identical counters (and tokens) to serving at full detail.
* **Coverage** — a pressured trace records the whole lifecycle
  (enqueue → admit → prefill chunks → megasteps with compute/replay
  split → page grow → preempt/swap → release) with per-request flow
  events, exports a schema-valid Chrome trace, and the expert-routing
  telemetry joins observed dispatch frequency against PMQ bit widths.

Engine traces reuse the simulation harness (tests/test_serving_sim.py)
and the offloaded-serving fixtures (tests/test_offload.py).
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from test_offload import ECFG, compress_for_serving, make_requests
from test_offload import TINY_MOE as OFFLOAD_MOE
from test_serving_sim import TINY_DENSE, Trace, _random_trace, run_trace

from repro.core.compressed_moe import BucketMeta
from repro.models.registry import get_model
from repro.serving import (
    EngineConfig,
    ExpertRoutingTelemetry,
    MetricsConsumer,
    PagedServingEngine,
    ServingMetrics,
    SpanTracer,
    validate_chrome_trace,
    validate_events,
)
from repro.serving.trace import NULL_TRACER, TRACE_LEVELS, gini


@pytest.fixture(scope="module")
def dense_model():
    bundle = get_model(TINY_DENSE)
    return TINY_DENSE, bundle.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def compressed_model():
    bundle = get_model(OFFLOAD_MOE)
    params = bundle.init(jax.random.PRNGKey(0))
    return OFFLOAD_MOE, compress_for_serving(OFFLOAD_MOE, params)


# ------------------------------------------------------------ unit: tracer
def test_level_gating():
    """"off" records nothing, "spans" records spans/instants/flows but
    no counters, "full" records everything; bad levels are rejected."""
    def drive(t):
        with t.span("megastep", track="engine", cat="decode"):
            pass
        t.instant("page_grow", track="pool", cat="kv", slot=0)
        t.flow("s", 7, track="queue")
        t.counter("pool", track="engine", page_util=0.5)

    off, spans, full = SpanTracer("off"), SpanTracer("spans"), SpanTracer("full")
    for t in (off, spans, full):
        drive(t)
    assert off.events == [] and not off.enabled and not off.full
    assert [e["ph"] for e in spans.events] == ["X", "i", "s"]
    assert [e["ph"] for e in full.events] == ["X", "i", "s", "C"]
    with pytest.raises(ValueError, match="trace level"):
        SpanTracer("verbose")
    with pytest.raises(ValueError, match="flow phase"):
        full.flow("x", 1, track="queue")
    assert NULL_TRACER.events == []  # the shared default stays inert


def test_span_times_its_body_at_every_level_and_takes_args_at_exit():
    """A span is timed whatever the level (callers read their intervals
    from it); its args may be completed in the body, and it is recorded,
    with those args, only at ``spans`` and above."""
    for level in TRACE_LEVELS:
        t = SpanTracer(level)
        with t.span("compute", track="engine", cat="decode", run=1) as sp:
            sp.args["runs"] = 2
        assert sp.end_ns >= sp.start_ns > 0 and sp.seconds >= 0.0
        if t.enabled:
            (ev,) = t.events
            assert ev["args"] == {"run": 1, "runs": 2}
            assert ev["ts_us"] == pytest.approx(t.us(sp.start_ns), abs=1e-3)
            assert ev["dur_us"] == pytest.approx(sp.seconds * 1e6, abs=1e-3)
        else:
            assert t.events == []


def test_span_left_out_when_its_body_raises_or_it_opts_out():
    t = SpanTracer("spans")
    with pytest.raises(RuntimeError):
        with t.span("sync", track="engine", cat="decode"):
            raise RuntimeError("device lost")
    with t.span("expert_upload", track="experts", cat="offload") as sp:
        sp.record = False
    with t.span("fetch", track="engine", cat="decode"):
        pass
    assert [e["name"] for e in t.events] == ["fetch"]


def test_span_is_a_profiler_annotation_at_level_off(tmp_path):
    """With tracing off, the profiler still sees every span by name."""
    from jax.profiler import ProfileData

    t = SpanTracer("off")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("megastep", track="engine", cat="decode"):
            with t.span("fetch", track="engine", cat="decode"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert {"megastep", "fetch"} <= names
    assert t.events == []


@pytest.mark.parametrize("level", ["off", "spans"])
def test_pmq_pass_spans_and_phase_seconds(level):
    """The PMQ pass is one ``pmq.calibrate``, one ``pmq.eps`` and one
    ``pmq.pack`` per layer, and one ``pmq.plan``, on the ``pmq`` track;
    it sums each phase's seconds and reads the device's peak after each
    layer's pack, at every level (the record only from ``spans``)."""
    from repro.core import pipeline

    cfg = dataclasses.replace(OFFLOAD_MOE, num_shared_experts=0)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    tokens = jax.numpy.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)),
        jax.numpy.int32)
    tracer = SpanTracer(level)
    calib = pipeline.calibrate(params, tokens, cfg, tracer=tracer)
    pipeline.compress_for_serving(params, calib, cfg)
    log = calib.log
    assert log.tracer is tracer
    phases = ("pmq.calibrate", "pmq.eps", "pmq.plan", "pmq.pack")
    assert set(log.seconds) == set(phases)
    assert all(t > 0 for t in log.seconds.values())
    assert len(log.peak_bytes) == cfg.num_layers
    names = [e["name"] for e in tracer.events]
    if level == "off":
        assert names == []
        return
    assert sorted(names) == sorted(
        ["pmq.calibrate", "pmq.plan"] + ["pmq.eps", "pmq.pack"] * cfg.num_layers)
    assert all(e["track"] == "pmq" and e["ph"] == "X" for e in tracer.events)
    for name in ("pmq.eps", "pmq.pack"):
        assert [e["args"]["layer"] for e in tracer.events
                if e["name"] == name] == list(range(cfg.num_layers))
    for name in phases:
        total = sum(e["dur_us"] for e in tracer.events if e["name"] == name)
        assert abs(total * 1e-6 - log.seconds[name]) < 1e-3
    validate_events(tracer.events)


def test_pmq_log_peaks_are_the_latest_packings():
    """Packing one calibration twice (as a bit-width sweep does) keeps one
    peak per layer, of the latest packing, and adds both packings' seconds
    and spans up."""
    from repro.core import pipeline

    cfg = dataclasses.replace(OFFLOAD_MOE, num_shared_experts=0)
    params = get_model(cfg).init(jax.random.PRNGKey(1))
    tokens = jax.numpy.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16)),
        jax.numpy.int32)
    tracer = SpanTracer("spans")
    calib = pipeline.calibrate(params, tokens, cfg, tracer=tracer)
    pipeline.compress_for_serving(params, calib, cfg)
    once = calib.log.seconds["pmq.pack"]
    pipeline.compress_for_serving(params, calib, cfg)
    assert len(calib.log.peak_bytes) == cfg.num_layers
    assert calib.log.seconds["pmq.pack"] > once
    assert sum(e["name"] == "pmq.pack" for e in tracer.events) == 2 * cfg.num_layers


def test_tracer_sits_below_serving_and_the_pmq_pass():
    """The PMQ pass and the span tracer load without the serving package:
    the offline compression pass does not depend on serving."""
    import os
    import subprocess
    import sys

    code = ("import sys, repro.core.pipeline, repro.trace; "
            "print(sorted(m for m in sys.modules if m.startswith('repro.serving')))")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True).stdout
    assert out.strip() == "[]", out
    from repro import trace
    from repro.serving import trace as serving_trace

    assert serving_trace.SpanTracer is trace.SpanTracer
    assert serving_trace.NULL_TRACER is trace.NULL_TRACER


def test_deterministic_projection_strips_wall_clock_only():
    t = SpanTracer("full")
    with t.span("decode", track="slot0", cat="decode", rid=3):
        pass
    t.instant("admit", track="slot0", cat="lifecycle", rid=3)
    assert all("ts_us" in e for e in t.events)
    det = t.deterministic_events()
    assert all("ts_us" not in e and "dur_us" not in e for e in det)
    # everything non-wall-clock survives, parseable line by line
    lines = t.deterministic_jsonl().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0])["args"] == {"rid": 3}
    # reset drops events and keeps the tracer usable
    t.reset()
    assert t.events == []
    t.instant("admit", track="slot0", cat="lifecycle")
    assert t.events[0]["seq"] == 0


def test_event_and_chrome_schema_validation():
    t = SpanTracer("full")
    with t.span("megastep", track="engine", cat="decode", horizon=4):
        t.instant("enqueue", track="queue", cat="lifecycle", rid=1)
    t.flow("s", 1, track="queue")
    t.flow("f", 1, track="slot0")
    assert validate_events(t.events) == 4
    doc = t.chrome_trace(extra={"note": "x"})
    assert validate_chrome_trace(doc) > 4  # metadata events included
    assert doc["otherData"] == {"note": "x"}
    # per-track tid mapping with human-readable thread names
    names = {
        e["args"]["name"] for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert names == {"engine", "queue", "slot0"}
    # violations raise
    with pytest.raises(ValueError, match="seq"):
        validate_events([
            {"ph": "i", "name": "a", "cat": "c", "track": "t", "seq": 1,
             "ts_us": 0.0, "args": {}},
            {"ph": "i", "name": "b", "cat": "c", "track": "t", "seq": 1,
             "ts_us": 0.0, "args": {}},
        ])
    with pytest.raises(ValueError, match="flow"):
        validate_events([
            {"ph": "s", "name": "request", "cat": "request", "track": "q",
             "seq": 0, "ts_us": 0.0},
        ])
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"events": []})


def test_lifecycle_feeds_consumers_at_every_level():
    """Metrics book-keep through the lifecycle stream, so the trace
    level cannot change what the counters record."""
    def drive(t):
        t.lifecycle("admit", track="slot0", rid=1, slot=0, step=0,
                    active_before=0, queue_depth=1, resumed=False)
        t.lifecycle("preempt", track="slot0", rid=1, slot=0, step=2,
                    mode="swap", swap_bytes=64)
        t.lifecycle("swap_in", track="slot0", rid=1, slot=0, nbytes=64)
        t.lifecycle("release", track="slot0", rid=1, slot=0, step=5)

    metrics = {}
    for level in ("off", "spans"):
        m = ServingMetrics()
        drive(SpanTracer(level, consumers=(MetricsConsumer(lambda: m),)))
        metrics[level] = m
    direct = ServingMetrics()
    direct.record_admission(1, 0, 0, 0, 1, resumed=False)
    direct.record_preemption(1, 0, 2, "swap", swap_bytes=64)
    direct.record_swap_in(64)
    direct.record_release(1, 0, 5)
    assert metrics["off"].counters() == direct.counters()
    assert metrics["spans"].counters() == direct.counters()


# -------------------------------------------------------- unit: telemetry
def test_gini():
    assert gini([]) == 0.0
    assert gini([0, 0, 0]) == 0.0
    assert gini([5, 5, 5, 5]) == pytest.approx(0.0)
    # all traffic on one of n experts → (n-1)/n
    assert gini([0, 0, 0, 12]) == pytest.approx(0.75)
    assert gini([1, 2, 3, 4]) == pytest.approx(0.25)


def test_telemetry_histogram_drift_and_gauges():
    tel = ExpertRoutingTelemetry(ema_decay=0.5)
    # uniform first step: matches the uniform EMA seed → zero drift
    g = tel.update(np.array([[2, 2], [3, 3]]))
    assert g["routing_drift"] == pytest.approx(0.0)
    assert g["routing_gini"] == pytest.approx(0.0)
    # hard skew: TV distance from the uniform EMA is 0.5 per layer
    g = tel.update(np.array([[4, 0], [0, 4]]))
    assert g["routing_drift"] == pytest.approx(0.5)
    assert tel.hist.tolist() == [[6, 2], [3, 7]]
    assert tel.steps == 2
    # empty / non-2D inputs are ignored
    assert tel.update(np.zeros((2, 0))) is None
    assert tel.steps == 2
    # a layer with zero traffic this step contributes zero drift
    g = tel.update(np.array([[0, 0], [1, 1]]))
    assert np.isfinite(g["routing_drift"])


def test_bit_misallocation_report_joins_freq_and_bits():
    meta = (BucketMeta(bits=1, start=0, count=1),
            BucketMeta(bits=2, start=1, count=2),
            BucketMeta(bits=3, start=3, count=1))
    tel = ExpertRoutingTelemetry()
    assert tel.bit_misallocation_report(meta) is None  # no traffic yet
    # layer 0: slot 0 (1-bit) hottest — a hot_low_bit candidate; slot 3
    # (3-bit) coldest — a cold_high_bit candidate. layer 1: bits follow
    # frequency perfectly (corr > 0), no candidates.
    tel.update(np.array([[10, 2, 2, 1], [1, 4, 4, 10]]))
    rep = tel.bit_misallocation_report(meta)
    assert rep["num_layers"] == 2 and rep["num_slots"] == 4
    assert rep["bits_per_slot"] == [1, 2, 2, 3]
    l0, l1 = rep["layers"]
    assert l0["hot_low_bit"] == [0] and l0["cold_high_bit"] == [3]
    assert l0["freq_bits_corr"] < 0 < l1["freq_bits_corr"]
    assert l1["hot_low_bit"] == [] and l1["cold_high_bit"] == []
    # per-slot join: counts, frequencies and stable ranks all line up
    assert [e["count"] for e in l0["entries"]] == [10, 2, 2, 1]
    assert l0["entries"][0]["freq_rank"] == 0
    assert sorted(e["freq_rank"] for e in l0["entries"]) == [0, 1, 2, 3]
    assert sum(e["freq"] for e in l1["entries"]) == pytest.approx(1.0)
    # uniform bits ⇒ no correlation and no candidates by construction
    flat = (BucketMeta(bits=2, start=0, count=4),)
    rep = tel.bit_misallocation_report(flat)
    assert rep["mean_freq_bits_corr"] is None
    assert rep["layers"][0]["hot_low_bit"] == []


# ------------------------------------------- engine traces: determinism
@pytest.mark.parametrize("horizon,preempt_mode", [
    (1, "swap"), (1, "recompute"), (4, "swap"),
    (4, "recompute"), (8, "swap"), (8, "recompute"),
])
def test_trace_determinism_under_pressure(dense_model, horizon, preempt_mode):
    """Satellite acceptance: identical replays of the same fuzzed trace
    produce bit-identical wall-clock-free event streams, across
    horizons and preemption modes at the tightest admissible pool."""
    cfg, params = dense_model
    base = _random_trace(np.random.default_rng(5))
    trace = dataclasses.replace(
        base, horizon=horizon, pool_blocks=base.min_pool,
        preempt_mode=preempt_mode,
    )
    streams, counters = [], []
    for _ in range(2):
        engine = run_trace(cfg, params, trace, trace_level="full")
        validate_events(engine.tracer.events)
        streams.append(engine.tracer.deterministic_jsonl())
        counters.append(engine.metrics.counters())
    assert streams[0] == streams[1]
    assert counters[0] == counters[1]


@pytest.mark.parametrize("budget,horizon", [(2, 1), (4, 4)])
def test_trace_determinism_offloaded(compressed_model, budget, horizon):
    """Replays with host-offloaded expert buckets (miss replays, EMA
    prefetch, budget grows) still produce bit-identical projections."""
    cfg, params = compressed_model
    ecfg = dataclasses.replace(
        ECFG, resident_experts=budget, decode_horizon=horizon,
        trace_level="full",
    )
    streams, outs = [], []
    for _ in range(2):
        engine = PagedServingEngine(cfg, params, ecfg)
        outs.append(engine.serve(make_requests(cfg, 3, seed=11)))
        validate_events(engine.tracer.events)
        streams.append(engine.tracer.deterministic_jsonl())
    assert outs[0] == outs[1]
    assert streams[0] == streams[1]


def test_tracing_level_invisible_to_counters_and_outputs(dense_model):
    """Acceptance: tracing disabled records zero events yet serves the
    exact same tokens with the exact same deterministic counters."""
    cfg, params = dense_model
    base = _random_trace(np.random.default_rng(21))
    trace = dataclasses.replace(
        base, pool_blocks=base.min_pool, preempt_mode="swap", horizon=4
    )
    e_off = run_trace(cfg, params, trace, trace_level="off")
    e_full = run_trace(cfg, params, trace, trace_level="full")
    assert e_off.tracer.events == []
    assert len(e_full.tracer.events) > 0
    assert dict(e_off.results) == dict(e_full.results)
    assert e_off.metrics.counters() == e_full.metrics.counters()


# --------------------------------------------- engine traces: coverage
def test_trace_covers_full_lifecycle_with_preemption(dense_model):
    """A deterministically preempting trace records every lifecycle
    event type, stitches each request's journey with flow events, and
    exports a schema-valid Chrome trace with per-track metadata."""
    cfg, params = dense_model
    # pool of 4 pages admits both 2-token-prompt requests (2 pages each,
    # horizon-ahead), then the first growth demand finds zero free pages
    # and must preempt the youngest — guaranteed pressure
    trace = Trace((2, 2), (10, 10), (0, 0), 4, "swap", max_slots=2,
                  horizon=4)
    engine = run_trace(cfg, params, trace, trace_level="full")
    ev = engine.tracer.events
    validate_events(ev)
    names = {e["name"] for e in ev}
    assert {
        "enqueue", "admit", "prefill_chunk", "first_token", "compute",
        "megastep", "decode", "page_grow", "preempt", "kv_swap_out",
        "swap_in", "kv_swap_in", "release", "request", "pool",
        "boundary", "plan", "prefill", "inputs", "dispatch", "sync",
        "account", "sample", "fetch", "apply",
    } <= names
    assert engine.metrics.counters()["preemptions"], "trace must preempt"
    # the preempted request was re-admitted as resumed
    assert any(
        e["name"] == "admit" and e["args"]["resumed"] for e in ev
    )
    # flows: every request starts on the queue ("s"), hops ≥ once ("t"),
    # finishes exactly once ("f")
    for rid in (0, 1):
        phases = [e["ph"] for e in ev if e.get("id") == rid]
        assert phases.count("s") == 1
        assert phases.count("f") == 1
        assert "t" in phases
    # spans carry their extents; instants don't
    for e in ev:
        assert (e["ph"] == "X") == ("dur_us" in e)
    doc = engine.tracer.chrome_trace()
    validate_chrome_trace(doc)
    tracks = {
        e["args"]["name"] for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert {"engine", "queue", "pool", "slot0", "slot1"} <= tracks


def test_offloaded_trace_has_upload_spans_and_replays(compressed_model):
    """Starving the expert budget must surface miss uploads (with kind
    and byte args) and replay spans in the trace."""
    cfg, params = compressed_model
    ecfg = dataclasses.replace(
        ECFG, resident_experts=2, decode_horizon=2, trace_level="full"
    )
    engine = PagedServingEngine(cfg, params, ecfg)
    engine.serve(make_requests(cfg, 3, seed=11))
    ev = engine.tracer.events
    ups = [e for e in ev if e["name"] == "expert_upload"]
    assert ups, "budget 2 of 4 slots must miss at least once"
    assert all(e["args"]["kind"] in ("miss", "prefetch") for e in ups)
    assert any(e["args"]["kind"] == "miss" for e in ups)
    assert all(e["args"]["bytes"] > 0 for e in ups)
    assert any(e["name"] == "replay" for e in ev), (
        "a miss must replay the program"
    )
    # full level records the routing gauges alongside
    assert any(e["name"] == "routing" and e["ph"] == "C" for e in ev)


def test_routing_report_from_served_engine(compressed_model):
    """Acceptance: the bit-misallocation report joins per-(layer, slot)
    observed dispatch frequency with the PMQ bit assignment."""
    cfg, params = compressed_model
    engine = PagedServingEngine(
        cfg, params, dataclasses.replace(ECFG, trace_level="full")
    )
    engine.serve(make_requests(cfg, 2, seed=3))
    rep = engine.routing_report()
    assert rep is not None
    assert rep["num_slots"] == 4
    assert rep["bits_per_slot"] == [1, 2, 2, 3]  # BITS buckets, permuted
    assert rep["steps"] > 0
    for layer in rep["layers"]:
        assert layer["total_dispatch"] > 0
        assert len(layer["entries"]) == 4
        assert sum(e["freq"] for e in layer["entries"]) == pytest.approx(1.0)
        assert sorted(e["freq_rank"] for e in layer["entries"]) == [0, 1, 2, 3]
        for e in layer["entries"]:
            assert e["bits"] == rep["bits_per_slot"][e["slot"]]
    # the report rides inside the Chrome artifact for offline reading
    doc = engine.tracer.chrome_trace(extra={"routing_report": rep})
    validate_chrome_trace(doc)
    assert doc["otherData"]["routing_report"]["num_slots"] == 4


def test_engine_without_tracing_has_no_telemetry(dense_model):
    """Dense models (no PMQ slot counts) and untraced engines keep the
    telemetry off — routing_report degrades to None, never crashes."""
    cfg, params = dense_model
    trace = Trace((4,), (4,), (0,), 4, "swap", max_slots=1)
    engine = run_trace(cfg, params, trace)  # default level: off
    assert engine.routing is None
    assert engine.routing_report() is None
