"""The engine's spans in the profiler's trace: where they land, how they
nest, that their in-memory record keeps the profiler's clock, and how
``bench/engine_spans.py`` charges device-idle time to them."""
import json
import shutil

import jax
import pytest
from jax.profiler import TraceAnnotation

from bench import engine_spans, loop, model, trace, traffic, weights
from bench.tests import tiny  # puts the program on the path

DATA = tiny.DATA
MS = 1_000_000  # ns

# child -> the spans it may sit in directly (docs/observability.md)
NESTING = {
    "boundary": ("engine.step",),
    "plan": ("boundary",),
    "prefill": ("boundary",),
    "prefill_chunk": ("prefill",),
    "sample": ("prefill",),
    "inputs": ("prefill", "prefill_chunk", "megastep"),
    "compute": ("prefill_chunk", "megastep"),
    "dispatch": ("compute",),
    "sync": ("compute",),
    "account": ("prefill_chunk",),
    "megastep": ("engine.step",),
    "fetch": ("megastep",),
    "apply": ("megastep",),
}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The tiny cell's engine (uncompressed, spans recorded in memory)
    serving two requests, admitted inside a profiler trace, driven as the
    harness drives it."""
    from repro.serving import EngineConfig, PagedServingEngine

    conf = json.loads((DATA / "tiny.json").read_text())
    mix = json.loads((DATA / "tiny_closed.json").read_text())
    cfg = model.model_config(conf)
    engine = PagedServingEngine(
        cfg, weights.make_tree(conf["program"], 0),
        EngineConfig(max_slots=2, block_size=16, num_blocks=16,
                     max_blocks_per_slot=8, decode_horizon=4,
                     trace_level="spans"))
    drv = loop.Driver(engine)
    gen = traffic.specs(mix, 5, cfg.vocab_size)

    def serve():
        for c in range(2):
            drv.submit(next(gen), loop.clock(), False, c)
        while drv.live:
            drv.step()

    serve()  # every program compiled before the trace
    engine.tracer.reset()
    out = tmp_path_factory.mktemp("engine_trace")
    jax.profiler.start_trace(str(out))
    try:
        with TraceAnnotation("traced_window"):
            serve()
    finally:
        jax.profiler.stop_trace()
    yield engine, trace.load(str(out)), engine_spans.load(str(out))
    shutil.rmtree(out, ignore_errors=True)


def _parents(spans, child):
    """Innermost span strictly around ``child`` (same clock, one thread)."""
    s, d, name = child
    around = [p for p in spans if p is not child and p[0] <= s
              and s + d <= p[0] + p[1] and (p[1], p[2]) != (d, name)]
    return min(around, key=lambda p: p[1])[2] if around else None


def test_engine_spans_land_in_the_host_plane(traced):
    _, events, engine = traced
    names = {n for *_, n in engine["spans"]}
    assert set(NESTING) <= names | {"engine.step"}
    assert {h[2] for h in events["host"]} >= {"traced_window", "engine.step"}


@pytest.mark.parametrize("child", sorted(NESTING))
def test_engine_spans_nest_as_documented(traced, child):
    _, events, engine = traced
    spans = [h for h in events["host"] if h[2] != "traced_window"] \
        + engine["spans"]
    mine = [sp for sp in spans if sp[2] == child]
    assert mine, child
    for sp in mine:
        assert _parents(spans, sp) in NESTING[child], (child, sp)


def test_every_chunk_in_a_prefill_in_an_engine_step(traced):
    _, events, engine = traced
    spans = [h for h in events["host"] if h[2] != "traced_window"] \
        + engine["spans"]

    def inside(sp, name):
        return any(p[2] == name and p[0] <= sp[0]
                   and sp[0] + sp[1] <= p[0] + p[1] for p in spans)

    chunks = [sp for sp in spans if sp[2] == "prefill_chunk"]
    prefills = [sp for sp in spans if sp[2] == "prefill"]
    assert len(chunks) > len(prefills) == 2  # some prompt spans chunks
    assert all(inside(sp, "prefill") for sp in chunks)
    assert all(inside(sp, "engine.step") for sp in prefills)


@pytest.mark.parametrize("name", ["prefill_chunk", "megastep", "boundary"])
def test_in_memory_spans_keep_the_profilers_clock(traced, name):
    """At level ``spans`` each recorded span starts where its profiler
    event does, to 0.1 ms, once both count from the same origin."""
    eng, _, engine = traced
    mem = sorted(e["ts_us"] for e in eng.tracer.events
                 if e["name"] == name and e["ph"] == "X")
    prof = sorted((engine["start_ns"] + s - eng.tracer.origin_ns) * 1e-3
                  for s, d, n in engine["spans"] if n == name)
    assert mem and len(mem) == len(prof)
    assert max(abs(a - b) for a, b in zip(mem, prof)) < 100.0


# ------------------------------------------------------ synthetic traces
def _events():
    """A window [0, 20) ms: one prefill of two chunks, then a megastep."""
    dev = "/device:TPU:0"
    return {
        "devices": [dev],
        "ops": [  # start, duration, name, kernel, plane
            [4 * MS, 2 * MS, "fusion", "", dev],
            [8 * MS, 2 * MS, "fusion", "", dev],
            [14 * MS, 2 * MS, "fusion", "", dev],
        ],
        "modules": [
            [4 * MS, 2 * MS, "jit_prefill_fn(3)", "prefill", dev],
            [8 * MS, 2 * MS, "jit_prefill_fn(3)", "prefill", dev],
            [14 * MS, 2 * MS, "jit_decode_fn(12)", "decode", dev],
        ],
        "host": [
            [0, 20 * MS, "traced_window"],
            [1 * MS, 18 * MS, "engine.step"],
        ],
    }


def _engine():
    return {"start_ns": 0, "spans": [
                [1 * MS, 11 * MS, "boundary"],
                [1 * MS, 1 * MS, "plan"],
                [2 * MS, 10 * MS, "prefill"],
                [2 * MS, 5 * MS, "prefill_chunk"],
                [2 * MS, 2 * MS, "inputs"],
                [4 * MS, 2 * MS, "compute"],
                [6 * MS, 1 * MS, "account"],
                [7 * MS, 4 * MS, "prefill_chunk"],
                [7 * MS, 1 * MS, "inputs"],
                [8 * MS, 2 * MS, "compute"],
                [10 * MS, 1 * MS, "account"],
                [11 * MS, 1 * MS, "sample"],
                [12 * MS, 7 * MS, "megastep"],
                [12 * MS, 2 * MS, "inputs"],
                [14 * MS, 2 * MS, "compute"],
                [16 * MS, 1 * MS, "fetch"],
                [17 * MS, 2 * MS, "apply"],
            ]}


def test_idle_is_charged_to_the_innermost_span():
    r = engine_spans.reduce(_events(), _engine())
    by = r["idle_s_by_span"]
    # idle: [0,4) [6,8) [10,14) [16,20)
    assert by == pytest.approx({
        "harness": 0.002,           # [0,1) and [19,20)
        "plan": 0.001, "inputs": 0.005, "account": 0.002,
        "sample": 0.001, "fetch": 0.001, "apply": 0.002,
    })
    assert r["idle_s"] == pytest.approx(0.014)
    assert r["idle_leaf_share"] == pytest.approx(12 / 14)
    gaps = dict(r["idle_gaps"])
    assert gaps["account -> jit_prefill_fn"] == pytest.approx(0.001)
    assert gaps["sample -> jit_decode_fn"] == pytest.approx(0.001)
    assert r["span_counts"]["prefill_chunk"] == 2


def test_engine_host_readings():
    r = engine_spans.reduce(_events(), _engine())
    # inside prefill: [2,4) [6,8) [10,12) = 6 ms over 2 chunks
    assert r["prefill_host_ms"] == pytest.approx(3.0)
    # in engine.step outside prefill: [1,2) [12,14) [16,19) = 6 ms, 1 megastep
    assert r["boundary_host_ms"] == pytest.approx(6.0)
    # programs that start inside each chunk: 2 ms each
    assert r["prefill_chunk_ms"] == pytest.approx(2.0)


def test_readings_are_silent_without_the_spans_or_a_device():
    r = engine_spans.reduce(_events(), {"spans": [], "start_ns": 0})
    assert r["prefill_host_ms"] is None and r["boundary_host_ms"] is None
    assert r["prefill_chunk_ms"] is None
    assert r["idle_s_by_span"] == pytest.approx({"engine.step": 0.012,
                                                 "harness": 0.002})
    cpu = dict(_events(), devices=[], ops=[])
    assert engine_spans.reduce(cpu, _engine()) is None


def _fixture_spans(events):
    """Engine spans laid over the recorded trace the way the engine
    nests them: a chunk from each program's end to the next one's end,
    its account and inputs covering the gap between."""
    runs = sorted(m[:2] for m in events["modules"])
    spans = []
    for (s0, d0), (s1, d1) in zip(runs, runs[1:]):
        end0 = s0 + d0
        spans += [[end0, s1 + d1 - end0, "prefill_chunk"],
                  [end0, (s1 - end0) / 2, "account"],
                  [end0 + (s1 - end0) / 2, (s1 - end0) / 2, "inputs"]]
    return {"spans": spans, "start_ns": 0}


@pytest.mark.parametrize("with_spans", [False, True])
def test_idle_charges_sum_to_window_minus_busy(with_spans):
    """On the recorded chip trace, the idle seconds charged to engine and
    harness spans together are the reducer's window minus busy."""
    events = json.loads((DATA / "trace_events.json").read_text())
    engine = _fixture_spans(events) if with_spans else \
        {"spans": [], "start_ns": 0}
    r = engine_spans.reduce(events, engine)
    base = trace.reduce(events)
    assert r["idle_s"] == pytest.approx(base["window_s"] - base["busy_s"],
                                        rel=1e-9)
    if with_spans:
        leaves = sum(v for k, v in r["idle_s_by_span"].items()
                     if k in engine_spans.LEAVES)
        assert r["idle_leaf_share"] == pytest.approx(leaves / r["idle_s"])
        assert r["idle_leaf_share"] > 0.9


def test_trace_reduce_of_the_recorded_trace_is_unchanged():
    """Every key ``trace.reduce`` gives on the recorded trace keeps its
    value: the seven accepted per-layer readers see the same numbers."""
    events = json.loads((DATA / "trace_events.json").read_text())
    want = json.loads((DATA / "trace_reduced.json").read_text())
    got = json.loads(json.dumps(trace.reduce(events)))

    def same(a, b, at):
        assert type(a) is type(b), at
        if isinstance(a, dict):
            assert set(a) == set(b), at
            for k in a:
                same(a[k], b[k], f"{at}.{k}")
        elif isinstance(a, list):
            assert len(a) == len(b), at
            for i, (x, y) in enumerate(zip(a, b)):
                same(x, y, f"{at}[{i}]")
        else:
            assert a == pytest.approx(b, rel=1e-12), at

    same(got, want, "reduce")


def test_device_events_move_to_causal_order():
    """A program the trace shows starting before the host call that
    issued it is moved later by the least shift that puts it after."""
    dev = "/device:TPU:0"
    prog = [int(2.5 * MS), int(3.5 * MS)]  # [2.5, 6) on the device plane
    events = {"devices": [dev],
              "ops": [prog + ["fusion", "", dev]],
              "modules": [prog + ["jit_prefill_fn(3)", "prefill", dev]],
              "host": [[0, 10 * MS, "traced_window"],
                       [1 * MS, 8 * MS, "engine.step"]]}
    engine = {"start_ns": 0, "spans": [
        [3 * MS, 4 * MS, "compute"],
        [3 * MS, MS // 2, "dispatch"],
        [int(3.5 * MS), int(3.5 * MS), "sync"],
    ]}
    lag = engine_spans.device_lag(events, engine)
    assert lag == pytest.approx((0.5 * MS, 1.0 * MS))
    r = engine_spans.reduce(events, engine)
    assert r["shift_ms"] == pytest.approx(0.5)
    # the program on the host's clock: [3, 6.5)
    assert r["idle_s_by_span"] == pytest.approx(
        {"harness": 0.002, "engine.step": 0.004, "sync": 0.0005})
    # already causal: nothing moves
    late = dict(events, ops=[[3 * MS] + events["ops"][0][1:]],
                modules=[[3 * MS] + events["modules"][0][1:]])
    assert engine_spans.reduce(late, engine)["shift_ms"] == 0.0
