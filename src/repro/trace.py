"""Structured span tracer: the repo's one recorder of timed host work.

:class:`SpanTracer` records typed span/instant/counter/flow events on
named tracks; :class:`Span` is one timed stretch of host work. It sits
below both the offline compression pass (:mod:`repro.core.pipeline`
marks its phases with it) and serving (:mod:`repro.serving.trace`
builds the request-lifecycle layer on it and re-exports both classes).

**Two exports, one contract.** Traces export as Chrome trace-event JSON
(:meth:`SpanTracer.chrome_trace` — drop the file on https://ui.perfetto.dev)
and as a JSONL event log (:meth:`SpanTracer.write_jsonl`). Every event
separates *deterministic* fields (seq, name, phase, category, track,
flow id, args — all derived from the trace being served, never from the
clock) from *wall-clock* fields (``ts_us``/``dur_us``). The
wall-clock-free projection (:meth:`SpanTracer.deterministic_events` /
``deterministic_jsonl``) of two replays of the same trace on the same
engine must be **bit-identical** — the event-stream extension of
:meth:`repro.serving.metrics.ServingMetrics.counters`' determinism
contract, asserted in ``tests/test_trace.py``.

**Levels.** ``off`` records nothing (every hook early-returns — tracing
disabled costs < 2% and changes no metric counters), ``spans`` records
lifecycle spans/instants/flows, ``full`` additionally records per-step
counter events (pool/queue gauges, routing drift/Gini) and feeds the
expert-routing telemetry.

**On the profiler's clock.** Every :meth:`SpanTracer.span` is also a
``jax.profiler.TraceAnnotation`` of the same name, entered at every
level: when ``jax.profiler`` is tracing, the engine's spans land in its
host plane beside the device's programs and ops (when it is not, an
annotation costs about a microsecond). Timestamps are read from the
clock the profiler stamps host events with (``CLOCK_REALTIME``,
``time.time_ns``): an event's ``ts_us`` is microseconds since
:attr:`SpanTracer.origin_ns`, and a profiler event at ``start_ns`` of a
trace whose ``Task Environment`` plane gives ``profile_start_time`` P
sits at ``(P + start_ns - origin_ns) / 1e3`` on the same axis.
"""
from __future__ import annotations

import json
import time
from typing import Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["TRACE_LEVELS", "Span", "SpanTracer", "NULL_TRACER"]

TRACE_LEVELS: Tuple[str, ...] = ("off", "spans", "full")
_LEVEL = {name: i for i, name in enumerate(TRACE_LEVELS)}

# wall-clock keys — stripped by the deterministic projection, required
# (where applicable) by the schema; everything else in an event must be
# replay-deterministic
_WALL_KEYS = ("ts_us", "dur_us")


class Span:
    """One timed stretch of host work (:meth:`SpanTracer.span`).

    A profiler annotation of the same name brackets the body at every
    trace level; the extent (``start_ns``/``end_ns``, on the tracer's
    clock) is taken at every level too, so callers time work from the
    span itself. At exit it is recorded through
    :meth:`SpanTracer.complete` unless the body raised. The body may add
    to :attr:`args` (values known only at exit, such as a run count) and
    clear :attr:`record` to leave this one out of the in-memory record
    (the profiler event stays).
    """

    __slots__ = ("tracer", "name", "track", "cat", "args", "record",
                 "start_ns", "end_ns", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, track: str,
                 cat: str, args: Dict):
        self.tracer, self.name, self.track, self.cat = tracer, name, track, cat
        self.args = args
        self.record = True
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end_ns = time.time_ns()
        self._ann.__exit__(exc_type, exc, tb)
        if self.record and exc_type is None:
            self.tracer.complete(
                self.name, track=self.track, cat=self.cat,
                start_us=self.tracer.us(self.start_ns),
                end_us=self.tracer.us(self.end_ns), args=self.args,
            )

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class SpanTracer:
    """Structured span/instant/counter/flow recorder for one engine.

    Events live in :attr:`events` in record order (deterministic, since
    the engine's control flow is deterministic per served trace). A
    span's event is recorded at *exit* — children therefore precede
    their parent in the buffer, which both exports tolerate (Chrome
    nests by ts/dur; the JSONL consumer has ``seq``).
    """

    def __init__(self, level: str = "off", consumers: Iterable = ()):
        if level not in _LEVEL:
            raise ValueError(
                f"trace level {level!r} not in {TRACE_LEVELS}"
            )
        self.level_name = level
        self.level = _LEVEL[level]
        self.consumers = list(consumers)
        self.events: List[Dict] = []
        self.origin_ns = time.time_ns()

    # ------------------------------------------------------------- state
    @property
    def enabled(self) -> bool:
        """Spans/instants/flows are recorded."""
        return self.level >= _LEVEL["spans"]

    @property
    def full(self) -> bool:
        """Counter events + routing telemetry are recorded too."""
        return self.level >= _LEVEL["full"]

    def reset(self) -> None:
        """Drop recorded events and re-anchor the clock (e.g. after a
        warmup pass). Consumers and level are kept."""
        self.events.clear()
        self.origin_ns = time.time_ns()

    def us(self, t_ns: int) -> float:
        """A ``time.time_ns`` reading as microseconds since the origin."""
        return (t_ns - self.origin_ns) * 1e-3

    def now_us(self) -> float:
        """Microseconds since tracer creation/reset, on the profiler's
        host clock."""
        return self.us(time.time_ns())

    def _record(self, ev: Dict) -> None:
        ev["seq"] = len(self.events)
        self.events.append(ev)

    # ------------------------------------------------------------ record
    def complete(self, name: str, *, track: str, cat: str,
                 start_us: float, end_us: Optional[float] = None,
                 args: Optional[Dict] = None) -> None:
        """Record one complete ("X") span from an explicit start time —
        the building block for spans whose args are only known at exit
        (e.g. an upload's row/byte counts)."""
        if not self.enabled:
            return
        end = self.now_us() if end_us is None else end_us
        self._record({
            "ph": "X", "name": name, "cat": cat, "track": track,
            "args": dict(args or {}),
            "ts_us": round(start_us, 3),
            "dur_us": round(max(end - start_us, 0.0), 3),
        })

    def span(self, name: str, *, track: str, cat: str, **args) -> Span:
        """``with tracer.span(...) as sp:`` brackets the body with a
        profiler annotation ``name`` (no annotation args: the args stay
        in the in-memory record) and times it; recorded as one "X" event
        at exit when spans are enabled."""
        return Span(self, name, track, cat, args)

    def instant(self, name: str, *, track: str, cat: str, **args) -> None:
        if not self.enabled:
            return
        self._record({
            "ph": "i", "name": name, "cat": cat, "track": track,
            "args": args, "ts_us": round(self.now_us(), 3),
        })

    def counter(self, name: str, *, track: str, **values) -> None:
        """Gauge samples (Chrome "C" events) — ``full`` level only."""
        if not self.full:
            return
        self._record({
            "ph": "C", "name": name, "cat": "gauge", "track": track,
            "args": {k: float(v) for k, v in values.items()},
            "ts_us": round(self.now_us(), 3),
        })

    def flow(self, phase: str, rid: int, *, track: str) -> None:
        """Per-request flow events: ``"s"`` at enqueue, ``"t"`` at every
        lifecycle hop (admit / preempt / resume), ``"f"`` at release —
        Perfetto draws the arrows that stitch one request's journey
        across queue and slot tracks."""
        if not self.enabled:
            return
        if phase not in ("s", "t", "f"):
            raise ValueError(f"flow phase must be s/t/f, got {phase!r}")
        self._record({
            "ph": phase, "name": "request", "cat": "request",
            "track": track, "id": int(rid),
            "ts_us": round(self.now_us(), 3),
        })

    def lifecycle(self, kind: str, *, track: str, **fields) -> None:
        """One structured lifecycle fact (admit / release / preempt /
        swap_in / enqueue …). Consumers are dispatched **always** —
        :class:`ServingMetrics` book-keeps through this path, so its
        deterministic counters cannot depend on the trace level — while
        the instant event is only recorded when tracing is enabled."""
        for c in self.consumers:
            c.on_lifecycle(kind, fields)
        if self.enabled:
            self.instant(kind, track=track, cat="lifecycle", **fields)

    # ------------------------------------------------------------ export
    def deterministic_events(self) -> List[Dict]:
        """The wall-clock-free projection: identical replays of the same
        trace must produce *bit-identical* output (list and dict order
        included — events are in record order, args in insertion order)."""
        return [
            {k: v for k, v in ev.items() if k not in _WALL_KEYS}
            for ev in self.events
        ]

    def deterministic_jsonl(self) -> str:
        return "\n".join(
            json.dumps(ev, sort_keys=True)
            for ev in self.deterministic_events()
        )

    def write_jsonl(self, path: str, deterministic: bool = False) -> None:
        """One JSON object per line; ``deterministic=True`` writes the
        wall-clock-free projection (the replay-comparable artifact)."""
        events = (
            self.deterministic_events() if deterministic else self.events
        )
        with open(path, "w") as fh:
            for ev in events:
                fh.write(json.dumps(ev, sort_keys=True) + "\n")

    def _track_ids(self) -> Dict[str, int]:
        """track name → Chrome tid, in first-appearance order (which is
        deterministic because event order is)."""
        ids: Dict[str, int] = {}
        for ev in self.events:
            t = ev["track"]
            if t not in ids:
                ids[t] = len(ids) + 1
        return ids

    @staticmethod
    def _sort_index(track: str) -> int:
        """Stable Perfetto track ordering: engine first, then the queue,
        slot tracks by index, pool/experts at the bottom."""
        if track == "engine":
            return 0
        if track == "queue":
            return 1
        if track.startswith("slot"):
            try:
                return 10 + int(track[4:])
            except ValueError:
                return 10
        return {"pool": 900, "experts": 901}.get(track, 500)

    def chrome_trace(self, extra: Optional[Dict] = None) -> Dict:
        """Chrome trace-event JSON (the dict; dump it to a ``.json`` file
        and open in Perfetto / chrome://tracing). ``extra`` lands under
        ``otherData`` — e.g. the bit-misallocation report rides along
        inside the trace artifact."""
        ids = self._track_ids()
        out: List[Dict] = [{
            "ph": "M", "pid": 1, "tid": 0, "name": "process_name",
            "args": {"name": "repro.serving"},
        }]
        for track, tid in ids.items():
            out.append({"ph": "M", "pid": 1, "tid": tid,
                        "name": "thread_name", "args": {"name": track}})
            out.append({"ph": "M", "pid": 1, "tid": tid,
                        "name": "thread_sort_index",
                        "args": {"sort_index": self._sort_index(track)}})
        for ev in self.events:
            base = {
                "ph": ev["ph"], "name": ev["name"], "cat": ev["cat"],
                "pid": 1, "tid": ids[ev["track"]], "ts": ev["ts_us"],
            }
            if ev["ph"] == "X":
                base["dur"] = ev["dur_us"]
                base["args"] = ev["args"]
            elif ev["ph"] == "i":
                base["s"] = "t"
                base["args"] = ev["args"]
            elif ev["ph"] == "C":
                base["args"] = ev["args"]
            else:  # flow s/t/f
                base["id"] = ev["id"]
                if ev["ph"] == "f":
                    base["bp"] = "e"
            out.append(base)
        doc = {"traceEvents": out, "displayTimeUnit": "ms"}
        if extra:
            doc["otherData"] = extra
        return doc

    def write_chrome(self, path: str, extra: Optional[Dict] = None) -> None:
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(extra), fh)


#: Shared disabled tracer — the default for components constructed
#: outside an engine (scheduler/kvcache/offload unit tests); every hook
#: early-returns and no consumer is attached.
NULL_TRACER = SpanTracer("off")
