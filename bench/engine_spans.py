"""The serving engine's own spans in a profiler trace.

Since every ``SpanTracer.span`` of the engine is also a
``jax.profiler.TraceAnnotation`` of the same name, a traced window holds
the host work of ``engine.step`` (planning, building inputs, the host
sync, accounting, sampling, applying tokens) on the same clock as the
device's programs. This module reads what ``bench/trace.py``'s ``load``
leaves out, the engine's host spans, and charges each device-idle
nanosecond to the innermost span it falls in:

- :func:`load` keeps the host events named in :data:`ENGINE_SPANS`;
- :func:`reduce` takes them and ``trace.load``'s events and gives idle
  seconds by innermost span, span counts, and three readings: host
  milliseconds inside ``prefill`` per prefill chunk, host milliseconds at
  the boundary per megastep, and device milliseconds per prefill chunk.

The profiler places the device's events on the host's clock only to
about a millisecond (on a TPU v5 lite a program can appear to start
before the host call that issued it); :func:`device_lag` bounds that
from the engine's own spans, and :func:`reduce` corrects by it.
"""
from __future__ import annotations

import bisect
import collections
import math
import re
from typing import Dict, List, Optional, Tuple

from bench import trace

__all__ = ["ENGINE_SPANS", "LEAVES", "device_lag", "load", "reduce"]

# the engine's span names (docs/observability.md); the leaves hold no
# other engine span, so an idle second charged to one names its host work
LEAVES = ("plan", "inputs", "dispatch", "issue", "sync", "residency",
          "account", "sample", "fetch", "apply", "expert_upload",
          "kv_swap_out", "kv_swap_in", "cow_copy_span")
PARENTS = ("boundary", "prefill", "prefill_chunk", "compute", "replay",
           "megastep")
ENGINE_SPANS = PARENTS + LEAVES


def load(trace_dir: str) -> dict:
    """The engine's host spans (``[start_ns, dur_ns, name]``) and the
    profile's start on the host clock (``start_ns``: an event's
    ``start_ns`` counts from it) of the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace._newest_xplane(trace_dir))
    spans, start = [], None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ENGINE_SPANS:
                        spans.append([ev.start_ns, ev.duration_ns, ev.name])
    return {"spans": spans, "start_ns": start}


def _idle(ops: list, lo: float, hi: float) -> List[List[float]]:
    """Intervals inside ``[lo, hi]`` that no op of ``ops`` covers."""
    busy = trace._merge([max(s, lo), min(s + d, hi)] for s, d, *_ in ops
                        if min(s + d, hi) > max(s, lo))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [[a, b] for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def device_lag(events: dict, engine: dict) -> Optional[Tuple[float, float]]:
    """Bounds, in ns, on how early the trace puts the first device's
    programs against the host's clock. A prefill or decode program starts
    after the host's ``dispatch`` span that issued it has begun, and ends
    before the ``sync`` span that fetches its counts ends; so the lag
    lies in ``[max(dispatch start - program start), min(sync end -
    program end)]`` over the engine's runs. None without a run."""
    first = min(events["devices"])
    progs = sorted((s, d) for s, d, name, prog, dev in events["modules"]
                   if prog and dev == first)
    starts = [s for s, _ in progs]
    dispatches = sorted(sp for sp in engine["spans"] if sp[2] == "dispatch")
    syncs = sorted(sp for sp in engine["spans"] if sp[2] == "sync")
    lo, hi = -math.inf, math.inf
    for (ds, dd, _), (ss, sd, _) in zip(dispatches, syncs):
        # the program issued in this run: nearest start to the dispatch,
        # and started before its sync ended
        i = bisect.bisect_left(starts, ds)
        near = [k for k in (i - 1, i) if 0 <= k < len(progs)
                and abs(starts[k] - ds) < 5 * 10**6 and starts[k] < ss + sd]
        if ss < ds or not near:
            continue
        s, d = progs[min(near, key=lambda k: abs(starts[k] - ds))]
        lo, hi = max(lo, ds - s), min(hi, ss + sd - (s + d))
    return None if lo == -math.inf else (lo, hi)


def reduce(events: dict, engine: dict) -> Optional[dict]:
    """Idle seconds by innermost host span, and the engine-host readings,
    inside the harness's ``traced_window``. ``events`` is
    ``trace.load``'s, ``engine`` is :func:`load`'s.

    The first device's events are moved later by the least shift that
    makes the trace causal (the lower bound of :func:`device_lag`, where
    it is positive). Every idle nanosecond of that device is then charged
    to the shortest host span that covers it, engine or harness
    (``harness`` where none does), so the charges sum to window minus
    busy. A gap is cut at the span edges inside it, not charged whole by
    its middle. None where the trace holds no device (a CPU run)."""
    win = [h for h in events["host"] if h[2] == "traced_window"]
    if not win:
        raise RuntimeError("the trace holds no traced_window span")
    if not events["devices"]:
        return None
    lag = device_lag(events, engine)
    shift_ns = max(lag[0], 0.0) if lag else 0.0
    first = min(events["devices"])
    events = dict(
        events,
        ops=[[s + shift_ns, d, name, label, dev]
             for s, d, name, label, dev in events["ops"] if dev == first],
        modules=[[s + shift_ns, d, name, prog, dev]
                 for s, d, name, prog, dev in events["modules"]
                 if dev == first])
    lo, hi = win[0][0], win[0][0] + win[0][1]
    spans = sorted([s, s + d, name] for s, d, name in
                   [h for h in events["host"] if h[2] != "traced_window"]
                   + engine["spans"])
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    programs = sorted((s, name) for s, d, name, prog, dev in events["modules"])
    prog_start = [s for s, _ in programs]

    by_span: Dict[str, float] = collections.defaultdict(float)
    gaps: Dict[str, float] = collections.defaultdict(float)
    prefill_idle = boundary_idle = 0.0
    for g0, g1 in _idle(events["ops"], lo, hi):
        j = bisect.bisect_left(prog_start, g1 - 1)
        nxt = re.sub(r"\(.*", "", programs[j][1]) if j < len(programs) else ""
        # spans that overlap the gap: they start before its end, and no
        # earlier than the longest span's length before its start
        i0 = bisect.bisect_left(starts, g0 - longest)
        i1 = bisect.bisect_left(starts, g1)
        near = [sp for sp in spans[i0:i1] if sp[1] > g0]
        cuts = sorted({g0, g1} | {x for s, e, _ in near for x in (s, e)
                                  if g0 < x < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [sp for sp in near if sp[0] <= mid <= sp[1]]
            who = min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover \
                else "harness"
            names = {sp[2] for sp in cover}
            sec = (b - a) * 1e-9
            by_span[who] += sec
            gaps[f"{who} -> {nxt}" if nxt else who] += sec
            if "prefill" in names:
                prefill_idle += sec
            elif "engine.step" in names:
                boundary_idle += sec

    def ending(name):
        return [(s, e) for s, e, n in spans if n == name and lo < e <= hi]

    counts = {n: len(ending(n)) for n in sorted({n for *_, n in spans})}
    chunks = ending("prefill_chunk")
    runs = [(s, d) for s, d, name, prog, dev in events["modules"]]
    chunk_s = sum(d for a, b in chunks for s, d in runs if a <= s <= b) * 1e-9
    megasteps = len(ending("megastep"))
    idle_s = sum(by_span.values())
    leaf_s = sum(v for k, v in by_span.items() if k in LEAVES)
    return {
        "device_lag_ms": [x * 1e-6 for x in lag] if lag else None,
        "shift_ms": shift_ns * 1e-6,
        "idle_s": idle_s,
        "idle_s_by_span": dict(by_span),
        "idle_leaf_share": leaf_s / idle_s if idle_s > 0 else None,
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:10],
        "span_counts": counts,
        "prefill_host_ms": (1000.0 * prefill_idle / len(chunks)
                            if chunks else None),
        "boundary_host_ms": (1000.0 * boundary_idle / megasteps
                             if megasteps else None),
        "prefill_chunk_ms": 1000.0 * chunk_s / len(chunks) if chunks else None,
    }
