"""Drive ``PagedServingEngine.submit`` / ``step`` in a closed or open loop.

Every time is taken on the host's ``perf_counter`` after ``engine.step()``
returns: the engine fetches its emitted tokens to the host inside the
step, so a token is stamped once the device has produced it and the host
has seen it. A request is timed from its due time: for an open loop the
schedule's, for a closed loop the moment its client's last request
finished. Admission times come from the engine's own admission record
(its lifecycle stream, the one ``ServingMetrics.record_admission`` reads).

Host spans (``jax.profiler.TraceAnnotation``) name what the host does
around the engine — ``generator``, ``submit``, ``engine.step``, ``drain``
— so that idle gaps in a device trace can be put down to one of them.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from .traffic import Spec

__all__ = ["Tracked", "Driver", "closed_loop", "open_loop"]

clock = time.perf_counter


@dataclasses.dataclass
class Tracked:
    spec: Spec
    req: object
    due: float
    in_window: bool
    client: int = -1
    submit: float = math.nan
    admit: float = math.nan
    first: float = math.nan
    last: float = math.nan
    seen: int = 0
    done: bool = False
    error: str = ""


class Driver:
    """Submits requests, steps the engine, stamps what the host sees."""

    def __init__(self, engine):
        from repro.serving import Request

        self._request = Request
        self.engine = engine
        self.live: Dict[int, Tracked] = {}
        self.tracked: List[Tracked] = []
        self.tokens = 0
        self.steps = 0
        engine.tracer.consumers.append(self)

    def on_lifecycle(self, kind: str, fields: dict) -> None:
        if kind == "admit" and not fields.get("resumed"):
            t = self.live.get(fields.get("rid"))
            if t is not None and math.isnan(t.admit):
                t.admit = clock()

    def submit(self, spec: Spec, due: float, in_window: bool,
               client: int = -1) -> Tracked:
        req = self._request(rid=spec.index, prompt=spec.prompt,
                            max_new=spec.max_new)
        t = Tracked(spec, req, due, in_window, client)
        self.live[spec.index] = t
        self.tracked.append(t)
        with TraceAnnotation("submit"):
            self.engine.submit(req)
        t.submit = clock()
        return t

    def step(self) -> List[Tracked]:
        """One engine round; returns the requests that ended in it."""
        with TraceAnnotation("engine.step"):
            self.engine.step()
        self.steps += 1
        now = clock()
        ended = []
        errors = self.engine.errors
        for rid, t in list(self.live.items()):
            n = len(t.req.out)
            if n > t.seen:
                if t.seen == 0:
                    t.first = now
                t.last = now
                self.tokens += n - t.seen
                t.seen = n
            if rid in errors:
                t.error = type(errors[rid]).__name__
            elif n >= t.req.max_new:
                t.done = True
            else:
                continue
            del self.live[rid]
            ended.append(t)
        return ended

    def drain(self, limit_s: float) -> None:
        """Step until nothing is live or ``limit_s`` has passed; what is
        still live then is marked failed."""
        t_end = clock() + limit_s
        with TraceAnnotation("drain"):
            while self.live and clock() < t_end:
                self.step()
        for t in self.live.values():
            t.error = t.error or "unfinished at the drain limit"


@dataclasses.dataclass
class Window:
    open: float
    close: float
    tokens: int
    steps: int
    lag_s: List[float]


def closed_loop(drv: Driver, gen: Iterator[Spec], clients: int,
                seconds: float, drain_s: float,
                on_open: Callable[[], None] = lambda: None,
                on_step: Callable[[float], None] = lambda now: None) -> Window:
    """One client per slot. Warm-up until every client has finished a
    request (every slot has turned over once, every shape has run); then
    a window of ``seconds``; then the requests sent in it are drained."""
    for c in range(clients):
        with TraceAnnotation("generator"):
            spec = next(gen)
        drv.submit(spec, clock(), False, c)
    turned = set()
    while len(turned) < clients:
        for t in drv.step():
            turned.add(t.client)
            with TraceAnnotation("generator"):
                spec = next(gen)
            drv.submit(spec, clock(), False, t.client)
    on_open()
    t_open = clock()
    tokens0, steps0 = drv.tokens, drv.steps
    now = t_open
    while now - t_open < seconds:
        ended = drv.step()
        now = clock()
        for t in ended:
            with TraceAnnotation("generator"):
                spec = next(gen)
            drv.submit(spec, now, True, t.client)
        on_step(now)
    win = Window(t_open, now, drv.tokens - tokens0, drv.steps - steps0, [])
    drv.drain(drain_s)
    return win


def open_loop(drv: Driver, gen: Iterator[Spec], dues: np.ndarray,
              warmup_s: float, seconds: float, drain_s: float,
              on_open: Callable[[], None] = lambda: None,
              on_step: Callable[[float], None] = lambda now: None) -> Window:
    """Arrivals at ``dues`` (seconds from the start), sent whatever the
    engine does. Before the schedule starts, one request per slot is
    served to its end, so that every program has run; then ``warmup_s`` of
    arrivals bring the queue to its steady state. The window holds the
    requests due in ``[warmup_s, warmup_s + seconds)``; they are drained
    after it."""
    slots = drv.engine.ecfg.max_slots
    for _ in range(slots):
        with TraceAnnotation("generator"):
            spec = next(gen)
        drv.submit(spec, clock(), False)
    while drv.live:
        drv.step()
    t0 = clock()
    t_open, t_close = t0 + warmup_s, t0 + warmup_s + seconds
    i, opened = 0, False
    lag: List[float] = []
    tokens0 = steps0 = 0

    def send(now: float, until: float) -> None:
        nonlocal i
        while i < len(dues) and t0 + dues[i] <= until:
            due = t0 + dues[i]
            with TraceAnnotation("generator"):
                spec = next(gen)
            t = drv.submit(spec, due, t_open <= due < t_close)
            if t.in_window:
                lag.append(t.submit - due)
            i += 1

    while True:
        now = clock()
        if not opened and now >= t_open:
            opened = True
            on_open()
            tokens0, steps0 = drv.tokens, drv.steps
        if now >= t_close:
            break
        send(now, now)
        if drv.engine.scheduler.has_work():
            drv.step()
            on_step(clock())
        elif i < len(dues):
            time.sleep(max(0.0, min(0.002, t0 + dues[i] - clock())))
    send(now, t_close - 1e-9)  # due before the close, sent late
    if i >= len(dues):
        raise RuntimeError("the arrival schedule ran out inside the window")
    win = Window(t_open, now, drv.tokens - tokens0, drv.steps - steps0, lag)
    drv.drain(drain_s)
    return win
