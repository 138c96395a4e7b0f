"""Profiler trace of part of the window, reduced to what the readers use.

Two stages, so the second can be tested on a small recorded trace:

1. :func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
   keeps, on one common clock: each device operation (line ``XLA Ops`` of
   the ``/device:TPU:N`` planes) with its start, duration, name and the
   kernel it belongs to; each program run (line ``XLA Modules``); and the
   harness's own host spans (``loop.py``'s ``TraceAnnotation`` names and
   ``traced_window``). An op is kept by its instruction name, and the
   kernel is found by it: a Pallas call is named after its jitted wrapper
   (``%moe_gmm_swiglu_pallas.47 = ...``, ``%quant_matmul_pallas.3``), and
   each kernel's ``PATTERN`` over those names sits in its module in
   ``bench/work``.
2. :func:`reduce` turns that into busy and idle seconds inside the traced
   window, seconds per kernel and per program, and the ``breakdown`` the
   result line carries.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional

from bench import work

__all__ = ["HOST_SPANS", "classify", "load", "op_name", "reduce"]

# ops that hold other ops of the same line: counted as busy, never summed
CONTAINERS = re.compile(r"^(while|conditional|call)$")
HOST_SPANS = ("traced_window", "generator", "submit", "engine.step", "drain")
PROGRAMS = {"decode": re.compile(r"decode_fn"), "prefill": re.compile(r"prefill_fn")}


def op_name(text: str) -> str:
    """The instruction's own name without its number: ``moe_gmm_pallas``
    for ``%moe_gmm_pallas.47 = bf16[...] custom-call(...)``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head.split()[0] if head else "")


def classify(text: str) -> str:
    base = op_name(text)
    for name, mod in work.kernels().items():
        if mod.PATTERN.search(base):
            return name
    return ""


def _program(name: str) -> str:
    for prog, pat in PROGRAMS.items():
        if pat.search(name):
            return prog
    return ""


def _newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir: str) -> dict:
    """Stage 1: the newest trace under ``trace_dir`` as plain lists."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(_newest_xplane(trace_dir))
    ops, modules, host = [], [], []
    devices = set()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.add(plane.name)
                    for ev in line.events:
                        name = op_name(ev.name)
                        ops.append([ev.start_ns, ev.duration_ns, name,
                                    classify(name), plane.name])
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append([ev.start_ns, ev.duration_ns, ev.name,
                                        _program(ev.name), plane.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append([ev.start_ns, ev.duration_ns, ev.name])
    return {"devices": sorted(devices), "ops": ops, "modules": modules,
            "host": host}


def _merge(intervals):
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, d, lo, hi):
    return max(s, lo), min(s + d, hi)


def reduce(events: dict) -> dict:
    """Stage 2: busy/idle, kernel, op and program seconds, and the
    breakdown, all inside the host span ``traced_window``."""
    win = [h for h in events["host"] if h[2] == "traced_window"]
    if not win:
        raise RuntimeError("the trace holds no traced_window span")
    lo, hi = win[0][0], win[0][0] + win[0][1]
    ndev = max(len(events["devices"]), 1)
    per_dev = collections.defaultdict(list)
    kernel_s: Dict[str, float] = collections.defaultdict(float)
    kernel_calls: Dict[str, int] = collections.defaultdict(int)
    op_s: Dict[str, float] = collections.defaultdict(float)
    for s, d, name, label, dev in events["ops"]:
        a, b = _clip(s, d, lo, hi)
        if b <= a:
            continue
        per_dev[dev].append((a, b))
        dur = (b - a) * 1e-9
        if not CONTAINERS.match(name):
            op_s[label or name] += dur
        if label:
            kernel_s[label] += dur
            kernel_calls[label] += 1
    busy = [_merge(v) for v in per_dev.values()]
    busy_s = sum(sum(e - s for s, e in m) for m in busy) * 1e-9 / ndev
    program_s: Dict[str, float] = collections.defaultdict(float)
    program_runs: Dict[str, int] = collections.defaultdict(int)
    for s, d, name, prog, dev in events["modules"]:
        a, b = _clip(s, d, lo, hi)
        if b > a and prog:
            program_s[prog] += (b - a) * 1e-9 / ndev
            program_runs[prog] += 1
    gaps = collections.defaultdict(float)
    spans = sorted((h for h in events["host"] if h[2] != "traced_window"),
                   key=lambda h: h[1])  # innermost (shortest) first
    starts = sorted((s, name) for s, d, name, prog, dev in events["modules"])
    start_ns = [s for s, _ in starts]
    for merged in busy[:1]:
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            who = next((h[2] for h in spans if h[0] <= mid <= h[0] + h[1]),
                       "harness")
            j = bisect.bisect_left(start_ns, g1 - 1)
            nxt = re.sub(r"\(.*", "", starts[j][1]) if j < len(starts) else ""
            gaps[f"{who} -> {nxt}" if nxt else who] += (g1 - g0) * 1e-9
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "kernel_s": dict(kernel_s),
        "kernel_calls": dict(kernel_calls),
        "op_s": dict(op_s),
        "program_s": dict(program_s),
        "program_runs": dict(program_runs),
        "breakdown": {"device_ops": [[k, v] for k, v in top],
                      "idle_gaps": [[k, v] for k, v in idle]},
    }
