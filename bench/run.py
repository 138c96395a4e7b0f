"""Run one cell of ``BENCHMARK.json`` on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``, from process start to the window's
opening): find a TPU, load the cell's files by the names in
``BENCHMARK.json``, make the weights, compress them or load the compressed
blocks (``bench/model.py``), build the paged engine, and run the cell's own
traffic until every program it uses has run (closed loop: until every
slot has turned over once; open loop: ``warmup_s`` of arrivals). Then the
window of ``--seconds``, then the drain of the requests due in it. With
``--trace 1`` the first seconds of the window are traced by the profiler
and the result line carries the cell's per-layer metrics; with ``--trace
0`` its end-to-end metrics. Last, with the program's state freed, a sample
of the served requests is compared with the plain reference
(``bench/check.py``), which decides ``correct``.

The last line of standard output is one JSON object; each number compared
is printed beside its limit on the last lines of standard error and under
``checks``, the last key of that object. A run that finds no TPU, or fewer
chips than the cell asks for, exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, loop, model, trace, traffic  # noqa: E402

TRACE_S = 4.0  # seconds of the window the profiler records with --trace 1


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ the cell
def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by the names ``BENCHMARK.json``
    gives: its configuration, traffic, check limits and metrics."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    base = root / "bench"

    def applies(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in e2e_names]
    return {
        "cell": cell,
        "run_seconds": bench["run_seconds"],
        "config": json.loads((root / conf_entry["file"]).read_text()),
        "traffic": json.loads(
            (base / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((base / "checks" / f"{name}.json").read_text()),
        "end_to_end": e2e,
        "per_layer": per_layer,
        "readers": {m["name"]: base / "metrics" / f"{m['name']}.py"
                    for m in per_layer},
    }


def _reader(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -------------------------------------------------------------- device
def check_device(chips: int) -> dict:
    """The TPU this run measures, with its peaks; refuses anything else."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"the benchmark needs a TPU; JAX's first device is "
            f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": chips,
            "peak": peaks[kind], "devices": devs[:chips]}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        BENCH / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts traces and backend compiles (or compile-cache loads)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.n += 1


# ------------------------------------------------------ traced window
class Tracer:
    """Profiler trace of the window's first ``TRACE_S`` seconds, and the
    engine's own counts over the same steps (the roofline readers' calls)."""

    def __init__(self, engine, out_dir: Path, seconds: float):
        self.engine, self.dir, self.seconds = engine, out_dir, seconds
        self.on = False
        self.step_counts, self.decode_lengths = [], []
        self.prompt_tokens = 0
        self._in_decode = False
        self._ann = None
        self._record = engine._record_capacity_util
        self._decode = engine._decode_megastep
        engine._record_capacity_util = self._record_hook
        engine._decode_megastep = self._decode_hook

    def _record_hook(self, counts, t):
        if self.on:
            c = np.array(counts)
            self.step_counts.append(c)
            if not self._in_decode:
                self.prompt_tokens += int(c[0].sum()) // self.engine.cfg.top_k
        return self._record(counts, t)

    def _decode_hook(self):
        if self.on:
            h = self.engine.ecfg.decode_horizon
            steps = [[] for _ in range(h)]
            for req in self.engine.scheduler.active.values():
                budget = req.max_new - len(req.out)
                for s in range(min(h, budget)):
                    steps[s].append(req.pos + s + 1)
            self.decode_lengths += steps
        self._in_decode = True
        try:
            return self._decode()
        finally:
            self._in_decode = False

    def start(self, drv) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no Python call events: host spans only
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        m = self.engine.metrics
        self.mark = (loop.clock(), drv.tokens, len(m.active_per_step),
                     len(m.megastep_logical_steps))
        self._ann = jax.profiler.TraceAnnotation("traced_window")
        self._ann.__enter__()
        self.on = True

    def maybe_stop(self, drv, now: float) -> None:
        if self.on and now - self.mark[0] >= self.seconds:
            self.stop(drv)

    def stop(self, drv) -> None:
        if not self.on:
            return
        self.on = False
        self._ann.__exit__(None, None, None)
        m = self.engine.metrics
        t0, tok0, act0, ms0 = self.mark
        self.span = {
            "span_s": loop.clock() - t0,
            "tokens": drv.tokens - tok0,
            "active_per_step": list(m.active_per_step[act0:]),
            "decode_steps": int(sum(m.megastep_logical_steps[ms0:])),
        }
        jax.profiler.stop_trace()


def per_layer(data: dict, tr: Tracer, reduced: dict, peak: dict,
              window_requests: list) -> dict:
    """Each per-layer reader of the cell on what the traced window holds;
    a reader that finds nothing is left out."""
    engine_meta = tr.meta
    ctx = types.SimpleNamespace(
        trace=reduced, peak=peak, model=data["config"]["program"],
        group=data["config"]["pmq"]["group"],
        attn_bits=data["config"]["pmq"]["attn_bits"],
        buckets=engine_meta["buckets"], slots=engine_meta["slots"],
        step_counts=tr.step_counts, decode_lengths=tr.decode_lengths,
        prompt_tokens=tr.prompt_tokens,
        tokens_processed=tr.span["tokens"] + tr.prompt_tokens,
        span_s=tr.span["span_s"], active_per_step=tr.span["active_per_step"],
        decode_steps=tr.span["decode_steps"], requests=window_requests,
    )
    units = {m["name"]: m["unit"] for m in data["per_layer"]}
    out = {}
    for name, path in data["readers"].items():
        value = _reader(path)(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out


# ----------------------------------------------------------------- run
def p95(xs) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


def end_to_end(data: dict, win, window_requests: list, setup_s: float) -> dict:
    done = [t for t in window_requests if t.done]
    vals = {"setup_s": setup_s}
    if win.close > win.open:
        vals["output_tokens_per_s"] = win.tokens / (win.close - win.open)
    first = [1000.0 * (t.first - t.due) for t in done]
    if first:
        vals["ttft_p95_ms"] = p95(first)
    tpot = [1000.0 * (t.last - t.first) / (t.seen - 1) for t in done
            if t.seen >= 2]
    if tpot:
        vals["tpot_p95_ms"] = p95(tpot)
    out = {}
    for m in data["end_to_end"]:
        if m["name"] not in vals:
            raise RuntimeError(f"the window gave no {m['name']}")
        out[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             root: Path = ROOT, device_check=check_device,
             control: bool = False) -> dict:
    """One run of a cell; returns the result object (``checks`` last).
    With ``control`` the float8 control's tokens are judged in place of
    the served ones."""
    data = load_cell(workload, root)
    cell, conf, mix = data["cell"], data["config"], data["traffic"]
    dev = device_check(cell["chips"])
    say(f"device {dev['platform']} {dev['kind']} x{dev['count']}; compile "
        f"cache {enable_compile_cache()}")
    counter = CompileCounter()
    cfg = model.model_config(conf)
    params, info = model.build_params(cfg, conf, log=say)
    engine = model.build_engine(cfg, conf, mix, params)
    del params
    ce = engine.params["blocks"]["moe_ce"]
    say(f"engine: {mix['slots']} slots, pool {engine.ecfg.num_blocks} pages "
        f"of {engine.ecfg.block_size}, horizon {engine.ecfg.decode_horizon}, "
        f"prefill chunk {engine.ecfg.prefill_chunk}; expert buckets "
        f"{[(m.bits, m.count) for m in ce.meta]}; weights loaded in "
        f"{info['load_s']:.1f} s")
    drv = loop.Driver(engine)
    gen = traffic.specs(mix, seed, cfg.vocab_size)
    tr = None
    if traced:
        tr = Tracer(engine, root / "bench" / "traces" / f"{workload}-{seed}",
                    min(seconds, TRACE_S))
        tr.meta = {"buckets": [(m.bits, m.start, m.count) for m in ce.meta],
                   "slots": mix["slots"]}
    marks = {}

    def on_open():
        marks["compiles"] = counter.n
        marks["open"] = loop.clock()
        if tr is not None:
            tr.start(drv)

    def on_step(now):
        if tr is not None:
            tr.maybe_stop(drv, now)

    if mix["loop"] == "closed":
        win = loop.closed_loop(drv, gen, mix["clients"], seconds,
                               mix["drain_s"], on_open, on_step)
    else:
        rate = mix["rate_per_s"]
        count = int(rate * (mix["warmup_s"] + seconds) * 1.5) + 64
        dues = traffic.due_times(mix, seed, count)
        win = loop.open_loop(drv, gen, dues, mix["warmup_s"], seconds,
                             mix["drain_s"], on_open, on_step)
    window_compiles = counter.n - marks["compiles"]
    if tr is not None:
        tr.stop(drv)
    setup_s = win.open - T_START
    window_requests = [t for t in drv.tracked if t.in_window]
    failed = [t for t in window_requests if not t.done]
    say(f"window {win.close - win.open:.3f} s: {win.tokens} tokens in "
        f"{win.steps} engine steps, {len(window_requests)} requests due, "
        f"{len(failed)} failed; compiles or cache loads inside the window: "
        f"{window_compiles}")
    if win.lag_s:
        say(f"generator lag: median {1000 * np.median(win.lag_s):.3f} ms, "
            f"p95 {1000 * p95(win.lag_s):.3f} ms, max "
            f"{1000 * max(win.lag_s):.3f} ms")
    if info["compressed_now"]:
        say(f"this run compressed the weights ({info['compress_s']:.1f} s of "
            f"its set-up); later runs in this checkout load them")
    devices = dev["devices"]
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    result = {"correct": False, "attempted": len(window_requests),
              "failed": len(failed)}
    if traced:
        reduced = trace.reduce(trace.load(str(tr.dir)))
        shutil.rmtree(tr.dir, ignore_errors=True)
        result["metrics"] = per_layer(data, tr, reduced, dev["peak"],
                                      window_requests)
        result["breakdown"] = reduced["breakdown"]
        say(f"trace: busy {reduced['busy_s']:.4f} of {reduced['window_s']:.4f}"
            f" s; kernels {reduced['kernel_s']}; calls "
            f"{reduced['kernel_calls']}; programs {reduced['program_s']}")
    else:
        result["metrics"] = end_to_end(data, win, window_requests, setup_s)
    result["device"] = {"platform": dev["platform"], "kind": dev["kind"],
                        "count": dev["count"], "memory_peak_bytes": peak_mem}
    if traced:
        result["device"].update(busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
    say("metrics " + json.dumps(result["metrics"]))

    # the reference runs with the program's state freed
    done = [t for t in window_requests if t.done]
    picked = check.sample(done, mix["check_requests"]["count"], seed)
    drv.engine = None
    del engine, drv, ce
    gc.collect()
    t0 = time.perf_counter()
    pad = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    res = check.compare(conf["program"], conf["weights"]["seed"], picked,
                        data["limits"], pad, control=control)
    say(f"reference over {res['requests']} requests, {res['tokens']} served "
        f"tokens, in {time.perf_counter() - t0:.1f} s; widest gap "
        f"{res['max_gap']!r} (a reading, not compared)")
    if control:
        say(f"control: the float8 reference's tokens stand where the served "
            f"tokens were, and are judged below; the served tokens read mean "
            f"gap {res['mean_gap']!r}; the control's widest gap "
            f"{res['control_max_gap']!r}")
    result["correct"] = res["correct"] and not failed
    result["checks"] = dict(res["checks"],
                            failed_requests={"value": len(failed), "limit": 0})
    for k, c in result["checks"].items():
        say(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", action="store_true",
                   help="judge the float8 control's tokens in place of the "
                        "served ones (a sound limit makes the run not correct)")
    args = p.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), control=args.control)
    except SystemExit as e:
        say(f"refused: {e}")
        return 2
    except Exception as e:  # any failure: no result line
        traceback.print_exc()
        say(f"FAILED: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
