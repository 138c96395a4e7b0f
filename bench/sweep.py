"""Find the knee of an open-loop cell: the highest arrival rate at which
the queue does not grow through the window.

    python3 bench/sweep.py --workload moonshot.chat --seed 7 --seconds 20 \\
        --rates 2 4 6 8

One process and one engine serve the cell's traffic at each rate in turn
(each with its own warm-up and drain). For each rate it prints the
requests due, the 50th and 95th percentile of time to first token in the
first and second half of the window, the queue depth at the window's
close, and output tokens per second. A rate whose second-half TTFT keeps
climbing, or whose queue is still full at the close, is past the knee.
The rate found is written into the traffic file by hand, as a number.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

import numpy as np  # noqa: E402

from bench import loop, model, run, traffic  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    data = run.load_cell(args.workload)
    conf, mix = data["config"], data["traffic"]
    dev = run.check_device(data["cell"]["chips"])
    run.enable_compile_cache()
    cfg = model.model_config(conf)
    params, _ = model.build_params(cfg, conf, log=run.say)
    engine = model.build_engine(cfg, conf, mix, params)
    del params
    rows = []
    for k, rate in enumerate(args.rates):
        drv = loop.Driver(engine)
        gen = traffic.specs(mix, args.seed + k, cfg.vocab_size)
        dues = traffic.due_times(
            mix, args.seed + k,
            int(rate * (mix["warmup_s"] + args.seconds) * 1.5) + 64, rate)
        depth = {}
        win = loop.open_loop(
            drv, gen, dues, mix["warmup_s"], args.seconds, mix["drain_s"],
            on_step=lambda now: depth.__setitem__(
                "close", engine.scheduler.queue_depth))
        reqs = [t for t in drv.tracked if t.in_window and t.done]
        mid = (win.open + win.close) / 2
        halves = [[1000 * (t.first - t.due) for t in reqs if (t.due < mid) == h]
                  for h in (True, False)]
        row = {
            "rate": rate, "due": sum(t.in_window for t in drv.tracked),
            "done": len(reqs),
            "ttft_p50_p95_ms_first_half": [
                float(np.percentile(h, q)) if h else math.nan for h in halves[:1]
                for q in (50, 95)],
            "ttft_p50_p95_ms_second_half": [
                float(np.percentile(h, q)) if h else math.nan for h in halves[1:]
                for q in (50, 95)],
            "queue_at_close": depth.get("close"),
            "tokens_per_s": win.tokens / max(win.close - win.open, 1e-9),
        }
        rows.append(row)
        run.say("sweep " + json.dumps(row, default=float))
        engine.tracer.consumers.remove(drv)
    print(json.dumps({"device": dev["kind"], "sweep": rows}, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
