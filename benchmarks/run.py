"""Benchmark harness entrypoint — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Each bench prints ``name,us_per_call,derived`` CSV rows; roofline rows
come from the dry-run JSONs (run repro.launch.dryrun --all first for the
full 40-cell table).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="reduced sweeps (CI-sized)")
    p.add_argument("--only", default=None,
                   help="comma-separated bench names")
    args = p.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        bit_allocation,
        kernel_bench,
        lambda_sweep,
        memory_speed,
        moe_ffn_bench,
        otp_ablation,
        pareto,
        roofline,
        serving_latency,
    )

    benches = {
        "kernel_bench": lambda: kernel_bench.run(args.quick),
        "moe_ffn": lambda: moe_ffn_bench.run(args.quick),
        "bit_allocation": lambda: bit_allocation.run(args.quick),
        "pareto": lambda: pareto.run(args.quick),
        "otp_ablation": lambda: otp_ablation.run(args.quick),
        "lambda_sweep": lambda: lambda_sweep.run(args.quick),
        "memory_speed": lambda: memory_speed.run(args.quick),
        "serving_latency": lambda: serving_latency.run(args.quick),
        "roofline": lambda: roofline.run(),
    }
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in benches.items() if k in keep}

    print("name,us_per_call,derived")
    failures = []
    t0 = time.time()
    for name, fn in benches.items():
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failures.append(name)
    print(f"# total {time.time()-t0:.0f}s; failures: {failures or 'none'}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
