"""A CPU-sized cell for the harness tests: the same block as the
benchmark's configurations at tiny widths, in a checkout of its own made
from new files and entries only (the way a later change adds a cell)."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import jax

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH / "tests" / "data"
if str(ROOT / "src") not in sys.path:  # the program, as bench/run.py finds it
    sys.path.insert(0, str(ROOT / "src"))


def cpu_device(chips: int) -> dict:
    """Stands in for ``run.check_device`` on the CPU (tests only)."""
    return {"platform": "cpu", "kind": "cpu", "count": chips,
            "devices": jax.devices()[:chips],
            "peak": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}}


def make_checkout(tmp: Path, traffic: str, *, dtype: str = "bfloat16",
                  limits: dict | None = None) -> Path:
    """A checkout under ``tmp`` whose ``BENCHMARK.json`` is the repo's with
    one cell, ``tiny.cell`` (``tiny-moe`` under ``traffic``), added as new
    files and entries; the repo's metric readers are used as they are."""
    root = tmp / f"checkout-{traffic}-{dtype}"
    shutil.rmtree(root, ignore_errors=True)
    for sub in ("configs", "traffic", "checks"):
        (root / "bench" / sub).mkdir(parents=True)
    os.symlink(BENCH / "metrics", root / "bench" / "metrics")
    conf = json.loads((DATA / "tiny.json").read_text())
    conf["program"]["dtype"] = dtype
    (root / "bench/configs/tiny-moe.json").write_text(json.dumps(conf))
    shutil.copy(DATA / f"{traffic}.json", root / f"bench/traffic/{traffic}.json")
    if limits is None:  # set from CPU readings of this cell (PERF.md)
        limits = json.loads((DATA / "tiny_limits.json").read_text())
    (root / "bench/checks/tiny.cell.json").write_text(json.dumps(limits))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    loop = json.loads((DATA / f"{traffic}.json").read_text())["loop"]
    like = [w["name"] for w in bench["workloads"]
            if json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                          .read_text())["loop"] == loop][:1]
    bench["configs"].append({"name": "tiny-moe", "source": "tests",
                             "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({"name": "tiny.cell", "config": "tiny-moe",
                               "traffic": traffic, "chips": 1, "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if set(like) & set(m.get("workloads", [])):
            m["workloads"].append("tiny.cell")
    if loop == "open":  # an open loop's users feel time to first token
        bench["end_to_end"].append({
            "name": "ttft_p95_ms", "unit": "ms", "better": "lower",
            "bound": 0.1, "source": "host_clock", "workloads": ["tiny.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_tiny(root: Path, cache: Path, seconds: float = 1.5, *, seed: int = 2**31 + 7,
             traced: bool = False, control: bool = False) -> dict:
    """One harness run of ``tiny.cell`` on the CPU, the compressed weights
    kept under ``cache``."""
    from bench import model, run

    old, was = model.CACHE, jax.config.jax_enable_compilation_cache
    model.CACHE = cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return run.run_cell("tiny.cell", seed, seconds, traced, root=root,
                            device_check=cpu_device, control=control)
    finally:
        model.CACHE = old
        jax.config.update("jax_enable_compilation_cache", was)
