"""A run that finds no TPU exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def test_check_device_refuses_the_cpu():
    from bench import run

    with pytest.raises(SystemExit, match="needs a TPU"):
        run.check_device(1)


def test_main_refuses_without_a_result_line(capsys):
    from bench import run

    assert run.main(["--workload", "moonshot.batch_short", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_a_checkout_of_the_benchmark_alone_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program, no
    chip; the command must fail and print nothing on standard output."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".cache", "traces", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, *spec["command"][1:], "--workload",
         spec["workloads"][0]["name"], "--seed", "5", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
