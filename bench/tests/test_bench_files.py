"""BENCHMARK.json against the files it names and the rules it keeps."""
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
# published key of the expert width, the routed and the shared expert count
EXPERT_KEYS = {
    "deepseek_v3": ("moe_intermediate_size", "n_routed_experts", "n_shared_experts"),
}


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word
    assert (ROOT / SPEC["command"][1]).is_file()


def test_names_units_and_entry_keys():
    names = [c["name"] for c in SPEC["configs"]] + CELLS + [
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_names_only_files_that_exist(cell):
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    conf = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert conf["file"].startswith("bench/") and (ROOT / conf["file"]).is_file()
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    assert (BENCH / "checks" / f"{cell}.json").is_file()
    for m in SPEC["per_layer"]:
        if cell in m.get("workloads", []):
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_another_metric_and_a_layer(cell):
    from bench import run

    data = run.load_cell(cell)
    e2e = {m["name"] for m in data["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert data["per_layer"]
    for m in SPEC["per_layer"]:
        if cell in m.get("workloads", []):
            assert m["moves"] in e2e, (m["name"], cell)
    assert set(data["readers"]) == {m["name"] for m in data["per_layer"]}


def test_every_configuration_is_used_and_matches_its_program_group():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        conf = json.loads((ROOT / c["file"]).read_text())
        prog = conf["program"]
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert prog["d_model"] == conf["hidden_size"]
        assert prog["num_layers"] == conf["num_hidden_layers"]
        assert prog["num_heads"] == conf["num_attention_heads"]
        assert prog["num_kv_heads"] == conf["num_key_value_heads"]
        width, experts, shared = EXPERT_KEYS[conf["model_type"]]
        assert prog["d_ff_expert"] == conf[width]
        assert prog["num_experts"] == conf[experts]
        assert prog["num_shared_experts"] == conf.get(shared, 0)
        assert prog["top_k"] == conf["num_experts_per_tok"]
        assert prog["vocab_size"] == conf["vocab_size"]
        assert prog["rope_theta"] == conf["rope_theta"]
        assert prog["norm_eps"] == conf["rms_norm_eps"]
        assert set(conf["reduced"]) <= set(conf["reduced_from"])


def test_peaks_are_keyed_by_device_kind_with_a_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_bench_gitignore_keeps_caches_and_traces_out():
    lines = (BENCH / ".gitignore").read_text().split()
    assert ".cache/" in lines and "traces/" in lines
