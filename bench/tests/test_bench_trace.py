"""The trace reducer: busy and idle seconds, per-kernel and per-program
sums, and the breakdown, on a hand-made trace and a recorded one."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def _events():
    dev = "/device:TPU:0"
    return {
        "devices": [dev],
        "ops": [  # start, duration, name, kernel, plane
            [0 * MS, 2 * MS, "fusion", "", dev],         # half before the window
            [2 * MS, 3 * MS, "moe_gmm_pallas", "moe_gmm", dev],
            [4 * MS, 2 * MS, "paged_attention_pallas", "paged_attention", dev],  # overlaps
            [10 * MS, 1 * MS, "moe_gmm_pallas", "moe_gmm", dev],
            [19 * MS, 4 * MS, "fusion", "", dev],        # runs past the close
        ],
        "modules": [
            [2 * MS, 4 * MS, "jit_decode_fn(12)", "decode", dev],
            [10 * MS, 1 * MS, "jit_prefill_fn(3)", "prefill", dev],
            [19 * MS, 4 * MS, "jit_decode_fn(12)", "decode", dev],
        ],
        "host": [
            [1 * MS, 19 * MS, "traced_window"],
            [6 * MS, 4 * MS, "engine.step"],
            [6 * MS, 1 * MS, "submit"],
        ],
    }


def test_busy_idle_and_sums_inside_the_window():
    r = trace.reduce(_events())
    assert r["window_s"] == pytest.approx(0.019)
    # busy: [1,6) from the first three ops merged, [10,11), [19,20)
    assert r["busy_s"] == pytest.approx(0.007)
    assert r["kernel_s"] == pytest.approx({"moe_gmm": 0.004, "paged_attention": 0.002})
    assert r["kernel_calls"] == {"moe_gmm": 2, "paged_attention": 1}
    assert r["program_s"] == pytest.approx({"decode": 0.005, "prefill": 0.001})
    gaps = dict(r["breakdown"]["idle_gaps"])
    # idle [6,10) inside engine.step (submit covers only [6,7)) and [11,19)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert gaps["engine.step -> jit_prefill_fn"] == pytest.approx(0.004)
    assert gaps["harness -> jit_decode_fn"] == pytest.approx(0.008)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["moe_gmm"] == pytest.approx(0.004) and ops["fusion"] == pytest.approx(0.002)


def test_reduce_refuses_a_trace_without_its_window():
    ev = _events()
    ev["host"] = ev["host"][1:]
    with pytest.raises(RuntimeError):
        trace.reduce(ev)


@pytest.mark.parametrize("text,kernel", [
    ("%moe_gmm_swiglu_pallas.47 = bf16[2784,1408] custom-call(s32[174] %a)", "moe_gmm"),
    ("%moe_gmm_pallas.47 = bf16[2784,2048] custom-call(s32[174] %a)", "moe_gmm"),
    ("%paged_attention_pallas.3 = bf16[64,16,1,128] custom-call(s32[64,161] %t)", "paged_attention"),
    ("%quant_matmul_pallas.105 = bf16[16,2048] custom-call(bf16[16,2048] %x)", "quant_matmul"),
    ("%pad.281 = bf16[2785,2048] pad(bf16[2784,2048] %moe_gmm_pallas.47)", ""),
    ("%fusion.12 = f32[64,163840] fusion(bf16[64,2048] %x)", ""),
])
def test_kernels_found_by_their_names(text, kernel):
    assert trace.classify(text) == kernel


def test_recorded_trace_sums():
    """0.3 s of a traced window of ``moonshot.batch_short`` on one TPU v5
    lite: mostly one prefill chunk program after another."""
    events = json.loads((DATA / "trace_events.json").read_text())
    r = trace.reduce(events)
    lo, span = next(h[:2] for h in events["host"] if h[2] == "traced_window")
    inside = [(max(s, lo), min(s + d, lo + span), label)
              for s, d, name, label, dev in events["ops"]
              if min(s + d, lo + span) > max(s, lo)]
    for kernel in ("moe_gmm", "quant_matmul"):
        want = sum(b - a for a, b, label in inside if label == kernel) * 1e-9
        assert r["kernel_s"][kernel] == pytest.approx(want)
        assert r["kernel_calls"][kernel] == sum(1 for *_, l in inside if l == kernel)
    assert 0 < r["busy_s"] <= r["window_s"] == pytest.approx(span * 1e-9)
    idle = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["program_runs"]["prefill"] > 0
    assert len(r["breakdown"]["device_ops"]) == 10
