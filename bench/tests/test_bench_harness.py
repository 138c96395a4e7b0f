"""The harness end to end on a CPU-sized cell added by new files alone:
weights that PMQ holds exactly, a reference that agrees with the program,
closed and open loops, and a traced run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.tests import tiny


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("weights")


def test_a_layer_made_alone_equals_its_slice_of_the_tree():
    import json
    m = json.loads((tiny.DATA / "tiny.json").read_text())["program"]
    tree = weights.make_tree(m, 3)
    for layer in range(m["num_layers"]):
        one = weights.make_layer(m, 3, layer, jnp.bfloat16)
        for path, leaf in jax.tree_util.tree_flatten_with_path(one)[0]:
            full = tree["blocks"]
            for k in path:
                full = full[k.key]
            assert np.array_equal(np.asarray(leaf, np.float32),
                                  np.asarray(full[layer], np.float32)), path


def test_pmq_holds_the_benchmark_weights_exactly():
    """Every packed expert and attention matrix dequantizes to the weight
    it was made from, bit for bit, at whatever width PMQ gave it."""
    import json
    from repro.core import pipeline
    from repro.kernels import ref
    from bench import model

    conf = json.loads((tiny.DATA / "tiny.json").read_text())
    cfg = model.model_config(conf)
    params = weights.make_tree(conf["program"], 0)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 32)), jnp.int32)
    calib = pipeline.calibrate(params, toks, cfg)
    pc, _ = pipeline.compress_for_serving(params, calib, cfg)
    ce = pc["blocks"]["moe_ce"]
    experts = params["blocks"]["moe"]["experts"]
    slot = np.asarray(ce.slot_of_expert)
    bits_seen = set()
    for i, meta in enumerate(ce.meta):
        bits_seen.add(meta.bits)
        for name, k in (("w_gate", 256), ("w_up", 256), ("w_down", 128)):
            w = ce.arrays[f"b{i}"][name]
            planes = (w["hi"], w["lo"]) if meta.bits == 3 else w["data"]
            for layer in range(cfg.num_layers):
                for j in range(meta.count):
                    e = int(np.flatnonzero(slot[layer] == meta.start + j)[0])
                    got = ref.dequant_ref(
                        jax.tree.map(lambda a: a[layer, j], planes),
                        w["scale"][layer, j], w["zero"][layer, j],
                        meta.bits, k, ce.group)
                    want = experts[name][layer, e]
                    assert np.array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))
    assert bits_seen == {1, 2, 3}
    wq = pc["blocks"]["attn"]["wq"]["w"]
    got = ref.dequant_ref(wq.data[0], wq.scale[0], wq.zero[0], wq.bits,
                          wq.shape[0], wq.group)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(params["blocks"]["attn"]["wq"]["w"][0], np.float32))


def test_float32_program_serves_the_references_best_tokens(tmp_path, cache):
    root = tiny.make_checkout(tmp_path, "tiny_closed", dtype="float32")
    res = tiny.run_tiny(root, cache)
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["mean_gap"]["value"] < 1e-6
    assert set(res["metrics"]) == {"output_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def test_closed_loop_bf16_run_is_correct_and_traced_run_reads_layers(tmp_path, cache):
    root = tiny.make_checkout(tmp_path, "tiny_closed")
    res = tiny.run_tiny(root, cache)
    assert res["correct"] and res["attempted"] > 0
    traced = tiny.run_tiny(root, cache, traced=True)
    assert traced["correct"]
    # on the CPU no device plane exists: only the counter reader reports
    assert set(traced["metrics"]) == {"batch_occupancy", "step_mfu"}
    assert 0 < traced["metrics"]["batch_occupancy"]["value"] <= 100


def test_open_loop_run_reports_ttft_and_is_correct(tmp_path, cache):
    root = tiny.make_checkout(tmp_path, "tiny_open")
    res = tiny.run_tiny(root, cache, seconds=2.0)
    assert res["correct"]
    assert set(res["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}


def test_sweep_reports_each_rate(tmp_path, cache, monkeypatch, capsys):
    import json
    from bench import model, run, sweep

    root = tiny.make_checkout(tmp_path, "tiny_open")
    load = run.load_cell
    monkeypatch.setattr(run, "load_cell", lambda name: load(name, root))
    monkeypatch.setattr(run, "check_device", tiny.cpu_device)
    monkeypatch.setattr(model, "CACHE", cache)
    assert sweep.main(["--workload", "tiny.cell", "--seed", "3",
                       "--seconds", "1", "--rates", "2", "6"]) == 0
    rows = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["sweep"]
    assert [r["rate"] for r in rows] == [2.0, 6.0]
    assert all(r["due"] > 0 and r["done"] == r["due"] for r in rows)
