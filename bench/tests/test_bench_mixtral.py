"""Mixtral's block on the program's normal path, at CPU size, against the
plain reference: the benchmark's weights for ``tiny_mixtral.json`` (d 256,
GQA 8 query / 2 KV heads of 32, 8 experts of 896 — Mixtral's 3.5x ratio —
top-2, no shared expert) go through ``calibrate``, ``compress_for_serving``
and ``PagedServingEngine`` (chunked prefill, then decode megasteps through
the paged cache), and every served token's logits are compared with
``bench/reference.py``'s full float32 forward, teacher-forced on the served
tokens. The PMQ pass itself is pinned to hashes its earlier, layer-copying
form produced."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model, reference, weights
from bench.tests import tiny

PROMPTS = (5, 16, 23, 40)  # one chunk, an exact chunk, two, three
MAX_NEW = 12
# Relative L2 error of a served token's logits against the float32
# reference, ||served - ref|| / ||ref||, with its reason:
# - float32 program: PMQ holds the benchmark's weights exactly, so only
#   accumulation order differs (kernels, paged attention's online softmax);
#   that is ~1e-6 of a row.
F32_TOL = 1e-4
# - bf16 program: activations and dequantized weight tiles in bf16, the
#   reference in float32. Rounding alone keeps a row near 1e-2, and the
#   median over a request's tokens must stay under BF16_MEDIAN; a row where
#   rounding swaps one of a token's top-2 experts in some layer moves
#   further, so each row must only stay under BF16_WORST (the values
#   chip_smoke.py holds the chip to).
#   The same sequences' logits from the reference in float8 (e4m3, the
#   precision below bf16) must break the median bound, so that a program
#   computing in float8 would fail.
BF16_MEDIAN, BF16_WORST = 0.05, 0.5
# sha256 of the compressed blocks' leaves (in tree order) and of the bit
# vector, from the PMQ pass as it was when it copied each layer of experts
# before slicing one: the streamed pass must give them bit for bit.
PASS_HASHES = {
    "tiny": ("4dc3c4cb08e2ed275e9abe10c696779281b3689b95c9923cdf622560c6d1cb12",
             [3, 3, 3, 3, 2, 1, 1, 1]),
    "tiny_mixtral": (
        "1414438f6aede4a67f5985e8597ae7381aeaae7513048a9114aac24086a61745",
        [3, 3, 3, 3, 2, 1, 1, 1]),
}


def _conf(name="tiny_mixtral", dtype=None):
    conf = json.loads((tiny.DATA / f"{name}.json").read_text())
    if dtype:
        conf["program"]["dtype"] = dtype
    return conf


def _compress(conf):
    """The benchmark's own PMQ step (``bench/model.py``)."""
    from repro.core import pipeline

    cfg = model.model_config(conf)
    params = weights.make_tree(conf["program"], conf["weights"]["seed"])
    q = conf["pmq"]
    rng = np.random.default_rng(q["calib_seed"])
    toks = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (q["calib_batch"], q["calib_len"])).astype(np.int32))
    calib = pipeline.calibrate(params, toks, cfg)
    served, _ = pipeline.compress_for_serving(
        params, calib, cfg, target_avg_bits=q["target_avg_bits"])
    return cfg, served


def _bits(ce):
    slot = np.asarray(ce.slot_of_expert)[0]
    return [next(m.bits for m in ce.meta if m.start <= s < m.start + m.count)
            for s in slot]


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def served(request):
    from repro.serving import EngineConfig, PagedServingEngine, Request

    conf = _conf(dtype=request.param)
    cfg, params = _compress(conf)
    pages = -(-(max(PROMPTS) + MAX_NEW) // 16) + 1
    engine = PagedServingEngine(cfg, params, EngineConfig(
        max_slots=len(PROMPTS), block_size=16,
        num_blocks=len(PROMPTS) * pages, max_blocks_per_slot=pages,
        keep_logits=True))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPTS]
    out = engine.serve([Request(rid=i, prompt=p, max_new=MAX_NEW)
                        for i, p in enumerate(prompts)])
    assert not engine.errors
    return conf, engine, prompts, out


def _reference_logits(m, seed, seq, precision="f32"):
    """Full logits ``[len(seq), V]`` of the plain reference (float32, or
    its float8 control): every vocabulary entry picked at every
    position."""
    t = len(seq)
    tp = -(-t // reference._PAD) * reference._PAD
    tokens = np.zeros(tp, np.int32)
    tokens[:t] = seq
    picks = np.tile(np.arange(m["vocab_size"], dtype=np.int32), (tp, 1))
    _, _, logits = reference._forward(m, seed, tokens, picks, precision)
    return np.asarray(logits)[:t]


def _rel(got, want):
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def _compare(conf, engine, prompts, out):
    """Per request, the rows' relative errors ``[MAX_NEW]``, the float8
    control's on the same rows, and whether each served token is the
    reference's best."""
    m, seed = conf["program"], conf["weights"]["seed"]
    errs, ctrl, best = [], [], []
    for rid, prompt in enumerate(prompts):
        got = np.stack(engine.token_logits[rid])  # [MAX_NEW, V]
        seq = np.concatenate([prompt, np.asarray(out[rid][:-1], np.int32)])
        rows = slice(len(prompt) - 1, None)
        want = _reference_logits(m, seed, seq)[rows]
        assert got.shape == want.shape == (MAX_NEW, m["vocab_size"])
        errs.append(_rel(got, want))
        ctrl.append(_rel(_reference_logits(m, seed, seq, "fp8")[rows], want))
        best.append(want.argmax(-1) == np.asarray(out[rid]))
    return np.stack(errs), np.stack(ctrl), np.stack(best)


def test_served_tree_has_mixtrals_experts_and_no_shared_expert(served):
    conf, engine, _, out = served
    blocks = engine.params["blocks"]
    assert "shared" not in blocks["moe"]
    ce = blocks["moe_ce"]
    # layer 0's budget, round(2.05 * 2 layers * 8) = 33 bits over 2
    # layers, gives it 17: 2.125 bits an expert in every layer
    assert sum(m.count for m in ce.meta) == 8
    assert sum(m.bits * m.count for m in ce.meta) == 17
    assert sorted(np.asarray(ce.slot_of_expert)[0]) == list(range(8))
    assert (ce.d_model, ce.d_ff) == (256, 896)
    assert all(len(o) == MAX_NEW for o in out.values())


def test_served_logits_match_the_reference(served):
    conf, engine, prompts, out = served
    errs, ctrl, best = _compare(conf, engine, prompts, out)
    assert np.all(np.isfinite(errs))
    if conf["program"]["dtype"] == "float32":
        assert errs.max() <= F32_TOL, errs.max()
        assert best.all()  # every greedy token the reference's best
    else:
        assert np.median(errs, axis=1).max() <= BF16_MEDIAN, errs
        assert errs.max() <= BF16_WORST, errs
    assert np.median(ctrl) > BF16_MEDIAN, ctrl  # float8 fails the bound


@pytest.mark.parametrize("name", sorted(PASS_HASHES))
def test_pmq_pass_reproduces_its_recorded_hashes(name):
    _, params = _compress(_conf(name))
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params["blocks"]):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    leaves, bits = PASS_HASHES[name]
    assert _bits(params["blocks"]["moe_ce"]) == bits
    assert h.hexdigest() == leaves
