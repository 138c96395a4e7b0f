"""End-to-end MC# pipeline tests on a small MoE model.

Covers: calibration capture, eps computation, PMQ allocation, GPTQ
compression, compressed forward fidelity (vs fp), OTP training
integration, and the compressed-vs-fp agreement ordering across bit
budgets (higher bits → closer to fp — the Pareto sanity check).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, QuantConfig
from repro.core import pipeline
from repro.core.compressed_moe import build_compressed_experts, compressed_moe_layer
from repro.core.otp_train import OTPTrainConfig, train_otp
from repro.models import transformer as tf
from repro.models.moe import moe_layer
from repro.models.registry import get_model

CFG = ModelConfig(
    name="test-moe",
    family="moe",
    num_layers=2,
    d_model=128,
    num_heads=4,
    num_kv_heads=2,
    head_dim=32,
    d_ff=128,
    d_ff_expert=128,
    vocab_size=256,
    num_experts=8,
    top_k=2,
    num_shared_experts=1,
    dtype="float32",
    remat="none",
    logits_chunk=32,
    attn_q_chunk=32,
    attn_kv_chunk=32,
    moe_capacity_factor=2.0,
)


@pytest.fixture(scope="module")
def model_and_calib():
    bundle = get_model(CFG)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (4, 64)), jnp.int32)
    calib = pipeline.calibrate(params, tokens, CFG)
    return bundle, params, tokens, calib


def test_calibration_stats(model_and_calib):
    _, params, tokens, calib = model_and_calib
    assert calib.phi.shape == (2, 8)
    assert calib.w.shape == (2, 8)
    # frequencies: each token activates top_k experts
    np.testing.assert_allclose(calib.phi.sum(axis=1), CFG.top_k, rtol=1e-6)
    assert (calib.w >= 0).all()
    assert len(calib.moe_inputs) == 2


def test_eps_monotone_in_bits(model_and_calib):
    _, params, tokens, calib = model_and_calib
    eps = pipeline.compute_eps(params, calib, CFG, eps_tokens=128)
    assert eps.shape == (2, 8, 3)
    # more bits → lower reconstruction error, per expert (weak: on average)
    assert (eps[..., 0] >= eps[..., 2]).mean() > 0.9


def test_pmq_plan_and_compress(model_and_calib):
    _, params, tokens, calib = model_and_calib
    eps = pipeline.compute_eps(params, calib, CFG, eps_tokens=128)
    plan = pipeline.run_pmq(params, calib, CFG, target_avg_bits=2.0, eps=eps)
    assert abs(plan.avg_bits - 2.0) < 1e-9
    blocks_c, top = pipeline.compress_model(
        params, calib, plan, CFG, use_gptq=True, gptq_tokens=256
    )
    # compressed weights much smaller than fp32 expert weights
    fp_bytes = sum(
        np.asarray(v).nbytes
        for v in jax.tree.leaves(params["blocks"])
    )
    c_bytes = pipeline.model_weight_bytes(blocks_c, top)
    assert c_bytes < fp_bytes
    # hidden-state fidelity vs fp model (random-init weights: cosine is the
    # right scale-free metric; argmax agreement only makes sense on trained
    # models and is measured in benchmarks/)
    logits_c, _ = pipeline.compressed_logits(blocks_c, top, tokens[:2], CFG)
    h_c, _ = pipeline.compressed_forward(blocks_c, top, tokens[:2], CFG)
    hidden, _, _ = tf.forward_hidden(params, tokens[:2], CFG)
    a = np.asarray(h_c, np.float64).reshape(-1)
    b = np.asarray(hidden, np.float64).reshape(-1)
    cos = a @ b / (np.linalg.norm(a) * np.linalg.norm(b))
    assert cos > 0.6, f"2-bit hidden cosine too low: {cos}"
    assert np.isfinite(np.asarray(logits_c)).all()


def test_gptq_beats_rtn_at_model_level(model_and_calib):
    _, params, tokens, calib = model_and_calib
    eps = pipeline.compute_eps(params, calib, CFG, eps_tokens=128)
    plan = pipeline.run_pmq(params, calib, CFG, target_avg_bits=2.0, eps=eps)
    hidden_fp, _, _ = tf.forward_hidden(params, tokens[:2], CFG)
    errs = {}
    for use_gptq in (False, True):
        blocks_c, top = pipeline.compress_model(
            params, calib, plan, CFG, use_gptq=use_gptq, gptq_tokens=256
        )
        h_c, _ = pipeline.compressed_forward(blocks_c, top, tokens[:2], CFG)
        errs[use_gptq] = float(jnp.mean((h_c - hidden_fp) ** 2))
    assert errs[True] < errs[False] * 1.05, errs


def test_higher_budget_closer_to_fp(model_and_calib):
    """Pareto sanity: avg 2.5 bits beats avg 1.6 bits in output MSE."""
    _, params, tokens, calib = model_and_calib
    eps = pipeline.compute_eps(params, calib, CFG, eps_tokens=128)
    hidden_fp, _, _ = tf.forward_hidden(params, tokens[:2], CFG)
    mses = []
    for target in (1.6, 2.5):
        plan = pipeline.run_pmq(params, calib, CFG, target_avg_bits=target, eps=eps)
        blocks_c, top = pipeline.compress_model(
            params, calib, plan, CFG, use_gptq=False
        )
        h_c, _ = pipeline.compressed_forward(blocks_c, top, tokens[:2], CFG)
        mses.append(float(jnp.mean((h_c - hidden_fp) ** 2)))
    assert mses[1] < mses[0], mses


def test_compressed_moe_layer_matches_dequant_reference():
    """Bucketed compressed layer == moe_layer on fake-quantized weights."""
    rng = jax.random.PRNGKey(5)
    bundle = get_model(CFG)
    params = bundle.init(rng)
    p_l = tf.unstack_blocks(params, CFG)[0]
    x = jax.random.normal(rng, (2, 16, CFG.d_model))
    bits = np.array([1, 1, 2, 2, 2, 3, 3, 2])
    experts = {k: np.asarray(p_l["moe"]["experts"][k]) for k in
               ("w_gate", "w_up", "w_down")}
    ce = build_compressed_experts(experts, bits, group=128, ep=1, refine=False)
    y_c, info = compressed_moe_layer(p_l["moe"], ce, x, CFG)
    # reference: fake-quantize each expert at its width, run normal layer
    from repro.core.quantizers import quantize_to_packed

    fq = {k: [] for k in experts}
    for i in range(8):
        for k in experts:
            pt = quantize_to_packed(jnp.asarray(experts[k][i]), int(bits[i]),
                                    group=128, refine=False)
            fq[k].append(pt.dequantize())
    p_ref = dict(p_l["moe"], experts={k: jnp.stack(v) for k, v in fq.items()})
    out_ref = moe_layer(p_ref, x, CFG)
    np.testing.assert_allclose(
        np.asarray(y_c), np.asarray(out_ref.y), rtol=5e-4, atol=5e-4
    )


def test_otp_training_increases_mask_ratio_and_keeps_kl_low(model_and_calib):
    _, params, tokens, calib = model_and_calib
    eps = pipeline.compute_eps(params, calib, CFG, eps_tokens=128)
    plan = pipeline.run_pmq(params, calib, CFG, target_avg_bits=2.0, eps=eps)
    blocks_c, top = pipeline.compress_model(params, calib, plan, CFG, use_gptq=False)
    rng = np.random.default_rng(1)
    data = rng.integers(0, CFG.vocab_size, (32, 32)).astype(np.int32)
    tcfg = OTPTrainConfig(steps=30, batch=4, lr=5e-3, lam=2.0, seed=0)
    otp_params, hist = train_otp(blocks_c, top, CFG, data, tcfg)
    r_first = np.mean([h["mask_ratio"] for h in hist[:5]])
    r_last = np.mean([h["mask_ratio"] for h in hist[-5:]])
    assert r_last > r_first, (r_first, r_last)  # sparsity pressure works
    assert np.isfinite(hist[-1]["kl"])


def _full_layer_pass(params, tokens, cfg, eps_tokens=128):
    """Calibration statistics and Eq. 6 eps the plain way: every layer's
    whole block sliced at once, the MoE layer run over all its experts,
    and each probe a full layer with one expert fake-quantized in the
    stack."""
    x = jnp.take(params["embed"], tokens, axis=0)
    windows = tf.layer_windows_static(cfg, tokens.shape[1])
    phi, w, inputs, eps = [], [], [], []
    for l, p_l in enumerate(tf.iter_blocks(params, cfg)):
        x, h2 = pipeline._block_parts(p_l, x, cfg, int(windows[l]))
        inputs.append(np.asarray(h2.reshape(-1, cfg.d_model), np.float32))
        out = moe_layer(p_l["moe"], h2, cfg)
        stats = pipeline.significance.RouterStats(cfg.num_experts)
        stats.update(np.asarray(out.topk_idx), np.asarray(out.topk_gates))
        phi.append(stats.phi)
        w.append(stats.w)
        x = x + out.y
        ex = p_l["moe"]["experts"]
        h = jnp.asarray(inputs[-1][:eps_tokens])[None]
        base = moe_layer(p_l["moe"], h, cfg).y
        e_l = np.zeros((cfg.num_experts, 3))
        for i in range(cfg.num_experts):
            for j, bits in enumerate((1, 2, 3)):
                q = pipeline._fake_quant_expert(
                    {k: ex[k][i] for k in ex}, bits, cfg.quant.group)
                probe = {k: jnp.stack([q[k] if e == i else ex[k][e]
                                       for e in range(cfg.num_experts)])
                         for k in ex}
                y = moe_layer(dict(p_l["moe"], experts=probe), h, cfg).y
                e_l[i, j] = float(jnp.linalg.norm((y - base).astype(jnp.float32)))
        eps.append(e_l)
    return np.stack(phi), np.stack(w), inputs, np.stack(eps)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_streamed_pass_equals_the_full_layer_pass(dtype):
    """Calibration and eps, one expert of one layer at a time, give bit
    for bit what the whole layer gives: routing does not depend on the
    experts, and each expert's rows are computed alone either way."""
    cfg = dataclasses.replace(CFG, dtype=dtype)
    params = get_model(cfg).init(jax.random.PRNGKey(3))
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 48)), jnp.int32)
    calib = pipeline.calibrate(params, tokens, cfg)
    eps = pipeline.compute_eps(params, calib, cfg, eps_tokens=128)
    phi, w, inputs, eps_full = _full_layer_pass(params, tokens, cfg)
    assert np.array_equal(calib.phi, phi) and np.array_equal(calib.w, w)
    for got, want in zip(calib.moe_inputs, inputs):
        assert np.array_equal(got, want)
    assert eps.max() > 0 and np.array_equal(eps, eps_full)


def test_pass_holds_no_layer_of_experts(monkeypatch):
    """Beside the model, the pass's live device arrays stay under the
    compressed output plus a few experts' float32 copies: no layer of
    experts (8 here) is ever copied, in any phase."""
    from repro.core import compressed_moe
    from repro.models import moe as moe_mod

    cfg = dataclasses.replace(CFG, d_ff_expert=512)
    params = get_model(cfg).init(jax.random.PRNGKey(4))
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(0, cfg.vocab_size, (2, 32)), jnp.int32)
    live = lambda: sum(a.nbytes for a in jax.live_arrays())
    samples = []

    def sampled(fn):
        def wrapper(*a, **kw):
            samples.append(live())
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(moe_mod, "expert_ffn", sampled(moe_mod.expert_ffn))
    monkeypatch.setattr(pipeline, "quantize_to_packed",
                        sampled(pipeline.quantize_to_packed))
    monkeypatch.setattr(compressed_moe, "quantize_to_packed",
                        sampled(compressed_moe.quantize_to_packed))
    base = live()
    calib = pipeline.calibrate(params, tokens, cfg)
    served, _ = pipeline.compress_for_serving(params, calib, cfg)
    expert_f32 = 3 * cfg.d_model * cfg.d_ff_expert * 4
    out = sum(a.nbytes for a in jax.tree.leaves(served["blocks"]))
    assert len(samples) > 3 * cfg.num_layers * cfg.num_experts
    assert max(samples) - base <= out + 4 * expert_f32, (
        max(samples) - base, out, expert_f32)
