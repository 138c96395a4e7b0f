"""Shared benchmark infrastructure.

All paper-table benchmarks run against a *trained* small MoE LM (random
models make quality metrics meaningless — see tests). The model trains
once on the synthetic corpus and is cached under results/bench_model.
Relative claims (strategy orderings, Pareto shape, OTP-vs-random) are
scale-free, which is what the tables assert.
"""
from __future__ import annotations

import os
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig
from repro.core import pipeline
from repro.data.pipeline import HostDataLoader, make_calibration_tokens
from repro.models.registry import get_model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.schedule import warmup_cosine

BENCH_CFG = ModelConfig(
    name="bench-moe-16m",
    family="moe",
    num_layers=4,
    d_model=256,
    num_heads=4,
    num_kv_heads=2,
    head_dim=64,
    d_ff=512,
    d_ff_expert=512,
    vocab_size=512,  # small vocab → the 512K-token budget actually learns the corpus
    num_experts=8,
    top_k=2,
    num_shared_experts=1,
    dtype="float32",
    remat="none",
    logits_chunk=64,
    attn_q_chunk=128,
    attn_kv_chunk=128,
    moe_capacity_factor=2.0,
)

CKPT_DIR = "results/bench_model"
_STATE: Dict = {}


def trained_model(steps: int = 250, force: bool = False):
    """Train (or load) the benchmark MoE. Returns (cfg, params)."""
    if "params" in _STATE and not force:
        return BENCH_CFG, _STATE["params"]
    cfg = BENCH_CFG
    bundle = get_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    ckpt = Checkpointer(CKPT_DIR, keep=1)
    last = ckpt.latest_step()
    if last is not None and not force:
        params = ckpt.restore(last, {"params": params})["params"]
        _STATE["params"] = params
        return cfg, params
    ocfg = AdamWConfig(lr=2e-3)
    opt = adamw_init(params, ocfg)
    loader = HostDataLoader(vocab=cfg.vocab_size, global_batch=16, seq_len=128)

    @jax.jit
    def step_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: bundle.train_loss(p, batch)[0]
        )(params)
        sc = warmup_cosine(opt["step"], warmup=20, total=steps)
        params, opt = adamw_update(params, grads, opt, ocfg, sc)
        return params, opt, loss

    t0 = time.time()
    for step in range(steps):
        batch = {k: jnp.asarray(v) for k, v in loader.batch_at(step).items()}
        params, opt, loss = step_fn(params, opt, batch)
        if step % 50 == 0:
            print(f"  [bench-train] step {step} loss {float(loss):.3f}")
    print(f"  [bench-train] done in {time.time()-t0:.0f}s "
          f"final loss {float(loss):.3f}")
    ckpt.save(steps - 1, {"params": params}, blocking=True)
    ckpt.wait()
    _STATE["params"] = params
    return cfg, params


def calibration(cfg, params, n: int = 16, seq: int = 128):
    key = ("calib", n, seq)
    if key not in _STATE:
        toks = jnp.asarray(make_calibration_tokens(cfg.vocab_size, n, seq))
        _STATE[key] = pipeline.calibrate(params, toks, cfg)
    return _STATE[key]


def eval_tokens(cfg, n: int = 16, seq: int = 128) -> jnp.ndarray:
    return jnp.asarray(
        make_calibration_tokens(cfg.vocab_size, n, seq, seed=999)
    )


def ppl_fp(cfg, params, tokens) -> float:
    from repro.models import transformer as tf
    from repro.models import layers as L

    hidden, _, _ = tf.forward_hidden(params, tokens[:, :-1], cfg)
    emb = params.get("unembed", params["embed"])
    nll = L.chunked_xent(hidden, emb, tokens[:, 1:], cfg.logits_chunk)
    return float(jnp.exp(nll))


def ppl_compressed(cfg, blocks_c, top, tokens, otp_params=None) -> float:
    from repro.models import layers as L

    hidden, _ = pipeline.compressed_forward(
        blocks_c, top, tokens[:, :-1], cfg, otp_params=otp_params
    )
    emb = top.get("unembed", top["embed"])
    nll = L.chunked_xent(hidden, emb, tokens[:, 1:], cfg.logits_chunk)
    return float(jnp.exp(nll))


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    row = f"{name},{us_per_call:.1f},{derived}"
    print(row)
    return row


def platform_meta(ffn_backend: str | None = None) -> Dict[str, object]:
    """Provenance stamp for every bench leg: which backend and devices
    produced the numbers, so a CPU leg is never read as a chip one.
    ``ffn_backend`` records the expert-FFN implementation the leg ran
    (CLI flag or REPRO_FFN_BACKEND; 'default' when unpinned)."""
    dev = jax.devices()[0]
    return {
        "platform": str(jax.default_backend()),
        "device_platform": str(dev.platform),
        "device_kind": str(dev.device_kind),
        "device_count": len(jax.devices()),
        "ffn_backend": str(
            ffn_backend or os.environ.get("REPRO_FFN_BACKEND") or "default"
        ),
    }
