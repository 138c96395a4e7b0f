"""End-to-end MC# compression pipeline (paper Fig. 3).

Orchestrates, for a *materialized* MoE model (the trained ~100M example
models and the benchmark subjects):

1. **Calibration capture** — python-loop forward over layers recording
   router statistics (phi, w) and each MoE layer's input activations.
2. **Significance** — ``eps[L, E, |bits|]`` per Eq. 6 (layer-output F-norm
   with one expert fake-quantized at a time).
3. **PMQ allocation** — Eq. 7 IP via :mod:`repro.core.pmq`.
4. **GPTQ** — per-(expert, matrix) Hessians from the expert's routed
   tokens; error-compensated quantization at the allocated width.
5. **Assembly** — bit-bucketed :class:`CompressedExperts` per layer +
   uniform ``attn_bits`` (HQQ-refined RTN) for attention/router/shared.

The compressed model evaluates through :func:`compressed_forward`
(python loop — exact per-layer bucket structure), while the dry-run uses
the stackable synthetic layout from :func:`synthetic_stacked_compressed`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import layers as L
from ..models import moe as moe_mod
from ..models import transformer as tf
from . import pmq, significance
from .compressed_moe import (
    BucketMeta,
    CompressedExperts,
    build_compressed_experts,
    compressed_moe_layer,
)
from .gptq import GPTQResult, gptq_quantize, hessian_from_inputs
from .packing import PackedTensor
from .quantizers import quantize_to_packed

__all__ = [
    "CalibrationResult",
    "calibrate",
    "compute_eps",
    "run_pmq",
    "compress_model",
    "compress_for_serving",
    "compressed_forward",
    "synthetic_stacked_compressed",
    "quantize_tree_uniform",
    "model_weight_bytes",
]


# ----------------------------------------------------------- calibration
@dataclasses.dataclass
class CalibrationResult:
    moe_inputs: List[np.ndarray]  # per layer [T, D] (inputs to MoE)
    phi: np.ndarray  # [L, E]
    w: np.ndarray  # [L, E]
    hidden_final: np.ndarray  # [T, D] (for distillation targets)


def _block_parts(p_l, x, cfg, window):
    """Attention half of a block; returns (x_after_attn, h_pre_ffn)."""
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    h = L.rms_norm(x, p_l["ln1"], cfg.norm_eps)
    a, _ = L.attention(p_l["attn"], h, cfg, positions=pos, causal=True, window=window)
    x = x + a
    h2 = L.rms_norm(x, p_l["ln2"], cfg.norm_eps)
    return x, h2


def calibrate(params, tokens: jnp.ndarray, cfg, max_tokens: int = 16384):
    """Run calibration batches through the fp model, capturing MoE inputs
    and router statistics (paper §3.2.2)."""
    assert cfg.is_moe, "calibration targets MoE archs"
    x = jnp.take(params["embed"], tokens, axis=0)
    windows = tf.layer_windows_static(cfg, tokens.shape[1])
    stats = [
        significance.RouterStats(cfg.num_experts) for _ in range(cfg.num_layers)
    ]
    moe_inputs = []
    for l, p_l in enumerate(tf.iter_blocks(params, cfg)):
        x, h2 = _block_parts(p_l, x, cfg, int(windows[l]))
        t = h2.reshape(-1, cfg.d_model)
        keep = min(max_tokens, t.shape[0])
        moe_inputs.append(np.asarray(t[:keep], np.float32))
        out = moe_mod.moe_layer(p_l["moe"], h2, cfg)
        stats[l].update(np.asarray(out.topk_idx), np.asarray(out.topk_gates))
        x = x + out.y
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return CalibrationResult(
        moe_inputs=moe_inputs,
        phi=np.stack([s.phi for s in stats]),
        w=np.stack([s.w for s in stats]),
        hidden_final=np.asarray(x.reshape(-1, cfg.d_model), np.float32),
    )


# ----------------------------------------------------------- eps (Eq. 6)
def _fake_quant_expert(ew: Dict, bits: int, group: int) -> Dict:
    out = {}
    for name, w in ew.items():
        pt = quantize_to_packed(jnp.asarray(w), bits, group=group, refine=False)
        out[name] = pt.dequantize(jnp.float32)
    return out


def compute_eps(
    params, calib: CalibrationResult, cfg,
    bit_choices=(1, 2, 3), group: int = 128, eps_tokens: int = 2048,
) -> np.ndarray:
    """``eps[L, E, |bits|]`` via Eq. 6 on captured calibration inputs."""
    L_, E = cfg.num_layers, cfg.num_experts
    eps = np.zeros((L_, E, len(bit_choices)))
    for l, p_l in enumerate(tf.iter_blocks(params, cfg)):
        h2 = jnp.asarray(calib.moe_inputs[l][:eps_tokens])[None]  # [1, T, D]
        experts = p_l["moe"]["experts"]
        ew_list = [
            {k: experts[k][i] for k in ("w_gate", "w_up", "w_down")}
            for i in range(E)
        ]

        def layer_forward(expert_list):
            stacked = {
                k: jnp.stack([e[k] for e in expert_list])
                for k in ("w_gate", "w_up", "w_down")
            }
            p_mod = dict(p_l["moe"], experts=stacked)
            return moe_mod.moe_layer(p_mod, h2, cfg).y

        eps[l] = significance.expert_eps(
            layer_forward,
            ew_list,
            lambda ew, b: _fake_quant_expert(ew, b, group),
            bit_choices,
        )
    return eps


# ------------------------------------------------------------------- PMQ
def run_pmq(
    params, calib: CalibrationResult, cfg,
    target_avg_bits: float = 2.25,
    bit_choices=(1, 2, 3),
    solver: str = "dp",
    eps: Optional[np.ndarray] = None,
    layer_adaptive: bool = False,
) -> pmq.PMQPlan:
    q = cfg.quant
    if eps is None:
        eps = compute_eps(params, calib, cfg, bit_choices, q.group)
    return pmq.allocate_model(
        calib.phi, calib.w, eps, target_avg_bits,
        alpha=q.alpha, beta=q.beta, gamma=q.gamma,
        bit_choices=bit_choices, solver=solver, layer_adaptive=layer_adaptive,
    )


# ---------------------------------------------------------------- GPTQ
def _routed_inputs(h2: np.ndarray, idx: np.ndarray, expert: int) -> np.ndarray:
    rows = np.any(idx == expert, axis=1)
    x = h2[rows]
    if x.shape[0] < 8:  # never-routed expert: fall back to all tokens
        x = h2
    return x


def _gptq_expert(ew: Dict, x: np.ndarray, bits: int, group: int) -> Dict:
    """GPTQ all three matrices of one expert given its routed inputs."""
    res = {}
    hg = hessian_from_inputs(x)
    for name in ("w_gate", "w_up"):
        res[name] = gptq_quantize(np.asarray(ew[name]), hg, bits, group)
    # down-proj sees silu(xWg)*(xWu)
    a = x @ np.asarray(ew["w_gate"], np.float64)
    a = a / (1.0 + np.exp(-a)) * (x @ np.asarray(ew["w_up"], np.float64))
    res["w_down"] = gptq_quantize(
        np.asarray(ew["w_down"]), hessian_from_inputs(a), bits, group
    )
    return res


def compress_model(
    params, calib: CalibrationResult, plan: pmq.PMQPlan, cfg,
    use_gptq: bool = True, ep: int = 1, gptq_tokens: int = 2048,
):
    """Produce the compressed parameter tree (python-loop layout).

    Returns ``(blocks_c, top)`` where ``blocks_c[l]`` holds
    ``{"ln1","attn","ln2","moe"(router/shared 4-bit),"moe_ce"}`` and
    ``top`` carries embed/final_norm (embeddings stay 16-bit, as in the
    paper's average-bit accounting).
    """
    q = cfg.quant
    blocks_c = []
    for l, p_l in enumerate(tf.iter_blocks(params, cfg)):
        h2 = calib.moe_inputs[l][:gptq_tokens].astype(np.float64)
        experts = p_l["moe"]["experts"]
        gptq_results = None
        if use_gptq:
            # routing of calibration tokens under the fp router
            _, idx, _ = moe_mod.route_topk(
                p_l["moe"]["router"], jnp.asarray(h2, jnp.float32), cfg.top_k
            )
            idx = np.asarray(idx)
            gptq_results = {}
            for i in range(cfg.num_experts):
                ew = {k: np.asarray(experts[k][i]) for k in ("w_gate", "w_up", "w_down")}
                res = _gptq_expert(
                    ew, _routed_inputs(h2, idx, i), int(plan.bits[l][i]), q.group
                )
                for name, r in res.items():
                    gptq_results[(i, name)] = r
        ce = build_compressed_experts(
            {k: np.asarray(experts[k]) for k in ("w_gate", "w_up", "w_down")},
            plan.bits[l], group=q.group, ep=ep, gptq_results=gptq_results,
        )
        moe_p = {"router": p_l["moe"]["router"]}
        if "shared" in p_l["moe"]:
            moe_p["shared"] = quantize_tree_uniform(
                p_l["moe"]["shared"], q.attn_bits, q.group
            )
        blk = {
            "ln1": p_l["ln1"],
            "attn": quantize_tree_uniform(p_l["attn"], q.attn_bits, q.group),
            "ln2": p_l["ln2"],
            "moe": moe_p,
            "moe_ce": ce,
        }
        blocks_c.append(blk)
    top = {
        "embed": params["embed"],
        "final_norm": params["final_norm"],
    }
    if "unembed" in params:
        top["unembed"] = params["unembed"]
    return blocks_c, top


def compress_for_serving(
    params, calib: CalibrationResult, cfg, *,
    target_avg_bits: float = 2.05, eps_tokens: int = 128,
) -> Tuple[Dict, float]:
    """Layer-uniform PMQ compression in the *stacked* serving layout.

    The PMQ plan is made layer-uniform (every layer gets layer 0's bit
    vector) so all layers share one bucket structure and ride the decode
    scan — the layout ``repro.serving`` and the serving benchmarks
    consume. Returns ``(params_compressed, avg_bits)`` where the tree
    carries ``blocks`` restacked for :mod:`repro.models.transformer`.
    """
    eps = compute_eps(params, calib, cfg, eps_tokens=eps_tokens)
    plan = run_pmq(params, calib, cfg, target_avg_bits=target_avg_bits,
                   eps=eps)
    plan.bits = [plan.bits[0]] * cfg.num_layers
    blocks_c, top = compress_model(params, calib, plan, cfg, use_gptq=False)
    out = dict(top)
    out["blocks"] = tf.restack_blocks(blocks_c)
    return out, plan.avg_bits


def quantize_tree_uniform(tree, bits: int, group: int):
    """Replace every 2-D ``w`` leaf with a PackedTensor (HQQ-refined RTN)."""

    def one(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if name == "w" and getattr(leaf, "ndim", 0) == 2:
            return quantize_to_packed(leaf, bits, group=group, refine=True)
        return leaf

    return jax.tree_util.tree_map_with_path(one, tree)


# ----------------------------------------------------- compressed forward
def compressed_forward(
    blocks_c, top, tokens: jnp.ndarray, cfg,
    otp_params: Optional[List] = None, otp_rngs=None, otp_tau: float = 1.0,
    collect_masks: bool = False,
):
    """Python-loop forward of the compressed model → (hidden, masks)."""
    x = jnp.take(top["embed"], tokens, axis=0)
    windows = tf.layer_windows_static(cfg, tokens.shape[1])
    masks = []
    for l, p_l in enumerate(blocks_c):
        x, h2 = _block_parts(p_l, x, cfg, int(windows[l]))
        y, info = compressed_moe_layer(
            p_l["moe"], p_l["moe_ce"], h2, cfg,
            otp_params=otp_params[l] if otp_params is not None else None,
            otp_rng=otp_rngs[l] if otp_rngs is not None else None,
            otp_tau=otp_tau,
        )
        x = x + y
        if collect_masks and info["mask"] is not None:
            masks.append(info["mask"])
    x = L.rms_norm(x, top["final_norm"], cfg.norm_eps)
    return x, masks


def compressed_logits(blocks_c, top, tokens, cfg, **kw):
    hidden, masks = compressed_forward(blocks_c, top, tokens, cfg, **kw)
    emb = top.get("unembed", top["embed"])
    logits = jnp.einsum(
        "btd,vd->btv", hidden.astype(jnp.float32), emb.astype(jnp.float32)
    )
    return logits, masks


# --------------------------------------------------- dry-run synthetic CE
def synthetic_stacked_compressed(cfg, target_avg_bits: float = 2.25, ep: int = 16):
    """L-stacked CompressedExperts with identical bucket structure per
    layer (dry-run only; built under eval_shape → no allocation).

    Bucket counts are multiples of the expert-parallel extent ``ep`` (so
    the EP scan in :func:`compressed_expert_ffn` shards cleanly) solving
    ``1·a + 2·b + 3·c ≈ target`` with the paper's ≥1-expert floors.
    """
    e = cfg.num_experts
    if e % ep:
        ep = 1
    # search bucket sizes on the ep grid closest to the bit budget
    best, best_err = None, float("inf")
    for n1 in range(ep, e - ep + 1, ep):
        for n3 in range(ep, e - n1 - ep + 1, ep):
            n2 = e - n1 - n3
            avg = (n1 + 2 * n2 + 3 * n3) / e
            err = abs(avg - target_avg_bits)
            if err < best_err:
                best, best_err = (n1, n2, n3), err
    n1, n2, n3 = best
    d, f, group = cfg.d_model, cfg.d_ff_expert, cfg.quant.group
    l = cfg.num_layers
    meta = []
    arrays = {}
    start = 0
    for bits, cnt in ((1, n1), (2, n2), (3, n3)):
        if cnt == 0:
            continue
        bdict = {}
        for name, (k, n) in (
            ("w_gate", (d, f)), ("w_up", (d, f)), ("w_down", (f, d))
        ):
            # bf16 scales/zeros at deployment: 0.25 bits/weight overhead
            # (HQQ stores fp16 scales; kimi-scale f32 scales alone = 64 GB)
            entry = {
                "scale": jnp.zeros((l, cnt, (k + group - 1) // group, n), jnp.bfloat16),
                "zero": jnp.zeros((l, cnt, (k + group - 1) // group, n), jnp.bfloat16),
            }
            if bits == 3:
                entry["hi"] = jnp.zeros((l, cnt, k // 4, n), jnp.uint8)
                entry["lo"] = jnp.zeros((l, cnt, k // 8, n), jnp.uint8)
            else:
                per = 8 // bits
                entry["data"] = jnp.zeros((l, cnt, k // per, n), jnp.uint8)
            bdict[name] = entry
        arrays[f"b{len(meta)}"] = bdict
        meta.append(BucketMeta(bits=bits, start=start, count=cnt))
        start += cnt
    slot = jnp.tile(jnp.arange(e, dtype=jnp.int32)[None], (l, 1))
    return CompressedExperts(
        meta=tuple(meta), slot_of_expert=slot, arrays=arrays,
        num_slots=start, group=group, d_model=d, d_ff=f,
    )


def model_weight_bytes(blocks_c, top) -> int:
    """Total compressed weight bytes (PackedTensor-aware)."""
    tot = 0

    def add(leaf):
        nonlocal tot
        if isinstance(leaf, PackedTensor):
            tot += leaf.nbytes
        elif isinstance(leaf, CompressedExperts):
            tot += leaf.weight_bytes
        elif hasattr(leaf, "nbytes"):
            tot += leaf.nbytes

    for blk in blocks_c:
        jax.tree.map(
            add, blk,
            is_leaf=lambda x: isinstance(x, (PackedTensor, CompressedExperts)),
        )
    jax.tree.map(add, top)
    return tot
