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

Memory: the pass reads the stacked full-precision tree in place. Beside
it, the device holds one expert's temporaries and, in step 5, the layer
being packed: steps 1–2 run each MoE layer one expert at a time, and
step 5 packs each expert on its device, the finished layers waiting on
the host. Each phase is a span (:class:`PassLog`).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import layers as L
from ..models import moe as moe_mod
from ..models import transformer as tf
from ..trace import SpanTracer
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
    "PassLog",
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


# ------------------------------------------------------- the pass's record
@dataclasses.dataclass
class PassLog:
    """What one PMQ pass cost, phase by phase.

    Each phase is a span of ``tracer`` on track ``pmq``: ``pmq.calibrate``
    (the whole capture), ``pmq.eps`` and ``pmq.pack`` (one per layer),
    ``pmq.plan`` (the allocation). A span is a ``jax.profiler``
    annotation of the same name at every trace level, and an event of
    the tracer's record from level ``spans`` up. ``seconds`` sums each
    phase's spans over every phase run on this calibration (a sweep that
    packs one calibration several times adds them up); ``peak_bytes[l]``
    is the device's ``peak_bytes_in_use`` once layer ``l`` of the latest
    packing is done (None where the device keeps no memory statistics,
    as the CPU does).
    """

    tracer: SpanTracer = dataclasses.field(
        default_factory=lambda: SpanTracer("off"))
    seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    peak_bytes: List[Optional[int]] = dataclasses.field(default_factory=list)

    @contextlib.contextmanager
    def phase(self, name: str, **args):
        with self.tracer.span(name, track="pmq", cat="pmq", **args) as sp:
            yield sp
        self.seconds[name] = self.seconds.get(name, 0.0) + sp.seconds


def _peak_bytes(leaf) -> Optional[int]:
    stats = next(iter(leaf.devices())).memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


# ----------------------------------------------------------- calibration
@dataclasses.dataclass
class CalibrationResult:
    moe_inputs: List[np.ndarray]  # per layer [T, D] (inputs to MoE)
    phi: np.ndarray  # [L, E]
    w: np.ndarray  # [L, E]
    hidden_final: np.ndarray  # [T, D] (for distillation targets)
    log: PassLog = dataclasses.field(default_factory=PassLog)


# The routed experts are the pass's only large leaves (a Mixtral-8x7B
# layer holds 2.8 GB of them in bf16). The pass never copies a layer of
# them: each layer's other leaves are sliced whole, each expert's three
# matrices one expert at a time, and each piece is dropped before the next.
# JAX dispatches ahead of the device, and a dropped buffer is freed only
# once the device has run what reads it, so each expert's work is waited
# for before the next expert's is dispatched: without the wait, several
# experts' temporaries were live at once (3 GB more at Mixtral's widths).
_EXPERT_MATS = ("w_gate", "w_up", "w_down")


def _layer(params, l: int) -> Dict:
    """Layer ``l``'s block without its routed experts."""
    blocks = dict(params["blocks"])
    moe = {k: v for k, v in blocks.pop("moe").items() if k != "experts"}
    p_l = jax.tree.map(lambda a: a[l], blocks)
    p_l["moe"] = jax.tree.map(lambda a: a[l], moe)
    return p_l


def _expert(params, l: int, i: int) -> Dict:
    """Expert ``i`` of layer ``l``: its three matrices, sliced alone."""
    ex = params["blocks"]["moe"]["experts"]
    return {k: ex[k][l, i] for k in _EXPERT_MATS}


class _LayerExperts:
    """``view[i]`` is expert ``i`` of one layer of a stacked ``[L, E, ...]``
    leaf, sliced when asked for (what :func:`build_compressed_experts`
    reads of its ``experts``)."""

    def __init__(self, stacked, layer: int):
        self.stacked, self.layer = stacked, layer
        self.shape = stacked.shape[1:]

    def __getitem__(self, i: int):
        return self.stacked[self.layer, i]


def _expert_rows(ew: Dict, xp, i: int, cap: int):
    """Expert ``i``'s SwiGLU on its ``cap`` rows of the capacity layout
    (one expert of ``moe_mod.expert_ffn``), waited for."""
    return jax.block_until_ready(moe_mod.expert_ffn(
        {k: w[None] for k, w in ew.items()}, xp[i * cap:(i + 1) * cap], 1))


def _streamed_ffn(params, l: int, E: int, rows: List, probe=None):
    """``moe_mod.moe_layer``'s ``expert_ffn_fn`` for layer ``l``: the
    experts' rows one expert at a time. An empty ``rows`` is filled with
    every expert's rows; a filled one is reused, expert ``i``'s rows
    recomputed with the weights ``ew`` where ``probe = (i, ew)``."""
    def ffn(xp):
        cap = xp.shape[0] // E
        if not rows:
            rows.extend(_expert_rows(_expert(params, l, i), xp, i, cap)
                        for i in range(E))
        yp = list(rows)
        if probe is not None:
            i, ew = probe
            yp[i] = _expert_rows(ew, xp, i, cap)
        return jnp.concatenate(yp)
    return ffn


def _block_parts(p_l, x, cfg, window):
    """Attention half of a block; returns (x_after_attn, h_pre_ffn)."""
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    h = L.rms_norm(x, p_l["ln1"], cfg.norm_eps)
    a, _ = L.attention(p_l["attn"], h, cfg, positions=pos, causal=True, window=window)
    x = x + a
    h2 = L.rms_norm(x, p_l["ln2"], cfg.norm_eps)
    return x, h2


def calibrate(params, tokens: jnp.ndarray, cfg, max_tokens: int = 16384,
              tracer: Optional[SpanTracer] = None):
    """Run calibration batches through the fp model, capturing MoE inputs
    and router statistics (paper §3.2.2). The result's ``log`` records
    this phase and, when the result is passed on, the later ones, on
    ``tracer`` (a fresh ``off`` tracer when None)."""
    assert cfg.is_moe, "calibration targets MoE archs"
    log = PassLog(tracer) if tracer is not None else PassLog()
    with log.phase("pmq.calibrate"):
        x = jnp.take(params["embed"], tokens, axis=0)
        windows = tf.layer_windows_static(cfg, tokens.shape[1])
        stats = [
            significance.RouterStats(cfg.num_experts) for _ in range(cfg.num_layers)
        ]
        moe_inputs = []
        for l in range(cfg.num_layers):
            p_l = _layer(params, l)
            x, h2 = _block_parts(p_l, x, cfg, int(windows[l]))
            t = h2.reshape(-1, cfg.d_model)
            keep = min(max_tokens, t.shape[0])
            moe_inputs.append(np.asarray(t[:keep], np.float32))
            out = moe_mod.moe_layer(
                p_l["moe"], h2, cfg,
                expert_ffn_fn=_streamed_ffn(params, l, cfg.num_experts, []))
            stats[l].update(np.asarray(out.topk_idx), np.asarray(out.topk_gates))
            x = x + out.y
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        hidden = np.asarray(x.reshape(-1, cfg.d_model), np.float32)
    return CalibrationResult(
        moe_inputs=moe_inputs,
        phi=np.stack([s.phi for s in stats]),
        w=np.stack([s.w for s in stats]),
        hidden_final=hidden,
        log=log,
    )


# ----------------------------------------------------------- eps (Eq. 6)
def _fake_quant_expert(ew: Dict, bits: int, group: int) -> Dict:
    out = {}
    for name, w in ew.items():  # one matrix's temporaries at a time
        pt = quantize_to_packed(jnp.asarray(w), bits, group=group, refine=False)
        out[name] = jax.block_until_ready(pt.dequantize(jnp.float32))
    return out


def compute_eps(
    params, calib: CalibrationResult, cfg,
    bit_choices=(1, 2, 3), group: int = 128, eps_tokens: int = 2048,
) -> np.ndarray:
    """``eps[L, E, |bits|]`` via Eq. 6 on captured calibration inputs:
    ``eps[l, i, j] = ||F(theta) - F(theta[e_i -> Q(e_i, j)])||_F``, the
    F-norm between layer ``l``'s MoE output with full-precision experts
    and with only expert ``i`` fake-quantized to ``bit_choices[j]`` bits.

    Routing does not depend on the experts, so a probe changes only
    expert ``i``'s rows of the capacity layout: each probe recomputes
    those rows and reuses the other experts' unchanged ones, one
    expert's weights at a time.
    """
    L_, E = cfg.num_layers, cfg.num_experts
    eps = np.zeros((L_, E, len(bit_choices)))
    for l in range(L_):
        with calib.log.phase("pmq.eps", layer=l):
            p_moe = _layer(params, l)["moe"]
            h2 = jnp.asarray(calib.moe_inputs[l][:eps_tokens])[None]  # [1, T, D]
            rows: List = []  # the full-precision experts' rows

            def layer_forward(probe=None):
                return moe_mod.moe_layer(
                    p_moe, h2, cfg,
                    expert_ffn_fn=_streamed_ffn(params, l, E, rows, probe)).y

            base = layer_forward()
            for i in range(E):
                ew = _expert(params, l, i)
                for j, bits in enumerate(bit_choices):
                    out = layer_forward((i, _fake_quant_expert(ew, bits, group)))
                    eps[l, i, j] = float(jnp.linalg.norm(
                        (out - base).astype(jnp.float32)))
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
    with calib.log.phase("pmq.plan"):
        return pmq.allocate_model(
            calib.phi, calib.w, eps, target_avg_bits,
            alpha=q.alpha, beta=q.beta, gamma=q.gamma,
            bit_choices=bit_choices, solver=solver,
            layer_adaptive=layer_adaptive,
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


def _compressed_layers(params, calib: CalibrationResult, plan: pmq.PMQPlan,
                       cfg, use_gptq: bool, ep: int, gptq_tokens: int):
    """Yield each layer's compressed block in turn (see
    :func:`compress_model`); the generator keeps no layer it has yielded."""
    q = cfg.quant
    stacked = params["blocks"]["moe"]["experts"]
    calib.log.peak_bytes = []
    for l in range(cfg.num_layers):
        with calib.log.phase("pmq.pack", layer=l):
            p_l = _layer(params, l)
            gptq_results = None
            if use_gptq:
                h2 = calib.moe_inputs[l][:gptq_tokens].astype(np.float64)
                # routing of calibration tokens under the fp router
                _, idx, _ = moe_mod.route_topk(
                    p_l["moe"]["router"], jnp.asarray(h2, jnp.float32),
                    cfg.top_k)
                idx = np.asarray(idx)
                gptq_results = {}
                for i in range(cfg.num_experts):
                    ew = {k: np.asarray(w)
                          for k, w in _expert(params, l, i).items()}
                    res = _gptq_expert(
                        ew, _routed_inputs(h2, idx, i), int(plan.bits[l][i]),
                        q.group)
                    for name, r in res.items():
                        gptq_results[(i, name)] = r
            ce = build_compressed_experts(
                {k: _LayerExperts(stacked[k], l) for k in _EXPERT_MATS},
                plan.bits[l], group=q.group, ep=ep, gptq_results=gptq_results,
            )
            moe_p = {"router": p_l["moe"]["router"]}
            if "shared" in p_l["moe"]:
                moe_p["shared"] = quantize_tree_uniform(
                    p_l["moe"]["shared"], q.attn_bits, q.group
                )
            blk = jax.block_until_ready({
                "ln1": p_l["ln1"],
                "attn": quantize_tree_uniform(p_l["attn"], q.attn_bits, q.group),
                "ln2": p_l["ln2"],
                "moe": moe_p,
                "moe_ce": ce,
            })
            del p_l, ce, moe_p
        calib.log.peak_bytes.append(_peak_bytes(stacked["w_gate"]))
        yield blk
        del blk


def _top(params) -> Dict:
    return {k: params[k] for k in ("embed", "final_norm", "unembed")
            if k in params}


def compress_model(
    params, calib: CalibrationResult, plan: pmq.PMQPlan, cfg,
    use_gptq: bool = True, ep: int = 1, gptq_tokens: int = 2048,
):
    """Produce the compressed parameter tree (python-loop layout).

    Returns ``(blocks_c, top)`` where ``blocks_c[l]`` holds
    ``{"ln1","attn","ln2","moe"(router/shared 4-bit),"moe_ce"}`` and
    ``top`` carries embed/final_norm (embeddings stay 16-bit, as in the
    paper's average-bit accounting). Experts are packed on their device
    one at a time.
    """
    blocks_c = list(_compressed_layers(params, calib, plan, cfg, use_gptq,
                                       ep, gptq_tokens))
    return blocks_c, _top(params)


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
    The phases' spans, seconds and device peaks go to ``calib.log``.

    Beside ``params`` the device holds one expert's temporaries and one
    compressed layer (the finished ones wait on the host): no layer of
    experts is copied.
    """
    eps = compute_eps(params, calib, cfg, eps_tokens=eps_tokens)
    plan = run_pmq(params, calib, cfg, target_avg_bits=target_avg_bits,
                   eps=eps)
    plan.bits = [plan.bits[0]] * cfg.num_layers
    # each compressed layer waits on the host until all are made, so the
    # device holds the full-precision tree and one layer's work at a time
    blocks_c = [jax.device_get(blk) for blk in _compressed_layers(
        params, calib, plan, cfg, use_gptq=False, ep=1, gptq_tokens=0)]
    out = _top(params)
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
