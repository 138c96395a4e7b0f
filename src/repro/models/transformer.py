"""Decoder-only LM covering dense, MoE and VLM-backbone families.

One stacked-parameter ``lax.scan`` over layers (compile time independent of
depth — essential for 62-layer × 512-device dry-runs). Mixed local/global
attention (gemma3 5:1) rides the same scan via a traced per-layer window
(global layers get window = S+1). The MoE path plugs the capacity
dispatch from :mod:`repro.models.moe`; the PMQ/OTP compressed path swaps
``expert_ffn_fn`` / ``gate_mask_fn`` (see :mod:`repro.core.compressed_moe`).

Modes: ``train_loss`` (chunked xent), ``prefill`` (build KV cache, last
logits), ``decode_step`` (one token, donated cache).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.quantizers import dequantize_kv_rows, quantize_kv_rows
from ..kernels import ops
from ..parallel.sharding import shard
from . import layers as L
from .moe import init_moe, moe_layer

__all__ = [
    "init_lm",
    "train_loss",
    "prefill",
    "decode_step",
    "paged_decode_step",
    "paged_decode_horizon",
    "paged_prefill_chunk",
    "forward_hidden",
    "layer_windows",
]


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def layer_windows_static(cfg, s: int):
    """Per-layer effective window as a host numpy array (python loops)."""
    import numpy as np

    idx = np.arange(cfg.num_layers)
    if cfg.local_global_ratio > 0 and cfg.local_window > 0:
        is_global = (idx % (cfg.local_global_ratio + 1)) == cfg.local_global_ratio
        return np.where(is_global, s + 1, cfg.local_window).astype(np.int32)
    if cfg.local_window > 0:
        return np.full((cfg.num_layers,), cfg.local_window, np.int32)
    return np.full((cfg.num_layers,), s + 1, np.int32)


def layer_windows(cfg, s: int) -> jnp.ndarray:
    """Per-layer effective window (traced into the scan)."""
    return jnp.asarray(layer_windows_static(cfg, s))


# ------------------------------------------------------------------- init
def init_lm(rng, cfg) -> Dict[str, Any]:
    dt = _dtype(cfg)
    k_emb, k_blocks, k_out = jax.random.split(rng, 3)

    def init_block(k):
        ka, km = jax.random.split(k)
        p = {
            "ln1": jnp.zeros((cfg.d_model,), dt),
            "attn": L.init_attention(ka, cfg, dt),
            "ln2": jnp.zeros((cfg.d_model,), dt),
        }
        if cfg.is_moe:
            p["moe"] = init_moe(km, cfg, dt)
        else:
            p["mlp"] = L.init_mlp(km, cfg.d_model, cfg.d_ff, dt)
        return p

    blocks = jax.vmap(init_block)(jax.random.split(k_blocks, cfg.num_layers))
    params = {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model), dt) * 0.02,
        "blocks": blocks,
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (
            jax.random.normal(k_out, (cfg.vocab_size, cfg.d_model), dt) * 0.02
        )
    return params


def _out_embedding(params):
    return params.get("unembed", params["embed"])


# ------------------------------------------------------------ block body
def _block(p, x, cfg, *, positions, window, moe_hooks=None):
    """One transformer block (full-sequence). Returns (x, aux, kv).

    Sequence-parallel discipline (Megatron-SP): the residual stream is
    seq-sharded ("act_btd"); attention/FFN regions run on the gathered
    layout ("act_full") — one AG entering, one RS leaving per region.
    """
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    h = shard(h, "act_full")
    attn_out, kv = L.attention(
        p["attn"], h, cfg, positions=positions, causal=True, window=window
    )
    attn_out = shard(attn_out, "act_btd")
    x = x + attn_out
    x = shard(x, "act_btd")
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    h = shard(h, "act_full")
    aux = jnp.float32(0)
    if cfg.is_moe:
        if "moe_ce" in p:  # PMQ-compressed experts (+ optional OTP)
            from ..core.compressed_moe import compressed_moe_layer

            hooks = moe_hooks or {}
            use_otp = hooks.get("use_otp", True)
            y, info = compressed_moe_layer(
                p["moe"], p["moe_ce"], h, cfg,
                otp_params=p.get("otp") if use_otp else None,
                otp_rng=hooks.get("otp_rng"),
                otp_tau=hooks.get("otp_tau", 1.0),
                ffn_backend=hooks.get("ffn_backend"),
            )
            # save the region output across remat: recomputing it would
            # re-all-gather the packed expert weights in the backward pass
            from jax.ad_checkpoint import checkpoint_name

            y = checkpoint_name(y, "moe_out")
            x = x + y
            if info.get("mask_l1") is not None:
                aux = info["mask_l1"]  # ℓ1 term channel (Eq. 14)
        else:
            hooks = moe_hooks or {}
            out = moe_layer(
                p["moe"], h, cfg,
                gate_mask_fn=hooks.get("gate_mask_fn"),
                expert_ffn_fn=hooks.get("expert_ffn_fn"),
            )
            x = x + out.y
            aux = out.aux_loss
    else:
        x = x + shard(L.mlp(p["mlp"], h), "act_btd")
    x = shard(x, "act_btd")
    return x, aux, kv


def _embed_inputs(params, cfg, tokens, patch_embeds=None):
    x = L.embed_tokens(params["embed"], tokens)
    if cfg.family == "vlm" and patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    return x


def forward_hidden(
    params,
    tokens: jnp.ndarray,
    cfg,
    *,
    patch_embeds: Optional[jnp.ndarray] = None,
    collect_cache: bool = False,
    moe_hooks=None,
):
    """Run all blocks; returns (hidden [B,S,D], aux_loss, cache|None)."""
    x = _embed_inputs(params, cfg, tokens, patch_embeds)
    b, s, d = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    windows = layer_windows(cfg, s)

    def body(carry, xs):
        xc, aux = carry
        p_l, win = xs
        xn, a, kv = _block(
            p_l, xc, cfg, positions=positions, window=win, moe_hooks=moe_hooks
        )
        ys = kv if collect_cache else None
        return (xn, aux + a), ys

    body_fn = body
    if cfg.remat == "block":
        body_fn = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names("moe_out"),
        )
    (x, aux), kvs = jax.lax.scan(body_fn, (x, jnp.float32(0)), (params["blocks"], windows))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    cache = None
    if collect_cache:
        cache = {"k": kvs[0], "v": kvs[1]}  # [L, B, S, Hkv, dh]
    return x, aux, cache


# ------------------------------------------------------------------ train
def train_loss(params, batch, cfg, *, moe_hooks=None, aux_weight: float = 0.01):
    tokens = batch["tokens"]
    labels = batch["labels"]
    patch = batch.get("patch_embeds")
    hidden, aux, _ = forward_hidden(
        params, tokens, cfg, patch_embeds=patch, moe_hooks=moe_hooks
    )
    if patch is not None:  # loss only on text positions
        hidden = hidden[:, patch.shape[1] :]
    nll = L.chunked_xent(hidden, _out_embedding(params), labels, cfg.logits_chunk)
    loss = nll + aux_weight * aux / max(cfg.num_layers, 1)
    return loss, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------- serving
def prefill(params, batch, cfg, *, moe_hooks=None, paged=None):
    """Build a KV cache of the prompt; return (cache, last-token logits).

    With ``paged={"cache": <paged layout>, "start": s, "valid_len": n}``
    the prompt chunk is written into the paged pool instead of building a
    dense cache (see :func:`paged_prefill_chunk`).
    """
    if paged is not None:
        new_cache, logits, _ = paged_prefill_chunk(
            params, paged["cache"], batch["tokens"],
            paged.get("start", 0),
            paged.get("valid_len", batch["tokens"].shape[1]),
            cfg, moe_hooks=moe_hooks,
        )
        return new_cache, logits
    tokens = batch["tokens"]
    patch = batch.get("patch_embeds")
    hidden, _, cache = forward_hidden(
        params, tokens, cfg, patch_embeds=patch, collect_cache=True,
        moe_hooks=moe_hooks,
    )
    last = hidden[:, -1:, :]
    logits = jnp.einsum(
        "btd,vd->btv", last.astype(jnp.float32),
        _out_embedding(params).astype(jnp.float32),
    )
    cache["pos"] = jnp.int32(tokens.shape[1] + (patch.shape[1] if patch is not None else 0))
    return cache, logits


def _ffn_delta(p, h, cfg, moe_hooks=None):
    """FFN half of a decode-style block.

    Returns ``(Δx, expert_activation [B, S], slot_counts [num_slots])``.

    ``expert_activation`` is the **per-token** executed fraction of top-k
    expert slots: the mean of the OTP decode mask (deterministic argmax,
    paper §3.4 τ→0 limit) when masks are active, else 1.0. It is kept
    per token so callers can exclude padding/inactive slots before
    reducing (the paged decode step masks with ``cache["active"]``).
    ``slot_counts`` is the PMQ layer's per-permuted-slot dispatch count
    (the offload prefetcher's router statistic; empty ``[0]`` outside the
    compressed path). ``moe_hooks["count_weight"]`` ([T] bool) marks
    which tokens are real traffic; ``moe_hooks["ffn_backend"]`` selects
    the compressed expert-FFN implementation (grouped GEMM vs legacy
    scan — a static trace-time choice, so the serving engine's jitted
    programs never retrace over it). Shared by the dense and paged
    decode paths so they stay numerically identical.
    """
    ones = jnp.ones(h.shape[:2], jnp.float32)
    no_counts = jnp.zeros((0,), jnp.int32)
    if not cfg.is_moe:
        return L.mlp(p["mlp"], h), ones, no_counts
    if "moe_ce" in p:
        from ..core.compressed_moe import compressed_moe_layer

        hooks = moe_hooks or {}
        use_otp = hooks.get("use_otp", True)
        y, info = compressed_moe_layer(
            p["moe"], p["moe_ce"], h, cfg,
            otp_params=p.get("otp") if use_otp else None,
            count_weight=hooks.get("count_weight"),
            ffn_backend=hooks.get("ffn_backend"),
        )
        act = ones
        if info.get("mask") is not None:
            act = info["mask"].mean(axis=-1).reshape(h.shape[:2])
        counts = info.get("slot_counts")
        return y, act, counts if counts is not None else no_counts
    out = moe_layer(p["moe"], h, cfg)
    return out.y, ones, no_counts


def _decode_block(p, x, cfg, *, k_cache, v_cache, pos, window, moe_hooks=None):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    attn_out, (k_cache, v_cache) = L.decode_attention(
        p["attn"], h, cfg, k_cache=k_cache, v_cache=v_cache, pos=pos, window=window
    )
    x = x + attn_out
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    delta, _, _ = _ffn_delta(p, h, cfg, moe_hooks)
    x = x + delta
    return x, (k_cache, v_cache)


def decode_step(params, cache, token: jnp.ndarray, pos: jnp.ndarray, cfg,
                *, moe_hooks=None):
    """One decode step. ``token [B, 1]``, ``pos`` scalar int32 (next slot).

    Cache layout ``{"k": [L,B,S,Hkv,dh], "v": ..., "pos"}``; returns
    ``(new_cache, logits [B,1,V])``.

    The cache rides the scan **carry** (not xs/ys): XLA aliases while-loop
    carries in place, so a donated multi-GB cache is updated with a single
    [B,1,Hkv,dh] write per layer instead of double-buffering the whole
    tensor (−2× cache HBM at decode).

    A cache carrying ``"block_tables"`` is the *paged* layout
    (:mod:`repro.serving.kvcache`); it dispatches to
    :func:`paged_decode_step` with ``pos`` as per-slot positions ``[B]``.
    """
    if "block_tables" in cache:
        new_cache, logits, _ = paged_decode_step(
            params, cache, token, pos, cfg, moe_hooks=moe_hooks
        )
        return new_cache, logits
    x = L.embed_tokens(params["embed"], token)
    b = token.shape[0]
    s = cache["k"].shape[2]
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    windows = layer_windows(cfg, s)
    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)

    def body(carry, xs):
        xc, kf, vf = carry
        p_l, win, l = xs
        k_l = jax.lax.dynamic_index_in_dim(kf, l, 0, keepdims=False)
        v_l = jax.lax.dynamic_index_in_dim(vf, l, 0, keepdims=False)
        xn, (k_l2, v_l2) = _decode_block(
            p_l, xc, cfg, k_cache=k_l, v_cache=v_l, pos=pos, window=win,
            moe_hooks=moe_hooks,
        )
        # persist only the new token's K/V into the carried buffers
        k_new = jax.lax.dynamic_slice(k_l2, (0, pos, 0, 0), (b, 1, hkv, dh))
        v_new = jax.lax.dynamic_slice(v_l2, (0, pos, 0, 0), (b, 1, hkv, dh))
        kf = jax.lax.dynamic_update_slice(kf, k_new[None], (l, 0, pos, 0, 0))
        vf = jax.lax.dynamic_update_slice(vf, v_new[None], (l, 0, pos, 0, 0))
        return (xn, kf, vf), None

    (x, kf, vf), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]), (params["blocks"], windows, layer_ids)
    )
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "btd,vd->btv", x.astype(jnp.float32),
        _out_embedding(params).astype(jnp.float32),
    )
    new_cache = {"k": kf, "v": vf, "pos": pos + 1}
    return new_cache, logits


# ------------------------------------------------------- paged serving
def _paged_pool_dims(cache):
    l, nb, bs = cache["k"].shape[0], cache["k"].shape[1], cache["k"].shape[2]
    return l, nb, bs


#: Code width of quantized KV pools (int8 per-row affine — see
#: repro.core.quantizers.quantize_kv_rows and serving.kvcache).
KV_QUANT_BITS = 8

_KV_QUANT_KEYS = ("k_scale", "k_zero", "v_scale", "v_zero")


def _flatten_kv_quant(cache, nl, nb, bs, hkv):
    """``cache["kv_quant"]`` ({k,v}×{scale,zero} [L, NB, BS, Hkv]) →
    flat tuple ``(ks, kz, vs, vz)`` [L, NB·BS, Hkv], or ``()`` on fp
    pools — an empty tuple threads through scan carries untouched."""
    q = cache.get("kv_quant")
    if q is None:
        return ()
    return tuple(q[k].reshape(nl, nb * bs, hkv) for k in _KV_QUANT_KEYS)


def _unflatten_kv_quant(qs, nl, nb, bs, hkv):
    if not qs:
        return None
    return {
        k: a.reshape(nl, nb, bs, hkv)
        for k, a in zip(_KV_QUANT_KEYS, qs)
    }


def _paged_decode_core(params, kf, vf, qs, tables, token, positions, active,
                       cfg, nb, bs, *, moe_hooks=None):
    """One decode step over the *flattened* paged pools — the shared body
    of :func:`paged_decode_step` (single step) and
    :func:`paged_decode_horizon` (H fused steps): both run exactly this
    computation per step, so their logits are bit-identical step for
    step.

    ``kf``/``vf`` are ``[L, NB·BS, Hkv, dh]``; ``tables [B, MB]``;
    ``token [B, 1]``; ``positions [B]``; ``active [B]`` bool or ``None``
    (every slot then writes). ``qs`` is ``()`` for fp pools — that path
    is byte-for-byte the historical computation — or the flat per-row
    dequant tables ``(k_scale, k_zero, v_scale, v_zero)`` ``[L, NB·BS,
    Hkv]`` for int8 pools: the new token's K/V rows are quantized
    (per-row affine, deterministic in the row values alone — so
    identical tokens at identical positions produce identical codes
    regardless of batch composition) before the scatter, and attention
    reads through the kernel's dequant epilogue. Returns ``(kf, vf, qs,
    logits [B,1,V], per_slot_act [B], slot_counts [L, num_slots])`` —
    ``per_slot_act`` is the per-slot executed fraction of top-k expert
    slots (OTP decode masks), unreduced so callers can mask inactive
    slots.
    """
    x = L.embed_tokens(params["embed"], token)
    b = token.shape[0]
    nl = kf.shape[0]
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = hq // hkv
    s_log = tables.shape[1] * bs
    windows = layer_windows(cfg, s_log)
    layer_ids = jnp.arange(nl, dtype=jnp.int32)
    # flat destination of the new token's K/V; inactive slots land one
    # past the pool end and are dropped by the scatter
    page = jnp.take_along_axis(
        tables, (positions // bs)[:, None], axis=1
    )[:, 0]
    dest = page * bs + positions % bs
    if active is not None:
        dest = jnp.where(active, dest, nb * bs)
    lengths = positions + 1
    hooks = dict(moe_hooks or {})
    if active is not None:
        hooks["count_weight"] = active  # [B] = [T] at decode (S = 1)
    quantized = bool(qs)

    def body(carry, xs):
        xc, kf, vf, qs = carry
        p_l, win, l = xs
        with jax.named_scope("attention"):
            h = L.rms_norm(xc, p_l["ln1"], cfg.norm_eps)
            q, k_new, v_new = L._qkv(p_l["attn"], h, cfg, positions[:, None])
            quant_l = None
            if quantized:
                ksf, kzf, vsf, vzf = qs
                kc, ks, kz = quantize_kv_rows(k_new[:, 0], KV_QUANT_BITS)
                vc, vs, vz = quantize_kv_rows(v_new[:, 0], KV_QUANT_BITS)
                kf = kf.at[l, dest].set(kc, mode="drop")
                vf = vf.at[l, dest].set(vc, mode="drop")
                ksf = ksf.at[l, dest].set(ks, mode="drop")
                kzf = kzf.at[l, dest].set(kz, mode="drop")
                vsf = vsf.at[l, dest].set(vs, mode="drop")
                vzf = vzf.at[l, dest].set(vz, mode="drop")
                qs = (ksf, kzf, vsf, vzf)
                quant_l = tuple(
                    jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)
                    .reshape(nb, bs, hkv) for a in qs
                )
            else:
                kf = kf.at[l, dest].set(k_new[:, 0].astype(kf.dtype), mode="drop")
                vf = vf.at[l, dest].set(v_new[:, 0].astype(vf.dtype), mode="drop")
            k_l = jax.lax.dynamic_index_in_dim(kf, l, 0, keepdims=False)
            v_l = jax.lax.dynamic_index_in_dim(vf, l, 0, keepdims=False)
            attn = ops.paged_attention(
                q.reshape(b, hkv, g, dh),
                k_l.reshape(nb, bs, hkv, dh),
                v_l.reshape(nb, bs, hkv, dh),
                tables, lengths, window=win, quant=quant_l,
            )
            attn = attn.reshape(b, 1, hq * dh).astype(xc.dtype)
            xc = xc + L.linear(p_l["attn"]["wo"], attn)
        with jax.named_scope("moe"):
            h2 = L.rms_norm(xc, p_l["ln2"], cfg.norm_eps)
            delta, act, counts = _ffn_delta(p_l, h2, cfg, hooks)
            xc = xc + delta
        return (xc, kf, vf, qs), (act, counts)

    (x, kf, vf, qs), (acts, slot_counts) = jax.lax.scan(
        body, (x, kf, vf, qs), (params["blocks"], windows, layer_ids)
    )
    with jax.named_scope("head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum(
            "btd,vd->btv", x.astype(jnp.float32),
            _out_embedding(params).astype(jnp.float32),
        )
    # acts [L, B, 1] per-token: keep per-slot so garbage tokens decoded
    # by empty slots cannot dilute the OTP activation metric
    per_slot = acts.mean(axis=(0, 2))  # [B]
    return kf, vf, qs, logits, per_slot, slot_counts


def _masked_activation(per_slot, active):
    if active is None:
        return per_slot.mean()
    w = active.astype(jnp.float32)
    return jnp.sum(per_slot * w) / jnp.maximum(w.sum(), 1.0)


def paged_decode_step(params, cache, token: jnp.ndarray, positions: jnp.ndarray,
                      cfg, *, moe_hooks=None):
    """One decode step over a paged KV pool (continuous batching).

    ``cache = {"k": [L,NB,BS,Hkv,dh], "v": ..., "block_tables": [B,MB],
    "active": [B] bool}``; ``token [B,1]``; ``positions [B]`` — per-slot
    write position (slots decode at *different* logical lengths, unlike
    the dense path's single scalar ``pos``). Inactive slots compute but
    never write (their scatter destination is out of bounds → dropped),
    so freed pages can be re-used by a newly admitted request in the same
    jitted program. ``"active"`` may be omitted — every slot then writes.

    The block tables are static-shape ``[B, MB]`` rows padded with 0
    beyond each slot's allocated pages: with dynamic page growth the
    serving engine appends entries between jitted steps, and the only
    invariant this step needs is that ``tables[slot, positions[slot]//BS]``
    is an allocated page for every *active* slot (the engine grows before
    decoding). Padding entries are never read — the attention gather is
    clamped to ``lengths = positions + 1``.

    Returns ``(new_cache, logits [B,1,V], info)`` where
    ``info["expert_activation"]`` is the mean executed fraction of top-k
    expert slots across layers (OTP §3.4 decode masks make it < 1),
    reduced over **active slots only** — inactive slots decode garbage
    tokens whose masks would otherwise dilute the metric — and
    ``info["slot_counts"]`` ([L, num_slots] int32, or [L, 0] outside the
    PMQ path) counts dispatched (token, choice) pairs per permuted expert
    slot per layer, again excluding inactive slots (the serving offload
    manager's prefetch/miss signal).
    """
    nl, nb, bs = _paged_pool_dims(cache)
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    active = cache.get("active")
    qs = _flatten_kv_quant(cache, nl, nb, bs, hkv)
    kf, vf, qs, logits, per_slot, slot_counts = _paged_decode_core(
        params,
        cache["k"].reshape(nl, nb * bs, hkv, dh),
        cache["v"].reshape(nl, nb * bs, hkv, dh),
        qs, cache["block_tables"], token, positions, active, cfg, nb, bs,
        moe_hooks=moe_hooks,
    )
    new_cache = dict(
        cache,
        k=kf.reshape(nl, nb, bs, hkv, dh),
        v=vf.reshape(nl, nb, bs, hkv, dh),
    )
    if qs:
        new_cache["kv_quant"] = _unflatten_kv_quant(qs, nl, nb, bs, hkv)
    info = {
        "expert_activation": _masked_activation(per_slot, active),
        "slot_counts": slot_counts,
    }
    return new_cache, logits, info


def paged_decode_horizon(params, cache, token: jnp.ndarray,
                         positions: jnp.ndarray, cfg, *, horizon: int,
                         budgets: jnp.ndarray, eos_ids: jnp.ndarray,
                         moe_hooks=None, temperature: float = 0.0,
                         rng_key=None, keep_logits: bool = False):
    """Fused ``H``-step decode: one jitted program advances every slot up
    to ``horizon`` tokens with **on-device sampling** feeding each step's
    output token into the next step — the serving engine pays one
    dispatch and one host sync per *megastep* instead of per token.

    Each scan step runs exactly :func:`_paged_decode_core` (the same body
    :func:`paged_decode_step` wraps), so greedy outputs are bit-identical
    to ``H`` single steps. Per-slot stop logic lives inside the scan as
    the carried ``active`` mask:

    * ``budgets [B]`` int32 — tokens the slot may still emit
      (``max_new - len(out)``); a slot deactivates the step its budget
      hits zero, so a request whose budget ends mid-horizon emits no
      extra tokens,
    * ``eos_ids [B]`` int32 — per-slot stop token, ``-1`` disables;
      emitting it deactivates the slot from the next step on,
    * slots inactive at entry (``cache["active"]``) compute but never
      write KV nor emit, exactly as in the single-step program.

    ``temperature`` is **trace-time static**: ``0`` (default) compiles
    greedy argmax — the bit-identity path every invariant test runs —
    and ``> 0`` compiles categorical sampling from ``logits/T`` with one
    explicit subkey per horizon step split from ``rng_key`` (replays of
    the same megastep reuse the same key, so sampled runs are
    deterministic per trace and idempotent under offload replay).

    Returns ``(new_cache, tokens [H, B], emits [H, B], info)``: row ``s``
    holds the token each slot emitted at horizon step ``s`` (``-1`` where
    ``emits`` is False); ``info["expert_activation"]`` is the per-step
    active-masked activation ``[H]`` and ``info["slot_counts"]`` the
    per-step dispatch counts ``[H, L, num_slots]`` (step-major — the
    offload manager's horizon-union working set + replay order).
    ``keep_logits`` (trace-time static) adds ``info["logits"]``, the
    ``[H, B, V]`` float32 logits each step sampled from.
    """
    if horizon < 1:
        raise ValueError(f"horizon must be ≥ 1, got {horizon}")
    greedy = temperature <= 0.0
    if not greedy and rng_key is None:
        raise ValueError("temperature sampling needs an explicit rng_key")
    nl, nb, bs = _paged_pool_dims(cache)
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    tables = cache["block_tables"]
    active0 = cache.get("active")
    if active0 is None:
        active0 = jnp.ones((token.shape[0],), bool)

    def step(carry, key):
        kf, vf, qs, cur, pos, act, budget = carry
        kf, vf, qs, logits, per_slot, counts = _paged_decode_core(
            params, kf, vf, qs, tables, cur, pos, act, cfg, nb, bs,
            moe_hooks=moe_hooks,
        )
        lg = logits[:, -1, :]  # [B, V] f32
        if greedy:
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        else:
            nxt = jax.random.categorical(
                key, lg / jnp.float32(temperature), axis=-1
            ).astype(jnp.int32)
        emit = act  # a slot active at step entry emits this step's token
        budget = budget - emit.astype(jnp.int32)
        stop = (budget <= 0) | ((eos_ids >= 0) & (nxt == eos_ids))
        ys = (
            jnp.where(emit, nxt, -1),
            emit,
            _masked_activation(per_slot, act),
            counts,
            *([lg] if keep_logits else []),
        )
        carry = (kf, vf, qs, nxt[:, None], pos + emit.astype(jnp.int32),
                 act & ~stop, budget)
        return carry, ys

    keys = (
        jnp.zeros((horizon,), jnp.int32) if greedy
        else jax.random.split(rng_key, horizon)
    )
    init = (
        cache["k"].reshape(nl, nb * bs, hkv, dh),
        cache["v"].reshape(nl, nb * bs, hkv, dh),
        _flatten_kv_quant(cache, nl, nb, bs, hkv),
        token, positions, active0, budgets,
    )
    # the horizon scan is fully unrolled: H is small and static, and a
    # rolled while-loop forbids XLA from aliasing the donated KV pools /
    # fusing across steps (measured ~1.8x per-step decode cost on CPU);
    # unrolling keeps per-step cost at the single-step program's while
    # still eliminating the per-token host round-trips
    (kf, vf, qs, *_), (toks, emits, acts, counts, *logits) = jax.lax.scan(
        step, init, keys, unroll=horizon
    )
    new_cache = dict(
        cache,
        k=kf.reshape(nl, nb, bs, hkv, dh),
        v=vf.reshape(nl, nb, bs, hkv, dh),
    )
    if qs:
        new_cache["kv_quant"] = _unflatten_kv_quant(qs, nl, nb, bs, hkv)
    info = {"expert_activation": acts, "slot_counts": counts}
    if keep_logits:
        info["logits"] = logits[0]
    return new_cache, toks, emits, info


def paged_prefill_chunk(params, cache, tokens: jnp.ndarray, start: jnp.ndarray,
                        valid_len: jnp.ndarray, cfg, *, moe_hooks=None):
    """Chunked prefill of ONE request (``B = 1``) into its paged slot.

    ``tokens [1, C]`` is one fixed-size prompt chunk (the tail chunk is
    right-padded; padded positions never write K/V and never appear in
    the gathered kv, so valid rows are exact). ``start`` (scalar) counts
    tokens already written; ``valid_len`` (scalar ≤ C) is the chunk's
    real length. ``cache`` carries this slot's table as ``[1, MB]``.

    Long prompts stream through in O(C · S) attention per chunk via the
    online-softmax path in :func:`repro.models.layers.attention` — the
    engine never materializes a full [P, P] score matrix nor re-prefills
    earlier chunks (contrast the wave batcher's per-wave re-prefill).

    Returns ``(new_cache, logits [1,1,V], info)`` — logits of the last
    *valid* token (the request's first generated token once the final
    chunk is in); ``info["slot_counts"]`` ([L, num_slots], or [L, 0]
    outside the PMQ path) counts the chunk's per-slot expert dispatches,
    excluding right-padded positions (see :func:`paged_decode_step`).
    """
    x = L.embed_tokens(params["embed"], tokens)
    c = tokens.shape[1]
    nl, nb, bs = _paged_pool_dims(cache)
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    tables = cache["block_tables"]  # [1, MB]
    mb = tables.shape[1]
    s_log = mb * bs
    windows = layer_windows(cfg, s_log)
    layer_ids = jnp.arange(nl, dtype=jnp.int32)
    kf = cache["k"].reshape(nl, nb * bs, hkv, dh)
    vf = cache["v"].reshape(nl, nb * bs, hkv, dh)

    posf = start + jnp.arange(c, dtype=jnp.int32)  # absolute positions [C]
    pos2d = posf[None, :]
    page = tables[0, posf // bs]
    dest = jnp.where(jnp.arange(c) < valid_len, page * bs + posf % bs, nb * bs)
    length = start + valid_len
    # logical kv axis with the -1 padding sentinel beyond the filled part
    logical = jnp.arange(s_log, dtype=jnp.int32)
    kv_pos = jnp.where(logical < length, logical, -1)
    phys = tables[0, logical // bs] * bs + logical % bs  # [S_log]
    hooks = dict(moe_hooks or {})
    hooks["count_weight"] = jnp.arange(c) < valid_len  # [C] = [T] at B=1
    qs = _flatten_kv_quant(cache, nl, nb, bs, hkv)
    quantized = bool(qs)

    def body(carry, xs):
        xc, kf, vf, qs = carry
        p_l, win, l = xs
        with jax.named_scope("attention"):
            h = L.rms_norm(xc, p_l["ln1"], cfg.norm_eps)
            k_new, v_new = L._kv_only(p_l["attn"], h, cfg, pos2d)
            if quantized:
                ksf, kzf, vsf, vzf = qs
                kc, ks, kz = quantize_kv_rows(k_new[0], KV_QUANT_BITS)
                vc, vs, vz = quantize_kv_rows(v_new[0], KV_QUANT_BITS)
                kf = kf.at[l, dest].set(kc, mode="drop")
                vf = vf.at[l, dest].set(vc, mode="drop")
                ksf = ksf.at[l, dest].set(ks, mode="drop")
                kzf = kzf.at[l, dest].set(kz, mode="drop")
                vsf = vsf.at[l, dest].set(vs, mode="drop")
                vzf = vzf.at[l, dest].set(vz, mode="drop")
                qs = (ksf, kzf, vsf, vzf)
                # dequantize the gathered rows with the SAME f32 expression as
                # the paged-attention kernels' epilogue — prefill attention
                # over shared-prefix pages sees bit-identical floats to every
                # later decode read of the same pages
                ksl, kzl, vsl, vzl = (
                    jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)[phys]
                    for a in qs
                )
                kr = jax.lax.dynamic_index_in_dim(kf, l, 0, keepdims=False)[phys]
                vr = jax.lax.dynamic_index_in_dim(vf, l, 0, keepdims=False)[phys]
                k_log = dequantize_kv_rows(kr, ksl, kzl)[None]
                v_log = dequantize_kv_rows(vr, vsl, vzl)[None]
            else:
                kf = kf.at[l, dest].set(k_new[0].astype(kf.dtype), mode="drop")
                vf = vf.at[l, dest].set(v_new[0].astype(vf.dtype), mode="drop")
                k_log = jax.lax.dynamic_index_in_dim(kf, l, 0, keepdims=False)[phys][None]
                v_log = jax.lax.dynamic_index_in_dim(vf, l, 0, keepdims=False)[phys][None]
            attn_out, _ = L.attention(
                p_l["attn"], h, cfg, positions=pos2d, causal=True, window=win,
                kv_override=(k_log, v_log, kv_pos),
            )
            xc = xc + attn_out
        with jax.named_scope("moe"):
            h2 = L.rms_norm(xc, p_l["ln2"], cfg.norm_eps)
            delta, _, counts = _ffn_delta(p_l, h2, cfg, hooks)
            xc = xc + delta
        return (xc, kf, vf, qs), counts

    (x, kf, vf, qs), slot_counts = jax.lax.scan(
        body, (x, kf, vf, qs), (params["blocks"], windows, layer_ids)
    )
    with jax.named_scope("head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, axis=1)
        logits = jnp.einsum(
            "btd,vd->btv", last.astype(jnp.float32),
            _out_embedding(params).astype(jnp.float32),
        )
    new_cache = dict(
        cache,
        k=kf.reshape(nl, nb, bs, hkv, dh),
        v=vf.reshape(nl, nb, bs, hkv, dh),
    )
    if qs:
        new_cache["kv_quant"] = _unflatten_kv_quant(qs, nl, nb, bs, hkv)
    return new_cache, logits, {"slot_counts": slot_counts}


# --------------------------------------------- python-loop (calibration)
def forward_layers_python(params, tokens, cfg, *, capture: str = "moe"):
    """Unscanned forward used by PMQ calibration / OTP training on small
    models: returns per-layer captured tensors (router stats or MoE inputs).

    Only usable when layer params are unstacked via :func:`unstack_blocks`.
    """
    raise NotImplementedError("use repro.core.calibrate helpers")


def iter_blocks(params, cfg):
    """Yield each layer's block pytree in turn. A layer's slice is made
    only when it is reached, so a loop that drops it before the next
    layer holds one layer's copy at a time, not the whole stack's."""
    blocks = params["blocks"]
    for i in range(cfg.num_layers):
        yield jax.tree.map(lambda a: a[i], blocks)


def unstack_blocks(params, cfg):
    """Split stacked block params into a list of per-layer pytrees."""
    return list(iter_blocks(params, cfg))


def restack_blocks(block_list):
    """Stack per-layer block pytrees (of one structure) into one tree.
    Empties ``block_list`` and drops each layer's piece of a leaf once
    that leaf is stacked, so the layers are never held twice."""
    flat = [jax.tree_util.tree_flatten(b) for b in block_list]
    block_list.clear()
    treedef = flat[0][1]
    assert all(d == treedef for _, d in flat), "layers differ in structure"
    columns = [list(col) for col in zip(*(leaves for leaves, _ in flat))]
    del flat
    stacked = []
    for col in columns:
        stacked.append(jnp.stack(col))
        col.clear()
    return jax.tree_util.tree_unflatten(treedef, stacked)
