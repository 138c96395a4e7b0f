"""Shared neural-net layers (pure-functional JAX; params are pytrees).

Conventions
-----------
* weight matrices are ``[K_in, N_out]`` (``y = x @ W``) so the PMQ packed
  kernels substitute 1:1 (a leaf may be a ``PackedTensor``);
* activations ``[B, S, D]``; attention heads ``[B, S, H, dh]``;
* long-context attention uses a q-chunk × kv-chunk online-softmax sweep
  (flash-style) so prefill_32k / long_500k never materialize [S, S];
* every init takes an explicit PRNG key; dtype from the config.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.packing import PackedTensor
from ..kernels import ops

__all__ = [
    "linear",
    "init_linear",
    "rms_norm",
    "apply_rope",
    "init_attention",
    "attention",
    "decode_attention",
    "init_mlp",
    "mlp",
    "chunked_xent",
    "sinusoidal_positions",
]

NEG_INF = -1e30


# ----------------------------------------------------------------- basics
def init_linear(rng, k: int, n: int, dtype=jnp.float32, scale: float = None):
    scale = scale if scale is not None else (1.0 / (k**0.5))
    return {"w": jax.random.normal(rng, (k, n), dtype) * scale}


def linear(p, x: jnp.ndarray) -> jnp.ndarray:
    w = p["w"]
    if isinstance(w, PackedTensor):
        return ops.quant_matmul(x, w)
    return x @ w.astype(x.dtype)


def embed_tokens(embed: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Vocab-sharded embedding lookup.

    GSPMD lowers ``jnp.take`` over a vocab-sharded table by all-gathering
    it (measured: 7.8 GiB/device f32 at command-r scale). Inside a mesh
    context this uses a shard_map masked-local-take + psum over ``model``
    instead (one [B,S,D] bf16 psum — the canonical Megatron embedding).
    """
    from ..parallel.sharding import batch_axes, current_mesh, manual_region

    mesh = current_mesh()
    if (
        mesh is None
        or "model" not in mesh.axis_names
        or embed.shape[0] % mesh.shape["model"] != 0
        or tokens.ndim != 2
    ):
        return jnp.take(embed, tokens, axis=0)
    ba = batch_axes(mesh)
    bsz = int(np.prod([mesh.shape[a] for a in ba])) if True else 1
    if tokens.shape[0] % bsz:
        return jnp.take(embed, tokens, axis=0)

    def body(emb_l, tok):
        with manual_region():
            vloc = emb_l.shape[0]
            lo = jax.lax.axis_index("model") * vloc
            rel = tok - lo
            ok = (rel >= 0) & (rel < vloc)
            x = jnp.take(emb_l, jnp.clip(rel, 0, vloc - 1), axis=0)
            x = x * ok[..., None].astype(emb_l.dtype)
            return jax.lax.psum(x, "model")

    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("model", None), P(ba, None)),
        out_specs=P(ba, None, None),
        check_vma=False,
    )(embed, tokens)


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    # statistics in f32 (tiny, per-token); the normalize multiply stays in
    # the activation dtype so no [B,S,D]-sized f32 exists in fwd or bwd
    var = jnp.mean(
        x.astype(jnp.float32) * x.astype(jnp.float32), axis=-1, keepdims=True
    )
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + w.astype(x.dtype))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding. ``x [B, S, H, dh]``, ``positions [S] or [B, S]``."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freq[None, :]  # [S, half]
        ang = ang[None, :, None, :]  # [1, S, 1, half]
    else:
        ang = positions[..., None].astype(jnp.float32) * freq  # [B, S, half]
        ang = ang[:, :, None, :]
    # angles in f32 (position-only, tiny); the rotation multiply stays in
    # the activation dtype — a full [B,S,H,dh] f32 copy here costs ~2 GiB
    # per layer in the backward pass at 35B scale
    sin = jnp.sin(ang).astype(x.dtype)
    cos = jnp.cos(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sinusoidal_positions(positions: jnp.ndarray, d: int) -> jnp.ndarray:
    """Absolute sinusoidal embeddings (whisper)."""
    half = d // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# -------------------------------------------------------------- attention
def init_attention(rng, cfg, dtype=jnp.float32):
    d, hq, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": init_linear(ks[0], d, hq * dh, dtype),
        "wk": init_linear(ks[1], d, hkv * dh, dtype),
        "wv": init_linear(ks[2], d, hkv * dh, dtype),
        "wo": init_linear(ks[3], hq * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), dtype)
        p["k_norm"] = jnp.zeros((dh,), dtype)
    return p


def _qkv(p, x, cfg, positions, rope: bool = True):
    b, s, _ = x.shape
    hq, dh = cfg.num_heads, cfg.head_dim
    q = linear(p["wq"], x).reshape(b, s, hq, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
    k, v = _kv_only(p, x, cfg, positions, rope=rope)
    return q, k, v


def _kv_only(p, x, cfg, positions, rope: bool = True):
    """K/V projection without the query — paged prefill writes K/V
    itself and lets :func:`attention`'s cross-attention path own q."""
    b, s, _ = x.shape
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    k = linear(p["wk"], x).reshape(b, s, hkv, dh)
    v = linear(p["wv"], x).reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def _mask_chunk(q_pos, kv_pos, causal: bool, window):
    """[qc, kc] boolean validity from absolute positions.

    ``window`` may be a *traced* scalar (mixed local/global scans pass the
    per-layer effective window; full-attention layers pass S+1) or None.
    """
    qp = q_pos[:, None]
    kp = kv_pos[None, :]
    m = kp >= 0  # padding sentinel
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= kp > qp - window
    return m


def _online_attn(q, k, v, q_pos, kv_pos, *, causal, window, kv_chunk):
    """One q-chunk, scan over kv chunks with online softmax.

    q [B, qc, Hkv, G, dh]; k/v [B, Skv, Hkv, dh]. Returns [B, qc, Hkv, G, dh].
    """
    b, qc, hkv, g, dh = q.shape
    skv = k.shape[1]
    nkv = skv // kv_chunk
    scale = dh**-0.5
    q32 = q.astype(jnp.float32) * scale

    kc3 = k.reshape(b, nkv, kv_chunk, hkv, dh)
    vc3 = v.reshape(b, nkv, kv_chunk, hkv, dh)
    kvp = kv_pos.reshape(nkv, kv_chunk)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, kp = inp
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q32, kc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        mask = _mask_chunk(q_pos, kp, causal, window)  # [qc, kc]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, qc), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, qc, dh), jnp.float32)
    # flash-style backward: never keep [qc, kc] score/probability tiles as
    # scan residuals — recompute them per kv-chunk in the backward pass
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        (m0, l0, a0),
        (jnp.moveaxis(kc3, 1, 0), jnp.moveaxis(vc3, 1, 0), kvp),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.einsum("bhgqd->bqhgd", out)


def attention(
    p,
    x: jnp.ndarray,
    cfg,
    *,
    positions: jnp.ndarray,
    causal: bool = True,
    window=None,
    rope: bool = True,
    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence attention (train / prefill / encoder / cross).

    Returns ``(out [B,S,D], (k, v))`` — k/v are returned for cache builds.
    ``kv_override = (k, v, kv_pos)`` turns this into cross-attention.
    """
    b, s, d = x.shape
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = hq // hkv
    if kv_override is not None:
        # cross-attention: only the query comes from x
        q = linear(p["wq"], x).reshape(b, s, hq, dh)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        if rope:
            q = apply_rope(q, positions, cfg.rope_theta)
        k, v, kv_pos = kv_override
    else:
        q, k, v = _qkv(p, x, cfg, positions, rope=rope)
        kv_pos = positions if positions.ndim == 1 else positions[0]
    qc = min(cfg.attn_q_chunk, s)
    kvc = min(cfg.attn_kv_chunk, k.shape[1])
    # pad q/kv to chunk multiples; padded kv positions get the -1 sentinel
    # (masked), padded query rows are sliced off below
    q_pos_1d = positions if positions.ndim == 1 else positions[0]
    s_pad = (-s) % qc
    if s_pad:
        q = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
        q_pos_1d_q = jnp.concatenate(
            [q_pos_1d, jnp.full((s_pad,), -1, q_pos_1d.dtype)]
        )
    else:
        q_pos_1d_q = q_pos_1d
    kv_pad = (-k.shape[1]) % kvc
    if kv_pad:
        k = jnp.pad(k, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
        kv_pos = jnp.concatenate([kv_pos, jnp.full((kv_pad,), -1, kv_pos.dtype)])
    sq = s + s_pad
    nq = sq // qc
    q5 = q.reshape(b, nq, qc, hkv, g, dh)
    qp = q_pos_1d_q.reshape(nq, qc)

    def one(args):
        qch, qpch = args
        o = _online_attn(
            qch, k, v, qpch, kv_pos, causal=causal, window=window, kv_chunk=kvc
        )
        return o.astype(x.dtype)  # never stack f32 [B,S,H,dh] across chunks

    one = jax.checkpoint(one, prevent_cse=False)
    out = jax.lax.map(one, (jnp.moveaxis(q5, 1, 0), qp))  # [nq, B, qc, hkv, g, dh]
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hq * dh)[:, :s]
    kv_s = k.shape[1] - kv_pad
    return linear(p["wo"], out.astype(x.dtype)), (k[:, :kv_s], v[:, :kv_s])


def decode_attention(
    p,
    x: jnp.ndarray,
    cfg,
    *,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,
    window=None,
    rope: bool = True,
    cross: bool = False,
    kv_len: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Single-step decode: ``x [B, 1, D]`` against cache ``[B, S, Hkv, dh]``.

    Self-attention writes the new k/v at ``pos`` (scalar int32) before
    attending. Cross-attention (``cross=True``) reads the cache only.
    Returns ``(out [B,1,D], (k_cache, v_cache))``.
    """
    b, _, d = x.shape
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = hq // hkv
    s = k_cache.shape[1]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _qkv(p, x, cfg, positions, rope=rope and not cross)
    if not cross:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_new.astype(k_cache.dtype), pos, axis=1
        )
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_new.astype(v_cache.dtype), pos, axis=1
        )
        limit = pos
    else:
        limit = (kv_len - 1) if kv_len is not None else s - 1
    q32 = q.reshape(b, hkv, g, dh).astype(jnp.float32) * dh**-0.5
    scores = jnp.einsum(
        "bhgd,bshd->bhgs", q32, k_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    kv_pos = jnp.arange(s)
    valid = kv_pos <= limit
    if window is not None and not cross:
        valid &= kv_pos > pos - window
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgs,bshd->bhgd", w, v_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    out = out.reshape(b, 1, hq * dh).astype(x.dtype)
    return linear(p["wo"], out), (k_cache, v_cache)


# -------------------------------------------------------------------- MLP
def init_mlp(rng, d: int, f: int, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": init_linear(ks[0], d, f, dtype),
        "w_up": init_linear(ks[1], d, f, dtype),
        "w_down": init_linear(ks[2], f, d, dtype),
    }


def mlp(p, x: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU feed-forward."""
    return linear(p["w_down"], jax.nn.silu(linear(p["w_gate"], x)) * linear(p["w_up"], x))


# ------------------------------------------------------------------- loss
def _divisor_chunk(s: int, chunk: int) -> int:
    """Largest divisor of ``s`` that is ≤ chunk (streaming chunk size)."""
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1
    return chunk


def chunked_xent(
    hidden: jnp.ndarray,
    emb: jnp.ndarray,
    labels: jnp.ndarray,
    chunk: int = 512,
) -> jnp.ndarray:
    """Streaming softmax cross-entropy: never materializes [B, S, V].

    ``hidden [B, S, D]``, ``emb [V, D]`` (output projection = embᵀ),
    ``labels [B, S]`` int32 (−1 = ignore). Returns mean NLL over valid.
    """
    b, s, d = hidden.shape
    chunk = _divisor_chunk(s, chunk)
    n = s // chunk
    hc = jnp.moveaxis(hidden.reshape(b, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n, chunk), 1, 0)
    w = emb  # keep vocab-sharded bf16; a global f32 cast all-gathers it

    def body(carry, inp):
        tot, cnt = carry
        h, y = inp
        logits = jnp.einsum(
            "bcd,vd->bcv", h.astype(w.dtype), w,
            preferred_element_type=jnp.float32,
        )
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(y, 0)[..., None], axis=-1
        )[..., 0]
        valid = (y >= 0).astype(jnp.float32)
        nll = (lse - picked) * valid
        return (tot + nll.sum(), cnt + valid.sum()), None

    # recompute the [B, c, V] logits chunk in the backward pass
    (tot, cnt), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        (jnp.float32(0), jnp.float32(0)),
        (hc, lc),
    )
    return tot / jnp.maximum(cnt, 1.0)


def chunked_kl(
    hidden_s: jnp.ndarray,
    hidden_t: jnp.ndarray,
    emb: jnp.ndarray,
    chunk: int = 512,
) -> jnp.ndarray:
    """Streaming KL(teacher ‖ student) over vocab, never materializing
    [B, S, V] (OTP distillation loss, Eq. 14 first term)."""
    b, s, d = hidden_s.shape
    chunk = _divisor_chunk(s, chunk)
    n = s // chunk
    hs = jnp.moveaxis(hidden_s.reshape(b, n, chunk, d), 1, 0)
    ht = jnp.moveaxis(hidden_t.reshape(b, n, chunk, d), 1, 0)
    w = emb  # keep vocab-sharded bf16 (see chunked_xent)

    def body(tot, inp):
        a, t = inp
        ls = jax.nn.log_softmax(
            jnp.einsum("bcd,vd->bcv", a.astype(w.dtype), w,
                       preferred_element_type=jnp.float32), axis=-1
        )
        lt = jax.nn.log_softmax(
            jnp.einsum("bcd,vd->bcv", t.astype(w.dtype), w,
                       preferred_element_type=jnp.float32), axis=-1
        )
        kl = jnp.sum(jnp.exp(lt) * (lt - ls), axis=-1)
        return tot + kl.sum(), None

    tot, _ = jax.lax.scan(body, jnp.float32(0), (hs, ht))
    return tot / (b * s)
