"""Plain reference of a configuration's forward pass, and its control.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
layer by layer, teacher-forced over one whole sequence: no kernels, no
cache, no batching, no capacity. Weights come from ``bench/weights.py``
with the configuration's seed, made again here, never taken from the
program. The layer is the program's transformer MoE block as its
configuration file states it (``served_as``): RMSNorm ``(1 + w)``,
multi-head attention with RoPE on the whole head (half-split rotation),
softmax router with top-k gates renormalised, SwiGLU experts, a shared
SwiGLU, untied output head.

``precision="fp8"`` is the control: the same forward with every matmul
operand rounded to float8 e4m3, the precision below the bf16 the
configuration serves in.

This module imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

__all__ = ["token_gaps"]

_PAD = 256  # sequence lengths are padded to a multiple: few compiled shapes


def _round(a, precision):
    if precision == "fp8":
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return a.astype(jnp.float32)


def _mm(a, b, precision):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32))


def _rope(x, theta):  # x [T, H, dh] at positions 0..T-1
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, wg, wu, wd, precision):
    a = _mm(h, wg, precision)
    return _mm(jax.nn.silu(a) * _mm(h, wu, precision), wd, precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, m_items, precision):
    """One decoder layer, ``x [T, D]`` float32."""
    m = dict(m_items)
    t = x.shape[0]
    hq, hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    a = w["attn"]
    h = _rms(x, w["ln1"], eps)
    q = _rope(_mm(h, a["wq"]["w"], precision).reshape(t, hq, dh), theta)
    k = _rope(_mm(h, a["wk"]["w"], precision).reshape(t, hkv, dh), theta)
    v = _mm(h, a["wv"]["w"], precision).reshape(t, hkv, dh)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", _round(q, precision), _round(k, precision),
                   precision=jax.lax.Precision.HIGHEST) * dh**-0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", _round(jax.nn.softmax(s, -1), precision),
                   _round(v, precision), precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(o.reshape(t, hq * dh), a["wo"]["w"], precision)

    h = _rms(x, w["ln2"], eps)
    moe = w["moe"]
    probs = jax.nn.softmax(_mm(h, moe["router"]["w"], precision), -1)
    gates, idx = jax.lax.top_k(probs, m["top_k"])
    gates = gates / gates.sum(-1, keepdims=True)
    weight = jnp.zeros((t, m["num_experts"]), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(gates)  # [T, E]
    ex = moe["experts"]

    def expert(acc, xs):
        wg, wu, wd, g = xs
        return acc + g[:, None] * _swiglu(h, wg, wu, wd, precision), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (ex["w_gate"], ex["w_up"], ex["w_down"], weight.T))
    if "shared" in moe:
        sh = moe["shared"]
        y = y + _swiglu(h, sh["w_gate"]["w"], sh["w_up"]["w"],
                        sh["w_down"]["w"], precision)
    return x + y


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(x, norm, unembed, picks, eps, precision):
    """Per position: the largest logit, its token, and the logits of the
    tokens in ``picks [T, P]``; the ``[T, V]`` logits are made 256 rows at
    a time."""
    h = _rms(x, norm, eps)
    t = h.shape[0]

    def rows(args):
        hb, pb = args
        lg = _mm(hb, unembed.T, precision)
        return lg.max(-1), lg.argmax(-1), jnp.take_along_axis(lg, pb, axis=1)

    best, arg, picked = jax.lax.map(
        rows, (h.reshape(t // _PAD, _PAD, -1),
               picks.reshape(t // _PAD, _PAD, -1)))
    return (best.reshape(t), arg.reshape(t),
            picked.reshape(t, picks.shape[1]))


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(embed, tokens, dtype):
    return embed[tokens].astype(dtype)


def _forward(m: dict, seed: int, tokens: np.ndarray, picks: np.ndarray,
             precision: str):
    m_items = tuple(sorted(m.items()))
    top = weights.make_tree(m, seed, top_only=True)
    x = _embed(top["embed"], jnp.asarray(tokens), jnp.float32)
    for layer in range(m["num_layers"]):
        w = weights.make_layer(m, seed, layer, jnp.bfloat16)
        x = _layer(x, w, m_items, precision)
        del w
    unembed = top.get("unembed", top["embed"])
    return _head(x, top["final_norm"], unembed, jnp.asarray(picks),
                 m["norm_eps"], precision)


def token_gaps(m: dict, seed: int, prompt: np.ndarray, served: np.ndarray,
               *, control: bool = False, pad_to: int = 0) -> dict:
    """Gaps by which served tokens' reference logits lie below the
    reference's best, at each position that produced one.

    ``prompt [P]`` and ``served [n]`` are the request's prompt and the
    tokens the program served. The reference runs once over
    ``prompt + served[:-1]``, padded at the end to ``pad_to`` (or to a
    multiple of 256), which changes no earlier position. Returns
    ``{"gaps": [n]}`` and, with ``control``, ``"control_gaps": [n]``: the
    gap of the token that the float8 control puts first at each of those
    positions.
    """
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    t = len(seq)
    tp = -(-max(t, pad_to) // _PAD) * _PAD
    tokens = np.zeros(tp, np.int32)
    tokens[:t] = seq
    rows = np.arange(len(prompt) - 1, t)  # positions that produced served
    picks = np.zeros((tp, 2), np.int32)
    if control:
        _, ctrl_arg, _ = _forward(m, seed, tokens, picks, "fp8")
        picks[:, 1] = np.asarray(ctrl_arg)
    picks[rows, 0] = served
    best, _, picked = _forward(m, seed, tokens, picks, "f32")
    best, picked = np.asarray(best), np.asarray(picked)
    out = {"gaps": best[rows] - picked[rows, 0]}
    if control:
        out["control_gaps"] = best[rows] - picked[rows, 1]
    return out
