"""Paged-KV decode attention Pallas TPU kernel (serving hot path).

One query token per sequence attends to a KV history scattered across
fixed-size blocks of a shared pool (``repro.serving.kvcache``): block
``j`` of sequence ``i`` lives at physical page ``block_tables[i, j]``.
The kernel streams pages HBM→VMEM via **scalar-prefetched** block tables
(the index map reads ``block_tables[i, j]`` to pick each page's DMA
source), so the gather costs exactly the bytes of the pages it visits —
no [B, S_max, Hkv, dh] contiguous copy ever exists. Online softmax
accumulates across pages (the "arbitrary" grid dim), the same recurrence
as :func:`repro.models.layers._online_attn`.

Grid ``(B, MB)``: one program per (sequence, page). Each program loads
the page of every KV head at once — a ``[BS, Hkv, dh]`` block whose
last two dims are the pool's own, as the TPU tiling requires — and
loops over the heads inside the kernel. GQA rides the block shape: head
``h`` computes all ``G = Hq/Hkv`` of its query heads against one
[BS, dh] slice of the page.

Layouts
-------
* ``q``: [B, Hkv, G, dh]
* ``k_pool`` / ``v_pool``: [NB, BS, Hkv, dh] (one layer's pool)
* ``block_tables``: [B, MB] int32 physical page ids (scalar prefetch)
* ``lengths``: [B] int32 logical kv length (newest token at length−1)
* ``window``: [1] int32 sliding-window size (≥ max length = full attn)

The jnp oracle is :func:`repro.kernels.ref.paged_attention_ref`; the CPU
serving path and tests run it (or this kernel under ``interpret=True``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_pallas", "paged_attention_quant_pallas"]

NEG_INF = -1e30


def _kernel(
    bt_ref,  # [B, MB] scalar prefetch (consumed by index maps)
    len_ref,  # [B] scalar prefetch
    win_ref,  # [1] scalar prefetch
    q_ref,  # [1, Hkv, G, dh]
    k_ref,  # [1, BS, Hkv, dh] — page bt[i, j], every kv head
    v_ref,  # [1, BS, Hkv, dh]
    *rest,
    bs: int,
    nj: int,
    quant: bool,
):
    """Online-softmax step over one page for every kv head.

    ``quant``: ``k_ref``/``v_ref`` hold uint8 codes and ``rest`` starts
    with the per-row K/V scale and zero blocks ``[1, BS, Hkv]`` f32 of
    the same page; each head's rows get the affine **dequant epilogue**
    ``(codes - zero) * scale`` in f32 — the exact expression of
    :func:`repro.core.quantizers.dequantize_kv_rows` and of the ref
    oracle's quant mode — after the page lands in VMEM (the DMA moves
    1-byte codes + one f32 pair per row).
    """
    if quant:
        ks_ref, kz_ref, vs_ref, vz_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[i]
    win = win_ref[0]
    hkv, dh = q_ref.shape[1], q_ref.shape[-1]
    kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = (kv_pos < length) & (kv_pos > (length - 1) - win)

    def rows(ref, h):
        return ref[0, :, h].astype(jnp.int32 if quant else jnp.float32) \
            .astype(jnp.float32)  # [BS, dh]; Mosaic has no uint8 -> f32 cast

    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32) * dh**-0.5  # [G, dh]
        k, v = rows(k_ref, h), rows(v_ref, h)
        if quant:
            k = (k - kz_ref[0, :, h][:, None]) * ks_ref[0, :, h][:, None]
            v = (v - vz_ref[0, :, h][:, None]) * vs_ref[0, :, h][:, None]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [G, BS]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[h] = m_new

    @pl.when(j == nj - 1)
    def _done():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def _paged_call(q, k_pool, v_pool, scales, block_tables, lengths, window,
                interpret):
    """Shared ``pallas_call`` of both pool formats (``scales`` = the four
    ``[NB, BS, Hkv]`` quant tables, or ``()`` for an fp pool)."""
    b, hkv, g, dh = q.shape
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]

    q_spec = pl.BlockSpec((1, hkv, g, dh), lambda i, j, bt, ln, wd: (i, 0, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, bs, hkv, dh), lambda i, j, bt, ln, wd: (bt[i, j], 0, 0, 0)
    )
    sc_spec = pl.BlockSpec(
        (1, bs, hkv), lambda i, j, bt, ln, wd: (bt[i, j], 0, 0)
    )
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mb),
        in_specs=[q_spec, kv_spec, kv_spec] + [sc_spec] * len(scales),
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, g, dh), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),  # running max
            pltpu.VMEM((hkv, g, 1), jnp.float32),  # running denominator
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, nj=mb, quant=bool(scales))
    return pl.pallas_call(
        kernel,
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        lengths.astype(jnp.int32),
        window.astype(jnp.int32),
        q,
        k_pool,
        v_pool,
        *(s.astype(jnp.float32) for s in scales),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_quant_pallas(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    k_scale: jnp.ndarray,
    k_zero: jnp.ndarray,
    v_scale: jnp.ndarray,
    v_zero: jnp.ndarray,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    window: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[B,Hkv,G,dh]`` over int8-quantized pools: ``k_pool/v_pool``
    are ``[NB, BS, Hkv, dh]`` uint8 codes, the scale/zero tables
    ``[NB, BS, Hkv]`` f32 — one affine pair per KV row, streamed
    page-at-a-time through the same scalar-prefetched block tables as
    the codes."""
    return _paged_call(
        q, k_pool, v_pool, (k_scale, k_zero, v_scale, v_zero),
        block_tables, lengths, window, interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    window: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[B,Hkv,G,dh]`` — see module docstring for layouts."""
    return _paged_call(
        q, k_pool, v_pool, (), block_tables, lengths, window, interpret
    )
