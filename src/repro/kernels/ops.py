"""Public jit'd wrappers around the Pallas kernels.

Handle platform selection (TPU → compiled kernel, CPU → interpret or
reference), padding to block multiples, and the `PackedTensor` container
from :mod:`repro.core.packing`. Models call these; they never touch
`pallas_call` directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.packing import PackedTensor
from . import ref
from .binary_matmul import binary_matmul_pallas
from .moe_gmm import (
    moe_gmm_pallas,
    moe_gmm_swiglu_pallas,
    pad_groups,
    sort_by_expert,
)
from .paged_attention import (
    paged_attention_pallas,
    paged_attention_quant_pallas,
)
from .quant_matmul import quant_matmul_pallas

__all__ = [
    "quant_matmul",
    "quant_matmul_parts",
    "binary_matmul",
    "moe_gmm",
    "moe_gmm_swiglu",
    "paged_attention",
    "pad_groups",
    "sort_by_expert",
    "default_backend",
]


def default_backend() -> str:
    """'pallas' on TPU, 'ref' elsewhere (tests opt into 'interpret')."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def quant_matmul(
    x: jnp.ndarray,
    pt: PackedTensor,
    *,
    backend: str | None = None,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
) -> jnp.ndarray:
    """``y = x @ dequant(pt)`` for any leading x shape; K = pt.shape[0]."""
    backend = backend or default_backend()
    k, n = pt.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    if backend == "ref":
        y = ref.quant_matmul_ref(
            x2, pt.data, pt.scale, pt.zero, bits=pt.bits, group=pt.group
        )
        return y.reshape(*lead, n)
    m = x2.shape[0]
    bm_ = min(bm, _next_mult(m, 8))
    bn_, bk_ = _gmm_blocks(n, k, pt.group, bn, bk)
    x2p = _pad_to(x2, bm_, 0)
    y = quant_matmul_pallas(
        x2p,
        pt.data,
        pt.scale,
        pt.zero,
        bits=pt.bits,
        group=pt.group,
        bm=bm_,
        bn=bn_,
        bk=bk_,
        interpret=(backend == "interpret"),
    )
    return y[:m].reshape(*lead, n)


def quant_matmul_parts(
    x: jnp.ndarray,
    w_packed,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    *,
    bits: int,
    group: int = 128,
    backend: str | None = None,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
) -> jnp.ndarray:
    """``y = x @ dequant(w)`` from raw packed parts (no PackedTensor).

    The backend-selection twin of :func:`quant_matmul` for call sites
    that hold per-expert stacked/sliced arrays rather than a
    :class:`PackedTensor` — the EP shard bodies and the legacy scan path
    route through here so TPU shards get the Pallas kernel and CPU tests
    keep the jnp oracle. ``w_packed`` is ``[K/per, N]`` uint8 (or the
    ``(hi, lo)`` plane pair for 3-bit).
    """
    backend = backend or default_backend()
    k = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    if backend == "ref":
        y = ref.quant_matmul_ref(
            x2, w_packed, scale, zero, bits=bits, group=group
        )
        return y.reshape(*lead, y.shape[-1])
    m = x2.shape[0]
    n = (w_packed[0] if bits == 3 else w_packed).shape[-1]
    bm_ = min(bm, _next_mult(m, 8))
    bn_, bk_ = _gmm_blocks(n, k, group, bn, bk)
    x2p = _pad_to(x2, bm_, 0)
    y = quant_matmul_pallas(
        x2p, w_packed, scale, zero,
        bits=bits, group=group, bm=bm_, bn=bn_, bk=bk_,
        interpret=(backend == "interpret"),
    )
    return y[:m].reshape(*lead, n)


def binary_matmul(
    x: jnp.ndarray,
    b_packed: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    backend: str | None = None,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
) -> jnp.ndarray:
    backend = backend or default_backend()
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    if backend == "ref":
        return ref.binary_matmul_ref(x2, b_packed, alpha).reshape(
            *lead, b_packed.shape[1]
        )
    m = x2.shape[0]
    bm_ = min(bm, _next_mult(m, 8))
    x2p = _pad_to(x2, bm_, 0)
    y = binary_matmul_pallas(
        x2p, b_packed, alpha, bm=bm_, bn=bn, bk=bk,
        interpret=(backend == "interpret"),
    )
    return y[:m].reshape(*lead, b_packed.shape[1])


def _gmm_blocks(n: int, k: int, group: int, bn: int, bk: int):
    """``(bn, bk)`` near the requested sizes that the TPU tiling accepts.

    A block's last two dims must be multiples of (8, 128) or span the
    array. So ``bn`` is a multiple of 128 dividing ``n``, else all of
    ``n``; and since the scale/zero block holds ``bk // group`` rows,
    ``bk`` is a multiple of ``8 * group`` dividing ``k``, else all of
    ``k``.
    """
    return (
        _aligned_divisor(n, 128, bn) or n,
        _aligned_divisor(k, 8 * group, max(bk, 8 * group)) or k,
    )


def _aligned_divisor(total: int, step: int, cap: int) -> int:
    """Largest multiple of ``step`` that divides ``total`` and is at most
    ``cap`` (0 when there is none)."""
    return next(
        (b for b in range(cap - cap % step, 0, -step) if total % b == 0), 0
    )


def moe_gmm(
    x_padded: jnp.ndarray,
    w_packed,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    block_expert: jnp.ndarray,
    num_active: jnp.ndarray | None = None,
    *,
    bits: int,
    group: int = 128,
    backend: str | None = None,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
) -> jnp.ndarray:
    """Grouped expert GEMM; ``num_active`` enables the ragged skip of
    row-blocks past the routed-token frontier (see moe_gmm.py).

    ``x_padded`` is in its natural column order. The Pallas path reorders
    its columns once per call, by a transpose, into the order in which the
    kernel unpacks the bit planes (within each quantisation group:
    plane-major, byte row within plane; for 3-bit, one copy per plane
    width), and scales each group's MXU product instead of each weight.
    The packed weights are read as stored."""
    backend = backend or default_backend()
    if backend == "ref":
        return ref.moe_gmm_ref(
            x_padded, w_packed, scale, zero, block_expert, num_active,
            bits=bits, group=group, bm=bm,
        )
    n = (w_packed[0] if bits == 3 else w_packed).shape[-1]
    bn, bk = _gmm_blocks(n, x_padded.shape[-1], group, bn, bk)
    return moe_gmm_pallas(
        x_padded, w_packed, scale, zero, block_expert, num_active,
        bits=bits, group=group, bm=bm, bn=bn, bk=bk,
        interpret=(backend == "interpret"),
    )


def moe_gmm_swiglu(
    x_padded: jnp.ndarray,
    wg_packed,
    wu_packed,
    g_scale: jnp.ndarray,
    g_zero: jnp.ndarray,
    u_scale: jnp.ndarray,
    u_zero: jnp.ndarray,
    block_expert: jnp.ndarray,
    num_active: jnp.ndarray | None = None,
    *,
    bits: int,
    group: int = 128,
    backend: str | None = None,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
) -> jnp.ndarray:
    """Fused gate/up grouped GEMM + SwiGLU epilogue (one x stream, the
    [M, F] hidden never round-trips HBM between the two projections).

    ``x_padded`` is reordered into plane order as in :func:`moe_gmm`; the
    hidden it returns is in natural order, so the down projection's
    :func:`moe_gmm` reorders it again for its own planes."""
    backend = backend or default_backend()
    if backend == "ref":
        return ref.moe_gmm_swiglu_ref(
            x_padded, wg_packed, wu_packed, g_scale, g_zero,
            u_scale, u_zero, block_expert, num_active,
            bits=bits, group=group, bm=bm,
        )
    n = (wg_packed[0] if bits == 3 else wg_packed).shape[-1]
    bn, bk = _gmm_blocks(n, x_padded.shape[-1], group, bn, bk)
    return moe_gmm_swiglu_pallas(
        x_padded, wg_packed, wu_packed, g_scale, g_zero, u_scale, u_zero,
        block_expert, num_active,
        bits=bits, group=group, bm=bm, bn=bn, bk=bk,
        interpret=(backend == "interpret"),
    )


def paged_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    window=None,
    backend: str | None = None,
    quant=None,
) -> jnp.ndarray:
    """Decode attention through a paged KV pool (serving hot path).

    ``q [B, Hkv, G, dh]``; ``k_pool/v_pool [NB, BS, Hkv, dh]`` — one
    layer's pool; ``block_tables [B, MB]``; ``lengths [B]`` logical kv
    lengths. ``window`` may be a python int or traced scalar (per-layer
    sliding windows ride the decode scan). Returns ``[B, Hkv, G, dh]``.

    ``quant = (k_scale, k_zero, v_scale, v_zero)`` (each ``[NB, BS,
    Hkv]`` f32) reads the pools as int8 codes with a per-row affine
    dequant epilogue on the gathered pages (ref oracle:
    :func:`repro.kernels.ref.paged_attention_ref` quant mode; TPU:
    :func:`repro.kernels.paged_attention.paged_attention_quant_pallas`).
    ``quant=None`` is the unchanged fp path.
    """
    backend = backend or default_backend()
    if backend == "ref":
        return ref.paged_attention_ref(
            q, k_pool, v_pool, block_tables, lengths, window=window,
            quant=quant,
        )
    mb, bs = block_tables.shape[1], k_pool.shape[1]
    win = jnp.full((1,), mb * bs + 1, jnp.int32) if window is None else (
        jnp.asarray(window, jnp.int32).reshape(1)
    )
    if quant is not None:
        ks, kz, vs, vz = quant
        return paged_attention_quant_pallas(
            q, k_pool, v_pool, ks, kz, vs, vz, block_tables, lengths, win,
            interpret=(backend == "interpret"),
        )
    return paged_attention_pallas(
        q, k_pool, v_pool, block_tables, lengths, win,
        interpret=(backend == "interpret"),
    )


def _next_mult(x: int, base: int) -> int:
    return ((x + base - 1) // base) * base
