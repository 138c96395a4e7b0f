"""Grouped (ragged) quantized expert matmul — the MoE hot loop.

MegaBlocks-style dropless expert GEMM adapted to TPU + PMQ quantization
(DESIGN.md §5.4): tokens are pre-sorted by expert id and padded so each
expert's row range is a multiple of ``bm``; a scalar-prefetch array
``block_expert [M/bm]`` tells each row-block which expert's packed weight
tile to fetch. Dequantization (group-wise affine over K) happens in VMEM
exactly as in :mod:`repro.kernels.quant_matmul`.

Because every PMQ bit-width rides the same (scale, zero) affine form
(1-bit: scale=2α, zero=0.5 — see ``quantize_to_packed``), a *bit-bucketed*
MoE layer issues one ``moe_gmm`` per bucket with experts of equal width.

**Ragged-length handling**: the caller
(:mod:`repro.core.compressed_moe`) gives the kernel one row per routed
(token, choice) pair, sorted by expert, each expert's group padded to
``bm`` rows, in a static-shape buffer whose extent is a bound on the
routed pairs (``grouped_extent``), not the experts' capacity. The grid
follows ``x_sorted.shape[0]``, so it walks about ``experts + pairs/bm``
row blocks. ``num_active [1]`` (second scalar-prefetch operand) tells
the kernel how many leading row-blocks carry tokens; blocks past it skip
the unpack/dequant/MXU work and write zeros, so the jitted program keeps
its static shape whatever the routing.

**SwiGLU epilogue** (:func:`moe_gmm_swiglu_pallas`): the gate and up
projections share their ``x`` tile and accumulate side by side in VMEM;
the epilogue applies ``silu(acc_g) · acc_u`` before the single output
write, so the [M, F] hidden tile never round-trips HBM between the two
GEMMs and ``x`` streams from HBM once instead of twice.

Layouts
-------
* ``x_sorted``:  [Mp, K]   tokens sorted by expert, bm-padded per expert
* ``w_packed``:  [E, K/per, N] uint8 (or (hi [E,K/4,N], lo [E,K/8,N]) for 3-bit)
* ``scale/zero``:[E, K/group, N] f32
* ``block_expert``: [Mp/bm] int32 — expert id per row-block (scalar prefetch)
* ``num_active``: [1] int32 — row-blocks carrying routed tokens (scalar
  prefetch; blocks ≥ it are skipped and zero-filled)
* grid (Mp/bm, N/bn, K/bk), K innermost, f32 scratch accumulator.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant_matmul import _dequant, _unpack_tile

__all__ = [
    "moe_gmm_pallas",
    "moe_gmm_swiglu_pallas",
    "pad_groups",
    "sort_by_expert",
]


def _w_specs_and_planes(w_packed, bits: int, bk: int, bn: int):
    """BlockSpecs + flat plane list for one packed weight operand."""
    if bits == 3:
        hi, lo = w_packed
        specs = [
            pl.BlockSpec((1, bk // 4, bn), lambda i, j, kk, be, na: (be[i], kk, j)),
            pl.BlockSpec((1, bk // 8, bn), lambda i, j, kk, be, na: (be[i], kk, j)),
        ]
        return specs, [hi, lo]
    per = 8 // bits
    specs = [
        pl.BlockSpec((1, bk // per, bn), lambda i, j, kk, be, na: (be[i], kk, j))
    ]
    return specs, [w_packed]


def _take_w_tile(refs, bits: int):
    """Pop one weight operand's refs and present it to ``_unpack_tile``."""
    if bits == 3:
        (hi_ref, lo_ref), rest = refs[:2], refs[2:]
        return (_Squeezed(hi_ref), _Squeezed(lo_ref)), rest
    return _Squeezed(refs[0]), refs[1:]


def _full_blocks(m: int, bm: int) -> jnp.ndarray:
    return jnp.full((1,), m // bm, jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "group", "bm", "bn", "bk", "out_dtype", "interpret"),
)
def moe_gmm_pallas(
    x_sorted: jnp.ndarray,
    w_packed,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    block_expert: jnp.ndarray,
    num_active: jnp.ndarray | None = None,
    *,
    bits: int,
    group: int = 128,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Block-diagonal grouped GEMM: row-block i uses expert block_expert[i].

    ``num_active [1]`` (optional) marks how many leading row-blocks carry
    routed tokens; blocks past it are zero-filled without touching the
    MXU (ragged capacity layouts pass the bm-padded routed-row count).
    """
    m, k = x_sorted.shape
    if bits == 3:
        hi, lo = w_packed
        e, _, n = hi.shape
    else:
        e, _, n = w_packed.shape
    out_dtype = out_dtype or x_sorted.dtype
    bn, bk = min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bk % group == 0
    assert block_expert.shape == (m // bm,)
    if num_active is None:
        num_active = _full_blocks(m, bm)
    nk = k // bk
    grid = (m // bm, n // bn, nk)

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, kk, be, na: (i, kk))
    s_spec = pl.BlockSpec(
        (1, bk // group, bn), lambda i, j, kk, be, na: (be[i], kk, j)
    )
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk, be, na: (i, j))
    w_specs, planes = _w_specs_and_planes(w_packed, bits, bk, bn)
    args = (block_expert, num_active, x_sorted, *planes, scale, zero)

    compute_dtype = jnp.float32 if x_sorted.dtype == jnp.float32 else jnp.bfloat16

    def kernel(be_ref, na_ref, x_ref, *rest):
        # squeeze the leading expert dim of the weight/scale tiles
        w_tile, rest = _take_w_tile(list(rest), bits)
        s_ref, z_ref, o_ref, acc_ref = rest
        s_t, z_t = s_ref[0], z_ref[0]

        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # ragged skip: blocks past the routed-row frontier never unpack,
        # dequantize or touch the MXU — their accumulator stays zero
        @pl.when(pl.program_id(0) < na_ref[0])
        def _compute():
            bk_ = x_ref.shape[1]
            bn_ = o_ref.shape[1]
            codes = _unpack_tile(w_tile, bits, bk_, bn_)
            w = _dequant(codes, s_t, z_t, group, compute_dtype)
            acc_ref[...] += jnp.dot(
                x_ref[...].astype(compute_dtype),
                w,
                preferred_element_type=jnp.float32,
            )

        @pl.when(pl.program_id(2) == nk - 1)
        def _done():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[x_spec, *w_specs, s_spec, s_spec],
        out_specs=o_spec,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "group", "bm", "bn", "bk", "out_dtype", "interpret"),
)
def moe_gmm_swiglu_pallas(
    x_sorted: jnp.ndarray,
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
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused gate/up grouped GEMM with the SwiGLU epilogue.

    ``y = silu(x @ dequant(Wg)) * (x @ dequant(Wu))`` per row-block's
    expert. The two projections accumulate in separate VMEM scratches
    off a single streamed ``x`` tile; the nonlinearity runs on the f32
    accumulators right before the one output write, so the [M, F] hidden
    never exists in HBM. Same ragged ``num_active`` semantics as
    :func:`moe_gmm_pallas`.
    """
    m, k = x_sorted.shape
    if bits == 3:
        e, _, n = wg_packed[0].shape
    else:
        e, _, n = wg_packed.shape
    out_dtype = out_dtype or x_sorted.dtype
    bn, bk = min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bk % group == 0
    assert block_expert.shape == (m // bm,)
    if num_active is None:
        num_active = _full_blocks(m, bm)
    nk = k // bk
    grid = (m // bm, n // bn, nk)

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, kk, be, na: (i, kk))
    s_spec = pl.BlockSpec(
        (1, bk // group, bn), lambda i, j, kk, be, na: (be[i], kk, j)
    )
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk, be, na: (i, j))
    g_specs, g_planes = _w_specs_and_planes(wg_packed, bits, bk, bn)
    u_specs, u_planes = _w_specs_and_planes(wu_packed, bits, bk, bn)
    args = (
        block_expert, num_active, x_sorted, *g_planes, *u_planes,
        g_scale, g_zero, u_scale, u_zero,
    )

    compute_dtype = jnp.float32 if x_sorted.dtype == jnp.float32 else jnp.bfloat16

    def kernel(be_ref, na_ref, x_ref, *rest):
        g_tile, rest = _take_w_tile(list(rest), bits)
        u_tile, rest = _take_w_tile(rest, bits)
        gs_ref, gz_ref, us_ref, uz_ref, o_ref, accg_ref, accu_ref = rest

        @pl.when(pl.program_id(2) == 0)
        def _init():
            accg_ref[...] = jnp.zeros_like(accg_ref)
            accu_ref[...] = jnp.zeros_like(accu_ref)

        @pl.when(pl.program_id(0) < na_ref[0])
        def _compute():
            bk_ = x_ref.shape[1]
            bn_ = o_ref.shape[1]
            xt = x_ref[...].astype(compute_dtype)
            wg = _dequant(
                _unpack_tile(g_tile, bits, bk_, bn_),
                gs_ref[0], gz_ref[0], group, compute_dtype,
            )
            accg_ref[...] += jnp.dot(xt, wg, preferred_element_type=jnp.float32)
            wu = _dequant(
                _unpack_tile(u_tile, bits, bk_, bn_),
                us_ref[0], uz_ref[0], group, compute_dtype,
            )
            accu_ref[...] += jnp.dot(xt, wu, preferred_element_type=jnp.float32)

        @pl.when(pl.program_id(2) == nk - 1)
        def _done():
            h = jax.nn.silu(accg_ref[...]) * accu_ref[...]
            o_ref[...] = h.astype(o_ref.dtype)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[x_spec, *g_specs, *u_specs, s_spec, s_spec, s_spec, s_spec],
        out_specs=o_spec,
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


class _Squeezed:
    """Adapter presenting ``ref[0]`` as a 2-D ref for ``_unpack_tile``."""

    def __init__(self, ref):
        self._ref = ref

    def __getitem__(self, idx):
        return self._ref[0][idx] if idx is not Ellipsis else self._ref[0]

    @property
    def shape(self):
        return self._ref.shape[1:]


def sort_by_expert(
    tokens: jnp.ndarray, expert_ids: jnp.ndarray, num_experts: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stable-sort rows by expert id.

    Returns ``(sorted_tokens, sort_idx, group_sizes)`` where
    ``group_sizes[e]`` counts rows routed to expert e.
    """
    order = jnp.argsort(expert_ids, stable=True)
    sorted_tokens = tokens[order]
    group_sizes = jnp.bincount(expert_ids, length=num_experts)
    return sorted_tokens, order, group_sizes


def pad_groups(
    sorted_tokens: jnp.ndarray,
    group_sizes: jnp.ndarray,
    bm: int,
    capacity: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scatter each expert's rows into a bm-aligned, fixed-capacity layout.

    Static-shape friendly (jit-safe): every expert gets ``capacity`` rows
    (capacity % bm == 0); rows beyond capacity are dropped (standard
    capacity-factor semantics). Returns ``(x_padded [E*capacity, K],
    block_expert [E*capacity/bm], row_map [T] -> padded index or -1)``.

    The *compacted* variant of this layout — groups packed back-to-back at
    bm boundaries with a ``num_active`` block count instead of a fixed
    per-expert stride — is built by
    :func:`repro.core.compressed_moe.ragged_expert_ffn` from the routed
    pairs, and by :func:`repro.core.compressed_moe.grouped_bucket_ffn`
    from the capacity-dispatch layout.
    """
    e = group_sizes.shape[0]
    assert capacity % bm == 0
    t = sorted_tokens.shape[0]
    starts = jnp.concatenate([jnp.zeros(1, group_sizes.dtype), jnp.cumsum(group_sizes)[:-1]])
    row_expert = jnp.repeat(
        jnp.arange(e), group_sizes, total_repeat_length=t
    )
    rank_in_group = jnp.arange(t) - starts[row_expert]
    dest = row_expert * capacity + rank_in_group
    valid = rank_in_group < capacity
    dest = jnp.where(valid, dest, t * 0 + e * capacity)  # overflow bucket
    x_padded = jnp.zeros(
        (e * capacity + 1, sorted_tokens.shape[1]), sorted_tokens.dtype
    )
    x_padded = x_padded.at[dest].set(sorted_tokens)[: e * capacity]
    block_expert = jnp.repeat(jnp.arange(e, dtype=jnp.int32), capacity // bm)
    row_map = jnp.where(valid, dest, -1)
    return x_padded, block_expert, row_map
