"""Grouped (ragged) quantized expert matmul — the MoE hot loop.

MegaBlocks-style dropless expert GEMM adapted to TPU + PMQ quantization
(DESIGN.md §5.4): tokens are pre-sorted by expert id and padded so each
expert's row range is a multiple of ``bm``; a scalar-prefetch array
``block_expert [M/bm]`` tells each row-block which expert's packed weight
tile to fetch.

Because every PMQ bit-width rides the same (scale, zero) affine form
(1-bit: scale=2α, zero=0.5 — see ``quantize_to_packed``), a *bit-bucketed*
MoE layer issues one ``moe_gmm`` per bucket with experts of equal width.

**Dequantisation: the products, not the weights.** With group-wise
affine codes, group ``g`` of ``group`` K rows gives

    x_g @ ((q_g − z_g)·s_g) = s_g ⊙ (x_g @ q_g − z_g ⊙ rowsum(x_g))

so a live grid step never forms a float weight. Per group it unpacks the
codes, centred as ``q_g − c`` (``c`` is ``2^(bits−1)``, and 0 for 1-bit
codes; exact in bf16, whose 8 significant bits hold every 4-bit code),
takes one MXU product of
depth ``group`` into a ``[bm, bn]`` f32 partial, and applies ``s_g`` and
``(z_g − c)·rowsum(x_g)`` to that partial. The vector unit does two
shifts and a convert per weight; the affine work shrinks from ``bk·bn``
to ``bm·bn·bk/group`` elements. Every caller uses
``bm ≤ group`` (``gmm_block_rows`` targets 16), where the epilogue costs
at most what the per-weight affine did. Products accumulate in f32 from
unrounded codes, so the result is at least as exact as dequantising the
weights to the compute dtype first.

**Plane order.** A packed byte row holds ``per = 8/bits`` consecutive K
rows, the ``i``-th at bit ``i·bits``. Each bit plane, the ``i``-th field
of every byte, is unpacked as its own dense ``[group/per, bn]`` tile, and the
planes are stacked plane-major: no ``[.., per, bn]`` broadcast and no
sublane-interleaving reshape. So within each group of ``group`` columns
``x`` must arrive in the same order: position ``i·(group/per) + r`` holds
column ``r·per + i`` (:func:`_x_planes`). The ``*_pallas`` entry points
take ``x`` in its natural order and transpose its columns once per call,
in XLA, before the kernel. 3-bit codes are ``2·hi + lo`` from a 2-bit plane
pair (``per`` 4) and a 1-bit plane (``per`` 8), which come out in
different orders: the kernel is given ``x`` in both and adds
``2·(x₄ @ hi) + x₈ @ lo`` per group, so neither plane is reordered.

**Ragged-length handling**: the caller
(:mod:`repro.core.compressed_moe`) gives the kernel one row per routed
(token, choice) pair, sorted by expert, each expert's group padded to
``bm`` rows, in a static-shape buffer whose extent is a bound on the
routed pairs (``grouped_extent``), not the experts' capacity. The grid
follows ``x_sorted.shape[0]``, so it walks about ``experts + pairs/bm``
row blocks. ``num_active [1]`` (second scalar-prefetch operand) tells
the kernel how many leading row-blocks carry tokens; blocks past it skip
the unpack and the MXU work and write zeros, so the jitted program keeps
its static shape whatever the routing.

**SwiGLU epilogue** (:func:`moe_gmm_swiglu_pallas`): the gate and up
projections share their ``x`` tile and accumulate side by side in VMEM;
the epilogue applies ``silu(acc_g) · acc_u`` before the single output
write, so the [M, F] hidden tile never round-trips HBM between the two
GEMMs and ``x`` streams from HBM once instead of twice.

Layouts
-------
* ``x_sorted``:  [Mp, K]   tokens sorted by expert, bm-padded per expert;
  the kernel reads it in plane order (one copy per plane width)
* ``w_packed``:  [E, K/per, N] uint8 (or (hi [E,K/4,N], lo [E,K/8,N]) for 3-bit),
  the PMQ packed format of :mod:`repro.core.packing`, unchanged
* ``scale/zero``:[E, K/group, N] f32
* ``block_expert``: [Mp/bm] int32 — expert id per row-block (scalar prefetch)
* ``num_active``: [1] int32 — row-blocks carrying routed tokens (scalar
  prefetch; blocks ≥ it are skipped and zero-filled)
* grid (Mp/bm, N/bn, K/bk), K innermost, f32 scratch accumulator; ``bk``
  a multiple of ``group``, which is a multiple of ``per`` (on the TPU of
  ``8·per``, so each plane's rows of a group fill whole int32 tiles).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "moe_gmm_pallas",
    "moe_gmm_swiglu_pallas",
    "pad_groups",
    "sort_by_expert",
]


def _pers(bits: int) -> Tuple[int, ...]:
    """Codes per byte of each packed plane operand (3-bit: hi, lo)."""
    return (4, 8) if bits == 3 else (8 // bits,)


def _x_planes(x: jnp.ndarray, bits: int, group: int):
    """``x`` in each plane operand's column order: within each group,
    position ``i·(group/per) + r`` takes column ``r·per + i`` (plane-major,
    byte row within plane), a transpose of ``[group/per, per]`` blocks."""
    m, k = x.shape
    return [
        x.reshape(m, k // group, group // per, per).swapaxes(2, 3).reshape(m, k)
        for per in _pers(bits)
    ]


def _w_specs_and_planes(w_packed, bits: int, bk: int, bn: int):
    """BlockSpecs + flat plane list for one packed weight operand."""
    planes = list(w_packed) if bits == 3 else [w_packed]
    specs = [
        pl.BlockSpec((1, bk // per, bn), lambda i, j, kk, be, na: (be[i], kk, j))
        for per in _pers(bits)
    ]
    return specs, planes


def _group_codes(packed: jnp.ndarray, per: int, g: int, group: int, dtype):
    """Group ``g``'s codes ``[group, bn]`` in plane order from an int32
    ``[bk/per, bn]`` tile of packed bytes: one dense two-op extract of
    each plane over the group's ``group/per`` byte rows, stacked
    plane-major.

    A multi-bit field is extracted sign-extended (to the word's top by a
    left shift, down by an arithmetic right shift) after each field's top
    bit is flipped once per byte, which gives ``q − 2^(bits−1)``: centred
    codes, so the f32 partial products stay small, for the two shifts an
    unsigned extract (shift, mask) would take. 1-bit codes stay 0/1.
    """
    rows = group // per
    bits = 8 // per
    byte = packed[g * rows:(g + 1) * rows]
    planes = []
    if bits == 1:
        for i in range(per):
            v = byte >> i if i else byte
            planes.append(v & 1 if i < per - 1 else v)
    else:
        tops = sum(1 << (i * bits + bits - 1) for i in range(per))
        word = (byte ^ tops) << 24
        for i in range(per):
            up = 8 - (i + 1) * bits
            planes.append((word << up if up else word) >> (32 - bits))
    return jnp.concatenate(
        [v.astype(jnp.float32).astype(dtype) for v in planes], axis=0
    )


def _accumulate(acc_ref, xs, rowsums, w_refs, s_ref, z_ref, *, bits, group,
                dtype):
    """``acc += x @ dequant(W)`` for one grid step, scaled per group after
    the MXU (module docstring). ``xs`` holds the x tile in each plane
    operand's order; ``rowsums`` each group's ``[bm, 1]`` f32 row sums."""
    packed = [w[0].astype(jnp.int32) for w in w_refs]
    s_t, z_t = s_ref[0], z_ref[0]
    # what the unpacked codes are offset by: multi-bit fields come out as
    # q − 2^(bits−1), 1-bit ones as q, so 3-bit 2·hi + lo is q − 4
    centre = 1 << (bits - 1) if bits > 1 else 0
    total = None
    for g in range(s_t.shape[0]):
        cols = slice(g * group, (g + 1) * group)
        prods = [
            jnp.dot(
                x[:, cols], _group_codes(p, per, g, group, dtype),
                preferred_element_type=jnp.float32,
            )
            for x, p, per in zip(xs, packed, _pers(bits))
        ]
        prod = 2.0 * prods[0] + prods[1] if bits == 3 else prods[0]
        s_g, z_g = s_t[g:g + 1], z_t[g:g + 1]
        part = (prod - rowsums[g] * (z_g - centre)) * s_g
        total = part if total is None else total + part
    acc_ref[...] += total


def _x_tiles(x_refs, group: int, dtype):
    """The step's x tiles in the compute dtype and each group's row sums
    ``[bm, 1]`` in f32 (a sum does not depend on the column order)."""
    xs = [r[...].astype(dtype) for r in x_refs]
    xf = xs[-1].astype(jnp.float32)
    rowsums = [
        jnp.sum(xf[:, g:g + group], axis=1, keepdims=True)
        for g in range(0, xf.shape[1], group)
    ]
    return xs, rowsums


def _full_blocks(m: int, bm: int) -> jnp.ndarray:
    return jnp.full((1,), m // bm, jnp.int32)


def _check_group(bits: int, group: int, bk: int):
    assert bk % group == 0
    assert all(group % per == 0 for per in _pers(bits)), (bits, group)


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

    ``x_sorted`` is in its natural column order; it is transposed into
    plane order here, once per call (module docstring). ``num_active [1]``
    (optional) marks how many leading row-blocks carry routed tokens;
    blocks past it are zero-filled without touching the MXU (ragged
    capacity layouts pass the bm-padded routed-row count).
    """
    m, k = x_sorted.shape
    e, _, n = (w_packed[0] if bits == 3 else w_packed).shape
    out_dtype = out_dtype or x_sorted.dtype
    bn, bk = min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    _check_group(bits, group, bk)
    assert block_expert.shape == (m // bm,)
    if num_active is None:
        num_active = _full_blocks(m, bm)
    nk = k // bk
    grid = (m // bm, n // bn, nk)

    xs = _x_planes(x_sorted, bits, group)
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, kk, be, na: (i, kk))
    s_spec = pl.BlockSpec(
        (1, bk // group, bn), lambda i, j, kk, be, na: (be[i], kk, j)
    )
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk, be, na: (i, j))
    w_specs, planes = _w_specs_and_planes(w_packed, bits, bk, bn)
    nx, nw = len(xs), len(planes)
    args = (block_expert, num_active, *xs, *planes, scale, zero)

    compute_dtype = jnp.float32 if x_sorted.dtype == jnp.float32 else jnp.bfloat16

    def kernel(be_ref, na_ref, *rest):
        x_refs, w_refs = rest[:nx], rest[nx:nx + nw]
        s_ref, z_ref, o_ref, acc_ref = rest[nx + nw:]

        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # ragged skip: blocks past the routed-row frontier never unpack or
        # touch the MXU — their accumulator stays zero
        @pl.when(pl.program_id(0) < na_ref[0])
        def _compute():
            xt, rowsums = _x_tiles(x_refs, group, compute_dtype)
            _accumulate(
                acc_ref, xt, rowsums, w_refs, s_ref, z_ref,
                bits=bits, group=group, dtype=compute_dtype,
            )

        @pl.when(pl.program_id(2) == nk - 1)
        def _done():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[*[x_spec] * nx, *w_specs, s_spec, s_spec],
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
    off a single streamed ``x`` tile (in plane order, as in
    :func:`moe_gmm_pallas`); the nonlinearity runs on the f32
    accumulators right before the one output write, so the [M, F] hidden
    never exists in HBM. Same ragged ``num_active`` semantics as
    :func:`moe_gmm_pallas`.
    """
    m, k = x_sorted.shape
    e, _, n = (wg_packed[0] if bits == 3 else wg_packed).shape
    out_dtype = out_dtype or x_sorted.dtype
    bn, bk = min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    _check_group(bits, group, bk)
    assert block_expert.shape == (m // bm,)
    if num_active is None:
        num_active = _full_blocks(m, bm)
    nk = k // bk
    grid = (m // bm, n // bn, nk)

    xs = _x_planes(x_sorted, bits, group)
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, kk, be, na: (i, kk))
    s_spec = pl.BlockSpec(
        (1, bk // group, bn), lambda i, j, kk, be, na: (be[i], kk, j)
    )
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk, be, na: (i, j))
    g_specs, g_planes = _w_specs_and_planes(wg_packed, bits, bk, bn)
    u_specs, u_planes = _w_specs_and_planes(wu_packed, bits, bk, bn)
    nx, nw = len(xs), len(g_planes)
    args = (
        block_expert, num_active, *xs, *g_planes, *u_planes,
        g_scale, g_zero, u_scale, u_zero,
    )

    compute_dtype = jnp.float32 if x_sorted.dtype == jnp.float32 else jnp.bfloat16

    def kernel(be_ref, na_ref, *rest):
        x_refs = rest[:nx]
        g_refs, u_refs = rest[nx:nx + nw], rest[nx + nw:nx + 2 * nw]
        gs_ref, gz_ref, us_ref, uz_ref, o_ref, accg_ref, accu_ref = (
            rest[nx + 2 * nw:]
        )

        @pl.when(pl.program_id(2) == 0)
        def _init():
            accg_ref[...] = jnp.zeros_like(accg_ref)
            accu_ref[...] = jnp.zeros_like(accu_ref)

        @pl.when(pl.program_id(0) < na_ref[0])
        def _compute():
            xt, rowsums = _x_tiles(x_refs, group, compute_dtype)
            for acc_ref, w_refs, s_ref, z_ref in (
                (accg_ref, g_refs, gs_ref, gz_ref),
                (accu_ref, u_refs, us_ref, uz_ref),
            ):
                _accumulate(
                    acc_ref, xt, rowsums, w_refs, s_ref, z_ref,
                    bits=bits, group=group, dtype=compute_dtype,
                )

        @pl.when(pl.program_id(2) == nk - 1)
        def _done():
            h = jax.nn.silu(accg_ref[...]) * accu_ref[...]
            o_ref[...] = h.astype(o_ref.dtype)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[*[x_spec] * nx, *g_specs, *u_specs, s_spec, s_spec, s_spec, s_spec],
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
