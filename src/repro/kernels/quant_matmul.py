"""Fused dequantize-matmul Pallas TPU kernel (DESIGN.md §5.2/§6).

Computes ``y = x @ dequant(W_packed)`` without ever materializing the
dequantized weights in HBM: packed uint8 tiles stream HBM→VMEM, the VPU
unpacks + applies group-wise ``(q - zero) * scale``, and the MXU consumes
the bf16/f32 tile directly. This is the TPU-native replacement for the
paper's HQQ ATEN dequant kernels — the ultra-low-bit serving path is
memory-bound, so weight bytes are the roofline term this kernel attacks
(2-bit: 8× less HBM traffic than bf16).

Layouts
-------
* ``x``: [M, K] (bf16/f32)
* ``w_packed``: [K/per, N] uint8 (pow-2 widths) or the (hi, lo) plane pair
  for 3-bit (K/4 + K/8 rows — exactly 3.0 bits/weight)
* ``scale``/``zero``: [K/group, N] f32, quantization groups along K
* grid (M/bm, N/bn, K/bk) with K innermost ("arbitrary" semantics), f32
  VMEM scratch accumulator, ``bk`` a multiple of ``group``.

TPU tiling: each block's last two dims are multiples of (8, 128) or the
array's own, so the :mod:`repro.kernels.ops` wrappers pick ``bn`` as a
multiple of 128 dividing N and ``bk`` as a multiple of ``8·group``
dividing K (the scale block has ``bk/group`` rows), else the whole dim.
Codes unpack in int32, as Mosaic casts uint8 to no float type; the
[bk, bn] int32 and f32 tiles are the largest VMEM temporaries (1 MiB
each at 1024 x 256).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quant_matmul_pallas"]


def _unpack_tile(w_ref, bits: int, bk: int, bn: int) -> jnp.ndarray:
    """uint8 packed tile -> [bk, bn] int32 codes (VPU shifts, no HBM)."""
    if bits == 3:
        hi = _unpack_pow2_tile(w_ref[0][...], 2, bk, bn)
        lo = _unpack_pow2_tile(w_ref[1][...], 1, bk, bn)
        return (hi << 1) | lo
    return _unpack_pow2_tile(w_ref[...], bits, bk, bn)


def _unpack_pow2_tile(packed: jnp.ndarray, bits: int, bk: int, bn: int):
    per = 8 // bits
    # [bk/per, bn] -> [bk/per, per, bn] -> [bk, bn]. The shifts run in
    # int32: Mosaic casts uint8 only to wider integers, never to float.
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, per, 1), 1) * bits
    vals = (packed.astype(jnp.int32)[:, None, :] >> shifts) & ((1 << bits) - 1)
    return vals.reshape(bk, bn)


def _dequant(codes: jnp.ndarray, scale, zero, group: int, compute_dtype):
    bk, bn = codes.shape
    ng = bk // group
    c = codes.astype(jnp.float32).reshape(ng, group, bn)
    w = (c - zero[:, None, :]) * scale[:, None, :]
    return w.reshape(bk, bn).astype(compute_dtype)


def _kernel(
    x_ref,
    *rest,
    bits: int,
    group: int,
    nk: int,
    compute_dtype,
):
    if bits == 3:
        hi_ref, lo_ref, s_ref, z_ref, o_ref, acc_ref = rest
        w_ref = (hi_ref, lo_ref)
    else:
        w_ref, s_ref, z_ref, o_ref, acc_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk = x_ref.shape[1]
    bn = o_ref.shape[1]
    codes = _unpack_tile(w_ref, bits, bk, bn)
    w = _dequant(codes, s_ref[...], z_ref[...], group, compute_dtype)
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(compute_dtype), w, preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "group", "bm", "bn", "bk", "out_dtype", "interpret"),
)
def quant_matmul_pallas(
    x: jnp.ndarray,
    w_packed,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    *,
    bits: int,
    group: int = 128,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``y[M,N] = x[M,K] @ dequant(w_packed)``. See module docstring.

    The wrapper in :mod:`repro.kernels.ops` handles padding / transposes /
    platform fallback; this function requires M % bm == N % bn == K % bk ==
    0 and bk % group == 0.
    """
    m, k = x.shape
    if bits == 3:
        hi, lo = w_packed
        n = hi.shape[1]
    else:
        n = w_packed.shape[1]
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert bk % group == 0, (bk, group)
    nk = k // bk
    grid = (m // bm, n // bn, nk)

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    s_spec = pl.BlockSpec((bk // group, bn), lambda i, j, kk: (kk, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    if bits == 3:
        w_specs = [
            pl.BlockSpec((bk // 4, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // 8, bn), lambda i, j, kk: (kk, j)),
        ]
        args = (x, hi, lo, scale, zero)
    else:
        per = 8 // bits
        w_specs = [pl.BlockSpec((bk // per, bn), lambda i, j, kk: (kk, j))]
        args = (x, w_packed, scale, zero)

    compute_dtype = jnp.float32 if x.dtype == jnp.float32 else jnp.bfloat16
    kernel = functools.partial(
        _kernel, bits=bits, group=group, nk=nk, compute_dtype=compute_dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, *w_specs, s_spec, s_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
