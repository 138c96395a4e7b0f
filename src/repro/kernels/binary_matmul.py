"""1-bit (binary) matmul Pallas TPU kernel — paper §3.3 Eqs. 8/9.

The paper stores ``B~ = (sign(W)+1)/2 ∈ {0,1}`` packed 8/byte and computes
``s·xB`` with additions only on GPU. The MXU has no add-only mode, so the
TPU-native reading (DESIGN.md §5.1) is bandwidth: weights stream at 1/16th
of bf16 bytes; the VPU unpacks to ``±1``, applies the per-output-channel L1
scale ``alpha`` (Eq. 4), and the MXU runs a normal dot. Decode-time expert
GEMMs are memory-bound, so the 16× byte reduction is the realized speedup.

Layouts: ``x [M, K]``, ``b_packed [K/8, N] uint8``, ``alpha [1, N] f32``.
Grid (M/bm, N/bn, K/bk), K innermost, f32 scratch accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant_matmul import _unpack_pow2_tile

__all__ = ["binary_matmul_pallas"]


def _kernel(x_ref, b_ref, a_ref, o_ref, acc_ref, *, nk: int, compute_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk = x_ref.shape[1]
    bn = o_ref.shape[1]
    bits01 = _unpack_pow2_tile(b_ref[...], 1, bk, bn)
    w = (bits01.astype(jnp.float32) * 2 - 1).astype(compute_dtype)  # ±1; alpha at the end
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(compute_dtype), w, preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * a_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def binary_matmul_pallas(
    x: jnp.ndarray,
    b_packed: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``y = (x @ (2·unpack(b_packed)-1)) * alpha`` — Eq. 9 on the MXU."""
    m, k = x.shape
    n = b_packed.shape[1]
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bk % 8 == 0
    nk = k // bk
    compute_dtype = jnp.float32 if x.dtype == jnp.float32 else jnp.bfloat16
    kernel = functools.partial(_kernel, nk=nk, compute_dtype=compute_dtype)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 8, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, b_packed, alpha)
