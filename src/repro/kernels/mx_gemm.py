"""Pallas TPU kernel: two-level microscaled FP8 GEMM (paper Fig 3b,
TPU-native).

y[m, n] = Σ_k ( Qx[m, k] · 2^sexp[m, k/32] ) · Qw[k, n]

The grid is (M/bm, N/bn, K/bk), K innermost ("arbitrary"); the f32
accumulator lives in VMEM scratch.  Per K-block the E8M0 subscale is an
exponent-only multiply applied to the *operand tile* on the VPU —
O(bm·bk) cheap work — and the MXU dot runs on the rescaled bf16 tile
(transposed, micro-groups on the sublane axis; the exponents arrive in
the (M/bm, K/32, bm) tile layout of kernels/mx_tile.py).  The single
f32 epilogue multiply (s_x·s_w) happens OUTSIDE the kernel in the
dispatch layer (the paper's "dequant in the epilogue on CUDA cores").

Contrast with group_gemm.py (COAT baseline): there an O(bm·bn) f32
multiply-accumulate of the partial-sum tile runs per K-block inside the
loop — the overhead MOSS eliminates.

Block shapes default to (128, 128, 512): MXU-aligned (multiples of 128)
and a VMEM working set of
  bm·bk (fp8) + bk·bn (fp8) + bm·bn·4 (f32 acc) + bm·bk/8 (int32 se)
= 64K + 64K + 64K·4 + 8K ≈ 0.4 MiB ≪ 16 MiB VMEM, leaving room for
double buffering of the HBM→VMEM pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mx_tile import MICRO, dot_t, scaled_operand_t, sexp_to_tiles


def _mx_gemm_kernel(qx_ref, se_ref, qw_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # E8M0 level-2 subscale: exponent-only operand rescale (exact in bf16)
    xt = qx_ref[...].astype(jnp.float32).T                # (bk, bm)
    x_op = scaled_operand_t(xt, se_ref[0])
    w = qw_ref[...].astype(jnp.bfloat16)                  # (bk, bn)
    acc_ref[...] += dot_t(x_op, w)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def mx_gemm_pallas(qx, sexp, qw, *, bm: int = 128, bn: int = 128,
                   bk: int = 512, interpret: bool = False):
    """qx: (M, K) float8_e4m3fn; sexp: (M, K//32) int8; qw: (K, N) fp8.
    Returns the UNSCALED f32 accumulation (caller applies s_x·s_w)."""
    m, k = qx.shape
    n = qw.shape[1]
    assert k % MICRO == 0 and sexp.shape == (m, k // MICRO)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"(M,N,K)=({m},{n},{k}) not divisible by blocks ({bm},{bn},{bk})"
    assert bk % MICRO == 0
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_mx_gemm_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((1, bk // MICRO, bm), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qx, sexp_to_tiles(sexp, bm), qw)
