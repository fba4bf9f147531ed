"""Pallas TPU kernel: fused two-level quantize + microscaled FP8 GEMM.

This is the steady-state operator of the MOSS training path (paper
Fig. 3b): the activation (forward) or gradient (backward-dx) enters in
bf16/f32 and leaves as a finished GEMM accumulation — the quantizer
never round-trips through HBM.  Per (bm, bk) LHS tile the kernel

  1. groups 32-wide micro-groups (on the sublane axis of the
     transposed tile — kernels/mx_tile.py), takes amaxes,
  2. derives the E8M0 level-2 exponents against the (precomputed)
     level-1 global scale,
  3. performs the saturating FP8 cast,
  4. applies the exponent-only operand rescale (exact in bf16), and
  5. runs the MXU dot against the FP8 RHS tile,

emitting the f32 accumulation *and* the quantized payload (q, sexp) so
the custom-VJP can keep the FP8 residual for the backward pass without
a second quantization pass.  The single f32 epilogue multiply
(s_x · s_w) happens outside in the dispatch layer.

Grid (M/bm, N/bn, K/bk), K innermost.  With ``resident`` (the
default) each (bm, bk) LHS tile is quantized once per call, on the
first N block (j == 0): that step writes q and sexp, and stores the
tile's MXU operand q·2^e (bf16, transposed, as ``dot_t`` takes it) into
a VMEM scratch that holds the whole row block, (K/bk, bk, bm).  Every
later N block of the row reads its operand from there and only
converts the fp8 weight tile and accumulates: no amax, no exponent, no
divide, no transpose, no fp8 cast.  The x, q and sexp block indices are
frozen at (i, K/bk - 1) while j > 0, so Pallas neither fetches x again
nor writes q and sexp back again.  j == 0 must run first in each row
block, so only the M axis is "parallel".  Without ``resident`` (a K too
wide for the row block's operand to stay in VMEM) every step quantizes
its tile into a one-tile scratch, and q/sexp are rewritten identically
on each N block.

The dot runs per 128-row slice of the operand, so an output row sums
the same products in the same order at any bm: the results do not
depend on the row block (the MXU works in 128-row tiles anyway).

VMEM working set (``vmem_bytes``): the operand scratch (K·bm·2 B, or
one tile's bk·bm·2 B) and the (bm, bn) f32 accumulator, plus
double-buffered x (bm·bk), qw (bk·bn), acc out (bm·bn·4), q (bm·bk) and
sexp (bk/32·bm·4) blocks.  ``vmem_limit_bytes`` is that plus
``VMEM_HEADROOM`` for the tile arithmetic's temporaries.  The dispatch
layer picks the blocks and ``resident`` from the shapes
(``dispatch._fused_blocks``), which bounds the operand at
``dispatch.FUSED_RESIDENT_MAX``; the olmo-7b head's input gradient
(K 50432, bm 128) holds the most, 12.9 MB of operand.

Operand contract (see docs/kernel-contract.md)
----------------------------------------------
  x         (M, K) f32/bf16 — the unquantized LHS
  s_global  ()     f32      — precomputed level-1 scale (one amax over
                              x, done by the dispatch layer)
  qw        (K, N) fp8      — per-tensor-quantized RHS *payload*; its
                              f32 scale s_w stays with the caller
  returns   acc (M, N) f32 UNSCALED, q (M, K) fp8, sexp (M, K//32) int8
            (the pallas_call writes sexp in the (M/bm, K/32, bm) int32
            tile layout of kernels/mx_tile.py; the wrapper converts back)

Two-level scale convention: the effective scale of LHS micro-group g is
``s_global · 2^sexp[g]`` with ``2^sexp ∈ (0, 1]``; the kernel applies
only the exponent part on the operand path (exact in bf16), so the
caller's single epilogue multiply is ``acc · s_global · s_w``.

Padding is CALLER-owned (repro.kernels.dispatch): M and N zero-padded
to block multiples, K to a micro-group multiple; this function only
*asserts* divisibility.  Zero padding is exact — a zero micro-group
quantizes to q = 0 at the E8M0 floor and contributes nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import E4M3_MAX, E5M2_MAX

from .mx_tile import (MICRO, TILE_DTYPE, dot_t, quant_tile,
                      scaled_operand_t, sexp_from_tiles, untranspose)


# Room above the blocks and scratch for the tile arithmetic's own
# temporaries (the f32 and transposed copies of an x tile, the bf16
# weight tile); well under the v5e's 128 MiB of VMEM.
VMEM_HEADROOM = 8 * 2 ** 20

# rows of one MXU dot: the dot's shape, so its summation order, is the
# same at every bm
ROWS = 128


def vmem_bytes(bm: int, bn: int, bk: int, k: int, x_itemsize: int,
               resident: bool = True) -> int:
    """VMEM the kernel's blocks and scratch take at these block sizes
    (blocks double-buffered), for a K-wide x of ``x_itemsize`` bytes."""
    scratch = (k if resident else bk) * bm * 2 + bm * bn * 4
    blocks = (bm * bk * x_itemsize + bk * bn + bm * bn * 4 + bm * bk
              + bk // MICRO * bm * 4)
    return scratch + 2 * blocks


def _fused_quant_gemm_kernel(x_ref, s_ref, qw_ref, o_ref, q_ref, se_ref,
                             xs_ref, acc_ref, *, n_k: int, fp8_max: float,
                             q_dtype, resident: bool):
    j, kk = pl.program_id(1), pl.program_id(2)
    slot = kk if resident else 0

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _quantize():
        x = x_ref[...].astype(jnp.float32)                # (bm, bk)
        e, q = quant_tile(x, s_ref[0, 0], fp8_max=fp8_max,
                          q_dtype=q_dtype)
        se_ref[0] = e.astype(TILE_DTYPE)                  # (bk/32, bm)
        q_ref[...] = untranspose(q)
        # operand path: quantized values × 2^e (exponent-only; exact in
        # bf16), kept for the row block's other N blocks
        xs_ref[slot] = scaled_operand_t(q, e)

    if resident:
        pl.when(j == 0)(_quantize)
    else:
        _quantize()

    w = qw_ref[...].astype(jnp.bfloat16)                  # (bk, bn)
    bm = acc_ref.shape[0]
    rows = ROWS if bm % ROWS == 0 else bm
    for r in range(0, bm, rows):
        acc_ref[r:r + rows] += dot_t(xs_ref[slot, :, r:r + rows], w)

    @pl.when(kk == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("fmt", "bm", "bn", "bk", "resident",
                                    "interpret"))
def fused_quant_gemm_pallas(x, s_global, qw, *, fmt: str = "e4m3",
                            bm: int = 128, bn: int = 128, bk: int = 512,
                            resident: bool = True,
                            interpret: bool = False):
    """x: (M, K) f32/bf16; s_global: () f32 level-1 scale; qw: (K, N)
    fp8 payload (e4m3/e5m2 per ``fmt``; the RHS f32 scale stays with
    the caller).  Returns (acc f32 (M, N) UNSCALED, q fp8 (M, K),
    sexp int8 (M, K//32)); the caller applies the s_x·s_w epilogue and
    owns the residual.  The caller also owns padding: M % bm == 0,
    N % bn == 0, K % bk == 0 and bk % 32 == 0 are asserted, never
    fixed up here (see the module docstring / docs/kernel-contract.md).
    ``resident`` keeps the row block's operand in VMEM over the N blocks
    (K·bm·2 B of scratch); without it every step quantizes its tile."""
    m, k = x.shape
    n = qw.shape[1]
    assert k == qw.shape[0] and k % MICRO == 0
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"(M,N,K)=({m},{n},{k}) not divisible by blocks ({bm},{bn},{bk})"
    assert bk % MICRO == 0
    fp8max = E4M3_MAX if fmt == "e4m3" else E5M2_MAX
    q_dtype = jnp.float8_e4m3fn if fmt == "e4m3" else jnp.float8_e5m2
    n_k = k // bk

    def lhs_k(j, kk):
        # frozen while j > 0: x is not fetched again, q/sexp not rewritten
        return jnp.where(j == 0, kk, n_k - 1) if resident else kk

    acc, q, sexp = pl.pallas_call(
        functools.partial(_fused_quant_gemm_kernel, n_k=n_k,
                          fp8_max=fp8max, q_dtype=q_dtype,
                          resident=resident),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, lhs_k(j, kk))),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, lhs_k(j, kk))),
            pl.BlockSpec((1, bk // MICRO, bm),
                         lambda i, j, kk: (i, lhs_k(j, kk), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, k), q_dtype),
            jax.ShapeDtypeStruct((m // bm, k // MICRO, bm), TILE_DTYPE),
        ],
        scratch_shapes=[pltpu.VMEM((n_k if resident else 1, bk, bm),
                                   jnp.bfloat16),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(bm, bn, bk, k, x.dtype.itemsize,
                                        resident) + VMEM_HEADROOM),
    )(x, s_global.reshape(1, 1), qw)
    return acc, q, sexp_from_tiles(sexp)
