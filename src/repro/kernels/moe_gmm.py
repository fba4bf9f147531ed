"""Pallas TPU kernels: MOSS-quantized grouped-expert GEMM (MoE hot path).

The MoE expert FFN used to run as ``jax.vmap`` over per-expert
``qlinear`` calls: E independent fused-quant GEMMs over the
capacity-padded ``(E, C, d)`` dispatch buffer, each with its own global
amax reduction — 3·E kernel launches + E reductions per MoE block.
These kernels collapse that to one launch per GEMM (up / gate / down)
and ONE level-1 amax over the whole token buffer:

``moe_gmm_pallas``
    Fused two-level quantize + grouped GEMM.  The flat sorted token
    buffer ``(E·C, K)`` — expert ``e`` owns rows ``[e·C, e·C+sizes[e])``,
    the remainder of each capacity slot is zero — is quantized exactly
    like ``mx_fused.py`` (one global scale, per-micro-group E8M0
    exponents, fp8 residual emitted for the backward) and every row
    block is multiplied against ITS expert's fp8 weight
    (``qw_stack[(i·bm)//C]``).  The ragged group sizes ride in as
    scalar-prefetch operands (SMEM): row blocks past a group's valid
    count skip the MXU dot entirely, so zero-size experts and
    capacity-padding rows cost no FLOPs.  Per-expert weight scales are
    applied row-wise in the dispatch-layer epilogue.

``moe_dw_gemm_pallas``
    The grouped dW backward: for every expert, ``requant_M(x̂_e)ᵀ @ Qg_e``
    over that expert's row range — the ``mx_bwd.py`` fusion
    (dequant → requant along tokens, level-1 scale pinned to
    s_x so it cancels in-kernel) with an extra expert grid dimension
    writing the stacked ``(E, K, N)`` weight gradient in one launch.

Both kernels require ``C % bm == 0`` so a row block never straddles an
expert boundary (the dispatch layer picks ``bm`` from the capacity and
pads per-expert rows to a micro-group multiple for dW).  Semantics are
defined over ALL ``E·C`` rows — group sizes are a compute-skipping hint
that is exact because rows beyond a group's size are zero (amax of a
zero micro-group clamps to the E8M0 floor → q = 0 → contributes 0).

Operand contract (see docs/kernel-contract.md)
----------------------------------------------
``moe_gmm_pallas``:
  x           (E·C, K)   f32/bf16 — flat sorted token buffer
  s_global    ()         f32      — ONE level-1 scale for the buffer
  qw_stack    (E, K, N)  fp8      — per-expert per-tensor payloads;
                                    the (E,) f32 scales stay with the
                                    caller (row-wise epilogue)
  group_sizes (E,)       int32    — scalar-prefetch (SMEM) operand
  returns acc (E·C, N) f32 UNSCALED, q (E·C, K) fp8,
          sexp (E·C, K//32) int8 (written in the (E·C/bm, K/32, bm)
          tile layout of kernels/mx_tile.py; the wrapper converts)
``moe_dw_gemm_pallas``:
  qx (E·C, K) fp8 + sexp (E·C, K//32) int8 — grouped forward residual
  qg (E·C, N) fp8 — gradient, ONE per-tensor scale for the buffer
  returns (E, K, N) f32 UNSCALED stacked dW

Two-level scale convention matches mx_fused/mx_bwd: fp8 payloads are
in units of their level-1 scale; epilogues (s_x·s_w[e] row-wise for
forward, s_x·s_g for dW) live in the dispatch layer.

Padding is CALLER-owned (repro.kernels.dispatch): N zero-padded to a
bn multiple, K to a micro-group multiple, and — for dW — each expert's
capacity slot padded to a 32-row multiple so along-token micro-groups
never straddle experts.  These functions assert, never pad.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import E4M3_MAX, E5M2_MAX

from .mx_tile import (MICRO, TILE_DTYPE, dequant_tile, dot_t, quant_tile,
                      requant_rows, scaled_operand_t, sexp_from_tiles,
                      sexp_to_tiles, untranspose)


# ---------------------------------------------------------------------------
# Forward / dx: fused two-level quantize + grouped GEMM
# ---------------------------------------------------------------------------


def _moe_gmm_kernel(sz_ref, x_ref, s_ref, qw_ref, o_ref, q_ref, se_ref,
                    acc_ref, *, n_k: int, cap: int, bm: int,
                    fp8_max: float, q_dtype):
    i = pl.program_id(0)
    kk = pl.program_id(2)
    e = (i * bm) // cap

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # quantize unconditionally: the residual must cover every row (zero
    # rows quantize to q=0 / sexp=-127, bit-identical to the reference)
    x = x_ref[...].astype(jnp.float32)                    # (bm, bk)
    ee, q = quant_tile(x, s_ref[0, 0], fp8_max=fp8_max, q_dtype=q_dtype)
    se_ref[0] = ee.astype(TILE_DTYPE)                     # (bk/32, bm)
    q_ref[...] = untranspose(q)

    # grouped MXU dot — skipped for row blocks past the group's count
    @pl.when((i * bm) % cap < sz_ref[e])
    def _dot():
        w = qw_ref[0].astype(jnp.bfloat16)                # (bk, bn)
        acc_ref[...] += dot_t(scaled_operand_t(q, ee), w)

    @pl.when(kk == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("capacity", "fmt", "bm", "bn", "bk",
                                    "interpret"))
def moe_gmm_pallas(x, s_global, qw_stack, group_sizes, *, capacity: int,
                   fmt: str = "e4m3", bm: int = 128, bn: int = 128,
                   bk: int = 512, interpret: bool = False):
    """x: (E·C, K) f32/bf16 grouped token buffer; s_global: () f32
    level-1 scale; qw_stack: (E, K, N) fp8; group_sizes: (E,) int32.
    Returns (acc f32 (E·C, N) UNSCALED, q fp8 (E·C, K), sexp int8
    (E·C, K//32)); the caller applies the s_x·s_w[e] row-wise epilogue
    and owns the residual.  Caller owns padding/alignment: C % bm == 0,
    N % bn == 0, K % bk == 0, bk % 32 == 0 are asserted, never fixed
    up here (docs/kernel-contract.md)."""
    t, k = x.shape
    e, kw, n = qw_stack.shape
    assert kw == k and k % MICRO == 0
    assert t == e * capacity, (t, e, capacity)
    assert group_sizes.shape == (e,)
    bm, bn, bk = min(bm, capacity), min(bn, n), min(bk, k)
    assert capacity % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"(C,N,K)=({capacity},{n},{k}) not divisible by ({bm},{bn},{bk})"
    assert bk % MICRO == 0
    fp8max = E4M3_MAX if fmt == "e4m3" else E5M2_MAX
    q_dtype = jnp.float8_e4m3fn if fmt == "e4m3" else jnp.float8_e5m2
    n_k = k // bk
    grid = (t // bm, n // bn, n_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, sz: (i, kk)),
            pl.BlockSpec((1, 1), lambda i, j, kk, sz: (0, 0)),
            pl.BlockSpec((1, bk, bn),
                         lambda i, j, kk, sz: ((i * bm) // capacity, kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk, sz: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j, kk, sz: (i, kk)),
            pl.BlockSpec((1, bk // MICRO, bm),
                         lambda i, j, kk, sz: (i, kk, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    acc, q, sexp = pl.pallas_call(
        functools.partial(_moe_gmm_kernel, n_k=n_k, cap=capacity, bm=bm,
                          fp8_max=fp8max, q_dtype=q_dtype),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((t, n), jnp.float32),
            jax.ShapeDtypeStruct((t, k), q_dtype),
            jax.ShapeDtypeStruct((t // bm, k // MICRO, bm), TILE_DTYPE),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(group_sizes, x, s_global.reshape(1, 1), qw_stack)
    return acc, q, sexp_from_tiles(sexp)


# ---------------------------------------------------------------------------
# dW: grouped requant-along-tokens GEMM (one launch for all experts)
# ---------------------------------------------------------------------------


def _moe_dw_kernel(sz_ref, qx_ref, se_ref, qg_ref, o_ref, acc_ref, *,
                   n_m: int, bm: int, fp8_max: float, q_dtype):
    ei = pl.program_id(0)
    mi = pl.program_id(3)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mi * bm < sz_ref[ei])
    def _dot():
        # dequant by the forward's level-2 exponents (units of s_x),
        # requant along M (tokens of THIS expert's row range); level-1
        # scale pinned to s_x, which cancels — see kernels/mx_bwd.py
        xd = dequant_tile(qx_ref[...], se_ref[0])         # (bm, bko)
        x_op = requant_rows(xd, fp8_max=fp8_max, q_dtype=q_dtype)
        g = qg_ref[...].astype(jnp.bfloat16)              # (bm, bn)
        acc_ref[...] += dot_t(x_op, g)

    @pl.when(mi == n_m - 1)
    def _done():
        o_ref[0] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("capacity", "fmt", "bm", "bn", "bko",
                                    "interpret"))
def moe_dw_gemm_pallas(qx, sexp, qg, group_sizes, *, capacity: int,
                       fmt: str = "e4m3", bm: int = 128, bn: int = 128,
                       bko: int = 256, interpret: bool = False):
    """qx: (E·C, K) fp8 forward residual; sexp: (E·C, K//32) int8;
    qg: (E·C, N) fp8 (per-tensor scaled); group_sizes: (E,) int32.
    Returns the UNSCALED f32 stacked weight gradient (E, K, N); the
    caller applies s_x·s_g in the epilogue.  Requires C % 32 == 0 so
    the along-token micro-groups never straddle an expert boundary."""
    t, k = qx.shape
    n = qg.shape[1]
    assert qg.shape[0] == t and sexp.shape == (t, k // MICRO)
    assert t % capacity == 0
    e = t // capacity
    assert group_sizes.shape == (e,)
    assert capacity % MICRO == 0, \
        f"C={capacity} must be a multiple of {MICRO} (dispatch pads)"
    bm, bn, bko = min(bm, capacity), min(bn, n), min(bko, k)
    assert capacity % bm == 0 and n % bn == 0 and k % bko == 0, \
        f"(C,N,K)=({capacity},{n},{k}) not divisible by ({bm},{bn},{bko})"
    assert bm % MICRO == 0 and bko % MICRO == 0
    fp8max = E4M3_MAX if fmt == "e4m3" else E5M2_MAX
    q_dtype = jnp.float8_e4m3fn if fmt == "e4m3" else jnp.float8_e5m2
    n_m = capacity // bm          # row blocks per expert slot
    grid = (e, k // bko, n // bn, n_m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bko),
                         lambda ei, ki, ni, mi, sz: (ei * n_m + mi, ki)),
            pl.BlockSpec((1, bko // MICRO, bm),
                         lambda ei, ki, ni, mi, sz: (ei * n_m + mi, ki, 0)),
            pl.BlockSpec((bm, bn),
                         lambda ei, ki, ni, mi, sz: (ei * n_m + mi, ni)),
        ],
        out_specs=pl.BlockSpec((1, bko, bn),
                               lambda ei, ki, ni, mi, sz: (ei, ki, ni)),
        scratch_shapes=[pltpu.VMEM((bko, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_moe_dw_kernel, n_m=n_m, bm=bm, fp8_max=fp8max,
                          q_dtype=q_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, k, n), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(group_sizes, qx, sexp_to_tiles(sexp, bm), qg)
