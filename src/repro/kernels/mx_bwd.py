"""Pallas TPU kernel: the dW backward GEMM of the MOSS custom-VJP.

  dW[k, n] = Σ_m requant_M(x̂)[k, m] · Qg[m, n]

where x̂ is the FP8 forward residual dequantized (x̂ = Qx · 2^sexp · s_x)
and ``requant_M`` re-quantizes the *transposed* activation with 32-wide
micro-groups along the token (M) dimension — the inner dimension of the
dW GEMM — so the level-2 scales again ride the operand and the single
f32 dequant stays in the epilogue (paper Fig. 3b applied to backward).

Key identity making the fusion cheap: re-quantizing against the SAME
level-1 scale s_x the forward used makes s_x cancel out of the in-kernel
arithmetic —

  x̂/s_x = Qx·2^sexp,    e' = ceil(log2(amax_M(x̂/s_x)/FP8_MAX)),
  q' = cast_fp8((x̂/s_x)/2^e'),

so the kernel needs only the fp8 residual + its exponents, never a f32
activation and never a second global amax reduction.  (This pins the dW
requant's level-1 scale to s_x; since every |x̂| ≤ FP8_MAX·s_x the ratio
is ≤ 1 and the E8M0 ceil guarantee still holds — same trade COAT makes
with its transposed quantized copy, minus the extra memory pass.)

Grid (K/bko, N/bn, M/bm), M (the contraction) innermost "arbitrary";
per M-block the kernel dequants Qx·2^sexp, requants along M (32-row
micro-groups on the sublane axis — kernels/mx_tile.py), rescales the
operand by 2^e', and accumulates the MXU dot (contracting M) with the
E5M2 gradient tile.  The forward exponents arrive in the
(M/bm, K/32, bm) tile layout of kernels/mx_tile.py.  Epilogue
(× s_x·s_g) happens in the dispatch layer.

Operand contract (see docs/kernel-contract.md)
----------------------------------------------
  qx      (M, K)      fp8  — the forward residual payload (E4M3)
  sexp    (M, K//32)  int8 — its level-2 E8M0 exponents
  qg      (M, N)      fp8  — per-tensor-quantized gradient payload
                             (E5M2 by default); s_g stays with caller
  returns (K, N) f32 UNSCALED dW accumulation

Two-level scale convention: both fp8 operands are in "units of their
level-1 scale" — qx·2^sexp ≡ x/s_x and qg ≡ g/s_g — so the caller's
epilogue is one multiply by s_x·s_g.  The in-kernel requant along M
re-uses s_x as its level-1 scale, which is why s_x never appears in
the kernel arithmetic.

Padding is CALLER-owned (repro.kernels.dispatch): M zero-padded to a
bm (and 32) multiple, N to bn, K to bko; the residual's K may carry
the forward's micro-group padding — the caller slices the result rows
back with ``out_rows`` in ``dispatch.mx_matmul_dw``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import E4M3_MAX, E5M2_MAX

from .mx_tile import MICRO, dequant_tile, dot_t, requant_rows, sexp_to_tiles


def _mx_dw_gemm_kernel(qx_ref, se_ref, qg_ref, o_ref, acc_ref, *,
                       n_m: int, fp8_max: float, q_dtype):
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequant by the forward's level-2 exponents (units of s_x), then
    # requant along M with level-1 scale = s_x (which cancels — see
    # module docstring)
    xd = dequant_tile(qx_ref[...], se_ref[0])             # (bm, bko)
    x_op = requant_rows(xd, fp8_max=fp8_max, q_dtype=q_dtype)
    g = qg_ref[...].astype(jnp.bfloat16)                  # (bm, bn)
    acc_ref[...] += dot_t(x_op, g)                        # (bko, bn)

    @pl.when(mi == n_m - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("fmt", "bm", "bn", "bko", "interpret"))
def mx_dw_gemm_pallas(qx, sexp, qg, *, fmt: str = "e4m3", bm: int = 128,
                      bn: int = 128, bko: int = 256,
                      interpret: bool = False):
    """qx: (M, K) fp8 forward residual; sexp: (M, K//32) int8; qg: (M, N)
    fp8 gradient (per-tensor scaled).  Returns the UNSCALED f32 dW
    accumulation (K, N); the caller applies s_x·s_g in the epilogue.
    Caller owns padding: M % 32 == 0 and block divisibility of (M, N,
    K) are asserted, never fixed up here."""
    m, k = qx.shape
    n = qg.shape[1]
    assert qg.shape[0] == m and sexp.shape == (m, k // MICRO)
    assert m % MICRO == 0, f"M={m} must be a multiple of {MICRO}"
    bm, bn, bko = min(bm, m), min(bn, n), min(bko, k)
    assert m % bm == 0 and n % bn == 0 and k % bko == 0, \
        f"(M,N,K)=({m},{n},{k}) not divisible by blocks ({bm},{bn},{bko})"
    assert bm % MICRO == 0 and bko % MICRO == 0
    fp8max = E4M3_MAX if fmt == "e4m3" else E5M2_MAX
    q_dtype = jnp.float8_e4m3fn if fmt == "e4m3" else jnp.float8_e5m2
    n_m = m // bm
    grid = (k // bko, n // bn, n_m)
    return pl.pallas_call(
        functools.partial(_mx_dw_gemm_kernel, n_m=n_m, fp8_max=fp8max,
                          q_dtype=q_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bko), lambda ki, ni, mi: (mi, ki)),
            pl.BlockSpec((1, bko // MICRO, bm),
                         lambda ki, ni, mi: (mi, ki, 0)),
            pl.BlockSpec((bm, bn), lambda ki, ni, mi: (mi, ni)),
        ],
        out_specs=pl.BlockSpec((bko, bn), lambda ki, ni, mi: (ki, ni)),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bko, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qx, sexp_to_tiles(sexp, bm), qg)
