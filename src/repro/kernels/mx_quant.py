"""Pallas TPU kernel: fused two-level microscaling quantizer
(paper Eqs. 2-3).

Per (bm, bk) tile: group amaxes over 32-wide micro-groups, E8M0
exponents relative to the (precomputed) level-1 global scale, and the
saturating E4M3/E5M2 cast — one HBM read of the bf16/f32 activation, one
fp8 write + one int8 exponent write.  This is the fusion that replaces
just-in-time scaling's multiple passes (paper §3.2's memory-traffic
argument applied to the activation path).

The global scale s = max_g(amax_g)/FP8_MAX needs a full reduction, so it
is computed OUTSIDE (one fused jnp.max) and passed in as a (1, 1) f32
operand.

Exponents leave the kernel in the (M/bm, K/32, bm) tile layout of
kernels/mx_tile.py (micro-groups on the sublane axis); the wrapper
returns them as (M, K/32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import E4M3_MAX, E5M2_MAX

from .mx_tile import (MICRO, TILE_DTYPE, quant_tile, sexp_from_tiles,
                      untranspose)


def _mx_quant_kernel(x_ref, s_ref, q_ref, se_ref, *, fp8_max: float,
                     out_dtype):
    x = x_ref[...].astype(jnp.float32)                    # (bm, bk)
    e, q = quant_tile(x, s_ref[0, 0], fp8_max=fp8_max, q_dtype=out_dtype)
    se_ref[0] = e.astype(TILE_DTYPE)                      # (bk/32, bm)
    q_ref[...] = untranspose(q)


@functools.partial(jax.jit, static_argnames=("fmt", "bm", "bk",
                                             "interpret"))
def mx_quant_pallas(x, s_global, *, fmt: str = "e4m3", bm: int = 256,
                    bk: int = 512, interpret: bool = False):
    """x: (M, K); s_global: () f32.  Returns (q fp8 (M,K), sexp int8
    (M, K//32))."""
    m, k = x.shape
    assert k % MICRO == 0
    bm, bk = min(bm, m), min(bk, k)
    assert m % bm == 0 and k % bk == 0 and bk % MICRO == 0
    fp8_max = E4M3_MAX if fmt == "e4m3" else E5M2_MAX
    out_dtype = jnp.float8_e4m3fn if fmt == "e4m3" else jnp.float8_e5m2
    grid = (m // bm, k // bk)
    q, sexp = pl.pallas_call(
        functools.partial(_mx_quant_kernel, fp8_max=fp8_max,
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((1, bk // MICRO, bm), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), out_dtype),
            jax.ShapeDtypeStruct((m // bm, k // MICRO, bm), TILE_DTYPE),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(x, s_global.reshape(1, 1))
    return q, sexp_from_tiles(sexp)
