"""Pallas TPU kernel: per-group FP8 GEMM — the COAT baseline of paper
Fig 3a, implemented for the GEMM-efficiency ablation (paper Table 6).

y[m, n] = Σ_g ( Σ_{k∈g} Qx[m, k] · Qw[k, n] ) · s_x[m, g]

The per-128-group f32 scales sit along the GEMM inner dimension, so
every K-block's partial sum must be rescaled on the VPU *inside* the
accumulation loop: an O(bm·bn) f32 multiply-add per K-block — K/bk of
them — versus MOSS's single epilogue multiply.  With bk = group = 128
and bm = bn = 128 that is 128× more in-loop VPU work per output element
than mx_gemm's operand rescale, which is the paper's core efficiency
argument restated for TPU (DESIGN.md §2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


GROUP = 128


def _group_gemm_kernel(qx_ref, sx_ref, qw_ref, o_ref, acc_ref, *,
                       n_k: int, groups_per_block: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = qx_ref[...].astype(jnp.bfloat16)                  # (bm, bk)
    w = qw_ref[...].astype(jnp.bfloat16)                  # (bk, bn)
    bm = x.shape[0]
    if groups_per_block == 1:
        partial = jnp.dot(x, w, preferred_element_type=jnp.float32)
        # in-loop dequant: O(bm·bn) f32 multiply per K-block (the cost
        # MOSS's two-level scheme removes from the main loop)
        acc_ref[...] += partial * sx_ref[...]             # (bm,1) bcast
    else:
        bk = x.shape[1]
        g = bk // groups_per_block
        xg = x.reshape(bm, groups_per_block, g)
        for gi in range(groups_per_block):
            partial = jnp.dot(xg[:, gi], w[gi * g:(gi + 1) * g],
                              preferred_element_type=jnp.float32)
            acc_ref[...] += partial * sx_ref[:, gi][:, None]

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def group_gemm_pallas(qx, sx, qw, *, bm: int = 128, bn: int = 128,
                      bk: int = GROUP, interpret: bool = False):
    """qx: (M, K) fp8; sx: (M, K//128) f32 group scales; qw: (K, N) fp8.
    Returns f32 accumulation scaled by the activation group scales;
    the caller applies the per-tensor weight scale."""
    m, k = qx.shape
    n = qw.shape[1]
    assert k % GROUP == 0 and sx.shape == (m, k // GROUP)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bk % GROUP == 0 or GROUP % bk == 0
    gpb = max(bk // GROUP, 1)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_group_gemm_kernel, n_k=n_k,
                          groups_per_block=gpb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, gpb), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qx, sx, qw)
