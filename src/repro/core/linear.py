"""Quantized linear layers with custom VJP — the MOSS training integration.

``qmm(cfg, x, w, w_scale)`` computes ``x @ w`` under the configured FP8
recipe with a fully custom backward:

  forward   y  = MXFP8-GEMM(Qx, Qw) · s_x·s_w          (E4M3 operands)
  residuals fp8 Qx (+ E8M0 exponents + one f32) and fp8 Qw — this is the
            paper's 1.8× activation-memory saving: backward never needs
            the bf16 activation.
  backward  dx = MXFP8-GEMM(Qg, Qwᵀ)                    (g in E5M2)
            dW = MXFP8-GEMM(requant_M(Qx)ᵀ, Qg)         (inner dim = tokens)

Weight scales come from MOSS automatic scaling (``w_scale`` argument,
predicted by ``repro.core.autoscale``) so no max|W| reduction appears in
the steady-state HLO.

All four recipes are selectable for baseline comparisons: ``bf16``,
``per_tensor`` (TE-style), ``per_group`` (COAT-style), ``moss``.

Every quantized GEMM here (forward, dx, dW) goes through the unified
kernel dispatch (``repro.kernels.dispatch``): Pallas-native on TPU,
interpret-mode Pallas under ``REPRO_KERNELS=interpret``, pure-jnp
reference on CPU.  The MOSS forward and dx use the *fused*
quantize+GEMM kernel; dW uses the fused requant-along-tokens kernel
whose level-1 scale is pinned to the forward's s_x (it cancels inside
the kernel — kernels/mx_bwd.py).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .actscale import ActScale, REC
from .formats import QuantConfig
from .quant import (
    PerTensorQ,
    quant_mx_delayed,
    quant_per_group,
    quant_per_tensor,
)


class QT(NamedTuple):
    """A weight tensor bundled with its (possibly predicted) fp8 scale.

    ``s`` is None in bf16 mode or for never-quantized params (norms,
    routers, recurrence gates); model code unwraps ``.w`` for those.

    Serving fast path: ``w`` may arrive *already quantized* (fp8 dtype,
    from ``repro.core.quant.PrequantParams``) with ``s`` its build-time
    dequant scale — ``_quantize_w`` detects the dtype and skips the
    in-graph quantize + max-reduction entirely (docs/serving.md).

    ``a`` carries this GEMM site's *activation*-scale state on the
    delayed-activation serving path (``repro.core.actscale``):

      None               — default: activations quantize just-in-time
                           (in-graph amax), the training semantics
      ActScale           — calibrated delayed scales: ``qlinear`` takes
                           the reduction-free ``_qmm_delayed`` forward
      str (site tag)     — calibration only: ``qlinear`` records the
                           activation's amax under this tag and then
                           runs the normal just-in-time path
    """

    w: jax.Array
    s: jax.Array | None = None
    a: Any = None


def _is_fp8(w: jax.Array) -> bool:
    return w.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2)


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    """Zero-pad ``axis`` up to a multiple of ``mult`` (zeros are exact
    under all our quantizers: amax of a zero group is clamped to TINY)."""
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# custom_vjp core:  (cfg static) (x, w, w_scale) -> y
#   x: (..., K)   w: (K, N)   w_scale: f32 scalar or None-like scalar
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def qmm(cfg: QuantConfig, x: jax.Array, w: jax.Array,
        w_scale: jax.Array) -> jax.Array:
    y, _ = _qmm_fwd(cfg, x, w, w_scale)
    return y


@jax.named_scope("moss.scale")
def _quantize_w(cfg: QuantConfig, w: jax.Array, w_scale: jax.Array):
    """Per-tensor weight quantization.  With automatic scaling the scale
    is the *predicted* one — no max-reduction over w in the HLO.

    Pre-quantized serving weights (fp8 dtype, built once by
    ``prequantize_params``) pass straight through: ``w_scale`` is their
    build-time dequant scale and the graph contains neither the cast
    nor the reduction."""
    if _is_fp8(w):
        return PerTensorQ(q=w, s=jnp.asarray(w_scale, jnp.float32))
    if cfg.weight_cast_bf16:
        w = w.astype(jnp.bfloat16)
    if cfg.weight_scaling == "auto":
        return quant_per_tensor(w, cfg.fwd_format, scale=w_scale)
    return quant_per_tensor(w, cfg.fwd_format)  # jit/delayed: reduce now


def _fwd_gemm(cfg: QuantConfig, x2d: jax.Array, wq: PerTensorQ):
    """Forward GEMM via the unified kernel dispatch (repro.kernels.
    dispatch): Pallas-native on TPU, interpret-mode Pallas under
    REPRO_KERNELS=interpret, jnp reference on CPU."""
    from repro.kernels import dispatch

    if cfg.mode == "moss":
        # fused quantize+GEMM: one pass over x, residual (q, sexp)
        # emitted by the same kernel (paper Fig. 3b steady state)
        with jax.named_scope("moss.scale"):
            wq_p = PerTensorQ(q=_pad_axis(wq.q, 0, cfg.micro_group),
                              s=wq.s)
        y, xq = dispatch.fused_quant_matmul(
            _pad_axis(x2d, -1, cfg.micro_group), wq_p,
            fmt=cfg.fwd_format, micro_group=cfg.micro_group,
            out_dtype=jnp.float32)
        return y, xq
    if cfg.mode == "per_group":
        xq = quant_per_group(_pad_axis(x2d, -1, cfg.group_size),
                             cfg.group_size, cfg.fwd_format)
        wq_p = PerTensorQ(q=_pad_axis(wq.q, 0, cfg.group_size), s=wq.s)
        y = dispatch.group_matmul(xq, wq_p, out_dtype=jnp.float32)
        return y, xq
    # per_tensor
    xq = quant_per_tensor(x2d, cfg.fwd_format)
    return dispatch.pt_matmul(xq, wq, out_dtype=jnp.float32), xq


def _qmm_fwd(cfg: QuantConfig, x, w, w_scale):
    orig_dtype = x.dtype
    *lead, k = x.shape
    if cfg.mode == "bf16":
        from .runtime_flags import mm

        y = mm(x, w, out_dtype=jnp.float32)
        # residual: the bf16 activation (what MOSS avoids storing);
        # zero-size witnesses carry the primal dtypes for the cotangents
        return y.astype(orig_dtype), (x.astype(jnp.bfloat16),
                                      w.astype(jnp.bfloat16),
                                      jnp.zeros((0,), x.dtype),
                                      jnp.zeros((0,), w.dtype))
    x2d = x.reshape(-1, k)
    wq = _quantize_w(cfg, w, w_scale)
    y2d, xq = _fwd_gemm(cfg, x2d, wq)
    y = y2d.reshape(*lead, w.shape[-1]).astype(orig_dtype)
    # fp8 residuals only — the activation-memory saving.  (cfg is static,
    # so the backward knows the mode without a runtime tag; the empty
    # array is a dtype witness for the weight cotangent.)
    return y, (xq, wq, jnp.zeros((0,), w.dtype))


def _qmm_bwd(cfg: QuantConfig, res, g):
    if cfg.mode == "bf16":
        from .runtime_flags import mm

        x_bf16, w_bf16, x_wit, w_wit = res
        *lead, k = x_bf16.shape
        g2d = g.reshape(-1, g.shape[-1])
        dx = mm(g2d, w_bf16.T, out_dtype=jnp.float32)
        dw = mm(x_bf16.reshape(-1, k).T, g2d, out_dtype=jnp.float32)
        return (dx.reshape(*lead, k).astype(x_wit.dtype),
                dw.astype(w_wit.dtype), jnp.zeros((), jnp.float32))

    from repro.kernels import dispatch

    xq, wq, w_witness = res
    lead = g.shape[:-1]
    k = wq.q.shape[0]
    x_dtype = g.dtype
    w_dtype = w_witness.dtype
    n = wq.q.shape[-1]
    g2d = g.reshape(-1, n).astype(jnp.float32)
    bfmt = cfg.bwd_format

    # ---- dx = g @ Wᵀ : inner dim N; g grouped along N (E5M2), Wᵀ
    # per-tensor.  MOSS path: fused quantize+GEMM kernel, same operator
    # as the forward.
    if cfg.mode == "moss":
        with jax.named_scope("moss.scale"):
            wqT = PerTensorQ(q=_pad_axis(wq.q.T, 0, cfg.micro_group),
                             s=wq.s)
        dx2d, _ = dispatch.fused_quant_matmul(
            _pad_axis(g2d, -1, cfg.micro_group), wqT, fmt=bfmt,
            micro_group=cfg.micro_group, out_dtype=jnp.float32)
    elif cfg.mode == "per_group":
        gq = quant_per_group(_pad_axis(g2d, -1, cfg.group_size),
                             cfg.group_size, bfmt)
        wqT = PerTensorQ(q=_pad_axis(wq.q.T, 0, cfg.group_size), s=wq.s)
        dx2d = dispatch.group_matmul(gq, wqT, out_dtype=jnp.float32)
    else:
        gq = quant_per_tensor(g2d, bfmt)
        dx2d = dispatch.pt_matmul(gq, PerTensorQ(q=wq.q.T, s=wq.s),
                                  out_dtype=jnp.float32)
    dx = dx2d[:, :k].reshape(*lead, k).astype(x_dtype)

    # ---- dW = xᵀ @ g : inner dim M (tokens); re-quantize the saved fp8
    # activation grouped along M (documented extra quantization — same
    # trade as COAT's transposed copy).  MOSS path: the dW kernel fuses
    # dequant → transpose → requant_M → GEMM, pinning the requant's
    # level-1 scale to s_x so no second amax reduction appears
    # (kernels/mx_bwd.py).
    if cfg.mode == "moss":
        with jax.named_scope("moss.scale"):
            g_pt = quant_per_tensor(g2d, bfmt)
        dw = dispatch.mx_matmul_dw(xq, g_pt, fmt=cfg.fwd_format,
                                   out_dtype=jnp.float32, out_rows=k)
    elif cfg.mode == "per_group":
        x2d = xq.dequant(jnp.bfloat16)[:, :k]     # (M, K) from fp8 residual
        xTq = quant_per_group(_pad_axis(x2d.T, -1, cfg.group_size),
                              cfg.group_size, cfg.fwd_format)
        g_pt = quant_per_tensor(_pad_axis(g2d, 0, cfg.group_size), bfmt)
        dw = dispatch.group_matmul(xTq, g_pt, out_dtype=jnp.float32)
    else:
        x2d = xq.dequant(jnp.bfloat16)
        xTq = quant_per_tensor(x2d.T, cfg.fwd_format)
        g_pt = quant_per_tensor(g2d, bfmt)
        dw = dispatch.pt_matmul(xTq, g_pt, out_dtype=jnp.float32)
    dw = dw.astype(w_dtype)

    return dx, dw, jnp.zeros((), jnp.float32)


qmm.defvjp(_qmm_fwd, _qmm_bwd)


# ---------------------------------------------------------------------------
# Grouped-expert custom_vjp:  (cfg, capacity static)
#   (x, w_stack, w_scale, group_sizes) -> y
#   x: (E·C, K) flat sorted token buffer (expert e owns rows
#      [e·C, e·C + group_sizes[e]); the rest of each slot is zero)
#   w_stack: (E, K, N)   w_scale: (E,) f32   group_sizes: (E,) int32
#
# The MoE hot path: all expert GEMMs in ONE grouped kernel launch with
# ONE global amax reduction over the token buffer (vs 3·E launches + E
# reductions on the vmapped per-expert path).  Residuals are the fp8
# payload of the whole buffer — same 1.8× activation saving as qmm.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def qmm_grouped(cfg: QuantConfig, capacity: int, x: jax.Array,
                w_stack: jax.Array, w_scale: jax.Array,
                group_sizes: jax.Array) -> jax.Array:
    y, _ = _qmm_grouped_fwd(cfg, capacity, x, w_stack, w_scale,
                            group_sizes)
    return y


def _quantize_w_stack(cfg: QuantConfig, w: jax.Array, w_scale: jax.Array):
    """Per-expert per-tensor weight quantization of the (E, K, N) stack:
    ``_quantize_w`` vmapped over the expert dim, so with automatic
    scaling the per-expert scales are the predicted ones (no
    max-reduction over the stack in the HLO)."""
    return jax.vmap(lambda wi, si: _quantize_w(cfg, wi, si))(w, w_scale)


def _qmm_grouped_fwd(cfg: QuantConfig, capacity: int, x, w_stack,
                     w_scale, group_sizes):
    orig_dtype = x.dtype
    e, k, n = w_stack.shape
    if cfg.mode == "bf16":
        from .runtime_flags import einsum

        y = einsum("eck,ekn->ecn", x.reshape(e, capacity, k), w_stack,
                   out_dtype=jnp.float32)
        return (y.reshape(e * capacity, n).astype(orig_dtype),
                (x.astype(jnp.bfloat16), w_stack.astype(jnp.bfloat16),
                 group_sizes, jnp.zeros((0,), x.dtype),
                 jnp.zeros((0,), w_stack.dtype)))
    assert cfg.mode == "moss", \
        f"qmm_grouped supports moss/bf16 modes, got {cfg.mode!r}"
    from repro.kernels import dispatch

    wq = _quantize_w_stack(cfg, w_stack, w_scale)
    y, xq = dispatch.moe_grouped_matmul(
        _pad_axis(x, -1, cfg.micro_group), group_sizes,
        _pad_axis(wq.q, 1, cfg.micro_group), wq.s,
        capacity=capacity, fmt=cfg.fwd_format,
        micro_group=cfg.micro_group, out_dtype=jnp.float32)
    return (y.astype(orig_dtype),
            (xq, wq, group_sizes, jnp.zeros((0,), w_stack.dtype)))


def _qmm_grouped_bwd(cfg: QuantConfig, capacity: int, res, g):
    import numpy as np

    if cfg.mode == "bf16":
        from .runtime_flags import einsum

        x_bf16, w_bf16, sizes, x_wit, w_wit = res
        e, k, n = w_bf16.shape
        g3 = g.reshape(e, capacity, n)
        dx = einsum("ecn,ekn->eck", g3, w_bf16, out_dtype=jnp.float32)
        dw = einsum("eck,ecn->ekn", x_bf16.reshape(e, capacity, k), g3,
                    out_dtype=jnp.float32)
        return (dx.reshape(e * capacity, k).astype(x_wit.dtype),
                dw.astype(w_wit.dtype), jnp.zeros((e,), jnp.float32),
                np.zeros(sizes.shape, jax.dtypes.float0))

    from repro.kernels import dispatch

    xq, wq, sizes, w_witness = res
    e, k, n = wq.q.shape
    g2d = g.astype(jnp.float32)
    bfmt = cfg.bwd_format

    # ---- dx: the same grouped fused-quant GEMM with transposed expert
    # weights (g grouped along N in E5M2) — one launch, one reduction.
    wqT = jnp.swapaxes(wq.q, 1, 2)                     # (E, N, K)
    dx, _ = dispatch.moe_grouped_matmul(
        _pad_axis(g2d, -1, cfg.micro_group), sizes,
        _pad_axis(wqT, 1, cfg.micro_group), wq.s,
        capacity=capacity, fmt=bfmt, micro_group=cfg.micro_group,
        out_dtype=jnp.float32)
    dx = dx.astype(g.dtype)

    # ---- dW: grouped requant-along-tokens GEMM over each expert's row
    # range; the gradient buffer gets ONE per-tensor scale (vs E).
    g_pt = quant_per_tensor(g2d, bfmt)
    dw = dispatch.moe_grouped_matmul_dw(
        xq, g_pt, sizes, capacity=capacity, fmt=cfg.fwd_format,
        out_dtype=jnp.float32, out_rows=k)
    return (dx, dw.astype(w_witness.dtype), jnp.zeros((e,), jnp.float32),
            np.zeros(sizes.shape, jax.dtypes.float0))


qmm_grouped.defvjp(_qmm_grouped_fwd, _qmm_grouped_bwd)


# ---------------------------------------------------------------------------
# Public layer API
# ---------------------------------------------------------------------------


def qlinear(x: jax.Array, wt: QT, cfg: QuantConfig) -> jax.Array:
    """Quantized ``x @ w``.  ``wt`` bundles the weight and its predicted
    scale; falls back to in-step (jit) scaling when the scale is None.
    A calibrated ``wt.a`` (ActScale) takes the reduction-free delayed-
    activation forward instead (docs/serving.md)."""
    if cfg.mode == "bf16":
        return qmm(cfg, x, wt.w, jnp.zeros((), jnp.float32))
    a = wt.a
    if isinstance(a, str):
        # calibration pass: report this site's activation amax, then
        # run the normal just-in-time forward (what we're calibrating)
        if REC.recording:
            REC.record(a, x, cfg)
        a = None
    if isinstance(a, ActScale):
        return _qmm_delayed(cfg, x, wt, a)
    if a is not None:
        # quant-health tap (repro.obs.quant_health, REPRO_QUANT_HEALTH=1
        # only — a TaggedScale never exists otherwise): record this
        # site's saturation/underflow/drift stats, then run the same
        # delayed forward the bare ActScale takes
        from repro.obs.quant_health import QH, TaggedScale

        if isinstance(a, TaggedScale):
            QH.record(a.tag, x, a.scale, cfg)
            return _qmm_delayed(cfg, x, wt, a.scale)
    s = wt.s
    if s is None:
        # no predicted scale available → behave like jit scaling
        cfg = QuantConfig(**{**cfg.__dict__, "weight_scaling": "jit"}) \
            if cfg.weight_scaling == "auto" else cfg
        s = jnp.ones((), jnp.float32)
    return qmm(cfg, x, wt.w, s)


def _qmm_delayed(cfg: QuantConfig, x: jax.Array, wt: QT,
                 a: ActScale) -> jax.Array:
    """Serving-only (forward, no VJP) quantized GEMM that consumes the
    site's calibrated activation scales instead of measuring them: the
    quantize is a rescale + saturating cast, with **zero** reductions in
    the graph (``core.introspect.count_quant_reductions``).  Weights
    ride the same pre-quantized fast path as the just-in-time forward
    (``_quantize_w``); the GEMM itself goes through the identical
    kernel-dispatch entry points, so a calibrated scale equal to the
    just-in-time one reproduces its output bitwise."""
    from repro.kernels import dispatch

    orig_dtype = x.dtype
    *lead, k = x.shape
    x2d = x.reshape(-1, k)
    if wt.s is None and not _is_fp8(wt.w):
        # hatch combo (REPRO_SERVE_PREQUANT=0, non-auto recipe): weight
        # still quantizes in-graph, only the activation side is delayed
        wcfg = QuantConfig(**{**cfg.__dict__, "weight_scaling": "jit"}) \
            if cfg.weight_scaling == "auto" else cfg
        wq = _quantize_w(wcfg, wt.w, jnp.ones((), jnp.float32))
    else:
        wq = _quantize_w(cfg, wt.w, wt.s if wt.s is not None
                         else jnp.ones((), jnp.float32))

    if cfg.mode == "moss":
        x2d = _pad_axis(x2d, -1, cfg.micro_group)
        xq = quant_mx_delayed(x2d, a.s, a.sub, cfg.micro_group,
                              cfg.fwd_format)
        wq_p = PerTensorQ(q=_pad_axis(wq.q, 0, cfg.micro_group), s=wq.s)
        y2d = dispatch.mx_matmul(xq, wq_p, out_dtype=jnp.float32)
    elif cfg.mode == "per_group":
        x2d = _pad_axis(x2d, -1, cfg.group_size)
        g = x2d.shape[-1] // cfg.group_size
        s = jnp.broadcast_to(a.s.astype(jnp.float32),
                             (x2d.shape[0], g))
        xq = quant_per_group(x2d, cfg.group_size, cfg.fwd_format, scale=s)
        wq_p = PerTensorQ(q=_pad_axis(wq.q, 0, cfg.group_size), s=wq.s)
        y2d = dispatch.group_matmul(xq, wq_p, out_dtype=jnp.float32)
    else:  # per_tensor
        xq = quant_per_tensor(x2d, cfg.fwd_format, scale=a.s)
        y2d = dispatch.pt_matmul(xq, wq, out_dtype=jnp.float32)
    return y2d.reshape(*lead, wt.w.shape[-1]).astype(orig_dtype)


def qlinear_grouped(x_flat: jax.Array, wt: QT, group_sizes: jax.Array,
                    capacity: int, cfg: QuantConfig) -> jax.Array:
    """Grouped-expert qlinear: the flat sorted token buffer
    ``x_flat (E·C, K)`` against the stacked expert weights
    ``wt.w (E, K, N)`` with per-expert predicted scales ``wt.s (E,)``.
    Falls back to in-step (jit) per-expert scaling when scales are
    missing, mirroring ``qlinear``."""
    e = wt.w.shape[0]
    if cfg.mode == "bf16":
        return qmm_grouped(cfg, capacity, x_flat, wt.w,
                           jnp.zeros((e,), jnp.float32), group_sizes)
    s = wt.s
    if s is None:
        cfg = QuantConfig(**{**cfg.__dict__, "weight_scaling": "jit"}) \
            if cfg.weight_scaling == "auto" else cfg
        s = jnp.ones((e,), jnp.float32)
    return qmm_grouped(cfg, capacity, x_flat, wt.w, s, group_sizes)


def dense_general(x: jax.Array, wt: QT, cfg: QuantConfig,
                  out_features_shape: tuple[int, ...] | None = None):
    """qlinear for weights whose logical out-dim is multi-axis (e.g.
    (K, H, Dh)): flattens trailing axes for the GEMM, reshapes back."""
    w = wt.w
    if w.ndim > 2:
        k = w.shape[0]
        wf = w.reshape(k, -1)
        y = qlinear(x, QT(wf, wt.s, wt.a), cfg)
        return y.reshape(*x.shape[:-1], *w.shape[1:])
    y = qlinear(x, wt, cfg)
    if out_features_shape:
        y = y.reshape(*x.shape[:-1], *out_features_shape)
    return y
