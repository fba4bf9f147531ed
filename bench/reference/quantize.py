"""Round-to-lower-precision for the controls: the plain reference run
with its GEMM operands rounded to a precision below the one the
configuration states.  A control must read as not correct; it is never
run by the benchmark's own runs.

Each operand is scaled along the contraction axis and rounded to the
format: ``int4`` (symmetric, -7..7), ``e4m3`` or ``e5m2``.  The scale is
the absmax of a whole row along that axis, or, with ``group``, of each
run of ``group`` elements along it: MOSS's own micro-group of 32, at
which a control differs from the program in its element format alone.
Each scale is exact (absmax over the format's largest value, not a
power of two), so the control is no coarser than the format makes it.
``Rounding(fwd, bwd, group)`` rounds a linear layer's forward operands
to ``fwd`` and its backward ones (the gradient and the operand it
meets) to ``bwd``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_FP8 = {"e4m3": (jnp.float8_e4m3fn, 448.0), "e5m2": (jnp.float8_e5m2, 57344.0)}


def round_to(x: jax.Array, fmt: str, axis: int,
             group: int | None = None) -> jax.Array:
    """``x`` (f32) rounded to ``fmt`` with one scale per slice along
    ``axis`` (per run of ``group`` elements of it, where given),
    returned in f32."""
    x = jnp.moveaxis(x.astype(jnp.float32), axis, -1)
    shape = x.shape
    if group is not None:
        if shape[-1] % group:
            raise ValueError(f"axis of {shape[-1]} is no multiple of the "
                             f"group {group}")
        x = x.reshape(*shape[:-1], shape[-1] // group, group)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    if fmt == "int4":
        s = jnp.maximum(amax, 1e-30) / 7.0
        y = jnp.clip(jnp.round(x / s), -7.0, 7.0) * s
    else:
        dtype, fmax = _FP8[fmt]
        s = jnp.maximum(amax, 1e-30) / fmax
        y = (x / s).astype(dtype).astype(jnp.float32) * s
    return jnp.moveaxis(y.reshape(shape), -1, axis)


class Rounding(NamedTuple):
    fwd: str
    bwd: str
    group: int | None = None


def linear(x: jax.Array, w: jax.Array, r: Rounding | None) -> jax.Array:
    """``x (..., K) @ w (K, N)`` in f32 at the highest precision; with
    ``r``, every GEMM of the layer (forward, dx, dW) runs on rounded
    operands."""
    if r is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    return _rounded_linear(x, w, r)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rounded_linear(x, w, r):
    return jnp.matmul(round_to(x, r.fwd, -1, r.group),
                      round_to(w, r.fwd, 0, r.group), precision=HIGHEST)


def _fwd(x, w, r):
    return _rounded_linear(x, w, r), (x, w)


def _bwd(r, res, g):
    x, w = res
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    dx = jnp.matmul(round_to(g2, r.bwd, -1, r.group),
                    round_to(w, r.bwd, 1, r.group).T,
                    precision=HIGHEST).reshape(x.shape)
    dw = jnp.matmul(round_to(x2, r.bwd, 0, r.group).T,
                    round_to(g2, r.bwd, 0, r.group), precision=HIGHEST)
    return dx, dw


_rounded_linear.defvjp(_fwd, _bwd)
