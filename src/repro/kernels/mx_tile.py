"""In-kernel MOSS micro-group arithmetic shared by the MX Pallas kernels
(mx_fused, mx_quant, mx_gemm, mx_bwd, moe_gmm), and the tile layout
their E8M0 exponents travel in.

Mosaic cannot split the lane (last) axis of a vector: the reshape
``(bm, bk) -> (bm, bk/32, 32)`` that groups 32-wide micro-groups along
K is refused by the TPU compiler ("unsupported shape cast").  Splitting
the SUBLANE axis by a multiple of 8 is a free relabelling of the
(8, 128) vreg tiles, so every helper here works on the transposed tile
``(bk, bm)``, where a K micro-group is 32 consecutive sublanes:

  x (bm, bk)  --T-->  (bk, bm)  --reshape-->  (bk/32, 32, bm)

and the per-group amax / exponent comes out as a ``(bk/32, bm)`` tile.

Exponent tile layout
--------------------
``MxQ.sexp`` is (M, K/32) int8 — a K/32-wide lane dim that no legal TPU
block of 16 or 8 exponents can tile.  Inside the kernels the exponents
therefore live in the tile layout ``(M/bm, K/32, bm)`` int32: block
``(1, bk/32, bm)`` has the full ``bm`` as its lane dim (always legal)
and ``bk/32`` sublanes (legal when ``bk % 256 == 0`` or ``bk == K``,
which ``dispatch`` guarantees).  int32, because a block of 8 sublanes
is then a whole (8, 128) tile; int8 tiles are (32, 128), which an
8-row block only partly covers.  The jitted kernel wrappers convert
with :func:`sexp_to_tiles` / :func:`sexp_from_tiles` around the
``pallas_call``; callers only ever see (M, K/32) int8.

All arithmetic is elementwise or a max, so the numerics are bitwise the
reference's (``repro.core.quant.quant_mx``) whatever the layout.  Powers
of two are built from their f32 bit pattern (``e8m0_decode``) rather
than by ``exp2``, which the TPU evaluates on its transcendental unit and
need not return exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import e8m0_decode

MICRO = 32
TILE_DTYPE = jnp.int32      # exponents inside the kernels (see above)
_TINY = 1e-30


def sexp_to_tiles(sexp: jax.Array, bm: int) -> jax.Array:
    """(M, K/32) int8 exponents -> the kernels' (M/bm, K/32, bm)
    int32 layout."""
    m, g = sexp.shape
    return sexp.astype(TILE_DTYPE).reshape(m // bm, bm, g).transpose(0, 2, 1)


def sexp_from_tiles(tiles: jax.Array) -> jax.Array:
    """Inverse of :func:`sexp_to_tiles`."""
    n, g, bm = tiles.shape
    return tiles.transpose(0, 2, 1).reshape(n * bm, g).astype(jnp.int8)


def e8m0_exponent(ratio: jax.Array) -> jax.Array:
    """``ceil(log2(ratio))`` clamped to the E8M0 range, as f32 — the
    same guards as ``repro.core.formats.e8m0_encode``."""
    e = jnp.ceil(jnp.log2(jnp.maximum(ratio, 2.0 ** -149)) - 1e-6)
    return jnp.clip(e, -127, 127)


def quant_tile(x: jax.Array, s: jax.Array, *, fp8_max: float, q_dtype):
    """Two-level quantize of one (bm, bk) f32 tile along K against the
    level-1 scale ``s`` (paper Eqs. 2-3).

    Returns ``e`` (bk/32, bm) f32 E8M0 exponents and ``q``
    (bk/32, 32, bm) fp8 values — both transposed (micro-groups on the
    sublane axis)."""
    bm, bk = x.shape
    xg = x.T.reshape(bk // MICRO, MICRO, bm)
    amax = jnp.max(jnp.abs(xg), axis=1)                   # (bk/32, bm)
    s = jnp.maximum(s, _TINY)
    e = e8m0_exponent(amax / fp8_max / s)
    denom = (e8m0_decode(e) * s)[:, None, :]
    safe = jnp.where(denom > 0, denom, 1.0)
    q = jnp.where(denom > 0, xg / safe, 0.0)
    return e, jnp.clip(q, -fp8_max, fp8_max).astype(q_dtype)


def untranspose(q: jax.Array) -> jax.Array:
    """(bk/32, 32, bm) fp8 tile from :func:`quant_tile` -> (bm, bk) fp8
    (transposed through f32: the cast back is exact)."""
    g, _, bm = q.shape
    return q.astype(jnp.float32).reshape(g * MICRO, bm).T.astype(q.dtype)


def scaled_operand_t(q: jax.Array, e: jax.Array) -> jax.Array:
    """MXU operand ``q · 2^e`` in bf16 (exact: a power-of-two rescale
    of an fp8 value), transposed: (bk/32, 32, bm) fp8 or (bk, bm) f32
    values with (bk/32, bm) exponents -> (bk, bm) bf16.  Feed it to
    :func:`dot_t`."""
    g, bm = e.shape
    ss = e8m0_decode(e).astype(jnp.bfloat16)
    qg = q.reshape(g, MICRO, bm).astype(jnp.bfloat16)
    return (qg * ss[:, None, :]).reshape(g * MICRO, bm)


def dot_t(a_t: jax.Array, b: jax.Array) -> jax.Array:
    """``a_tᵀ @ b`` with f32 accumulation: (k, m) x (k, n) -> (m, n)."""
    return jax.lax.dot_general(a_t, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def dequant_tile(qx: jax.Array, sexp_t: jax.Array) -> jax.Array:
    """Forward residual tile in units of s_x: (bm, bk) fp8 payload with
    its (bk/32, bm) exponents -> ``Qx · 2^sexp`` as (bm, bk) f32."""
    bm, bk = qx.shape
    xt = qx.astype(jnp.float32).T.reshape(bk // MICRO, MICRO, bm)
    ss = e8m0_decode(sexp_t)[:, None, :]
    return (xt * ss).reshape(bk, bm).T


def requant_rows(xd: jax.Array, *, fp8_max: float, q_dtype) -> jax.Array:
    """The dW requantization: (bm, bk) f32 tile in units of s_x,
    re-quantized with 32-row micro-groups along M (the dW inner dim)
    against level-1 scale 1 (s_x cancels — kernels/mx_bwd.py).
    Returns the bf16 MXU operand ``q' · 2^e'`` (bm, bk); contract it
    over M with :func:`dot_t`."""
    bm, bk = xd.shape
    xg = xd.reshape(bm // MICRO, MICRO, bk)               # sublane split
    amax = jnp.max(jnp.abs(xg), axis=1)                   # (bm/32, bk)
    ss = e8m0_decode(e8m0_exponent(amax / fp8_max))
    safe = jnp.where(ss > 0, ss, 1.0)[:, None, :]
    q = jnp.where(ss[:, None, :] > 0, xg / safe, 0.0)
    q = jnp.clip(q, -fp8_max, fp8_max).astype(q_dtype)    # fp8 requant
    return (q.astype(jnp.bfloat16)
            * ss.astype(jnp.bfloat16)[:, None, :]).reshape(bm, bk)
