"""Unified kernel dispatch — the single entry point for every quantized
GEMM and fused quantizer in the training path.

``repro.core.linear``'s custom-VJP (forward, dx and dW GEMMs) and the
public ``ops`` wrappers all route through this module; nothing above
this layer touches a Pallas kernel or the jnp reference directly.  Per
call the backend is chosen by ``repro.core.runtime_flags.kernel_backend``:

  pallas      Pallas-native TPU kernels (mx_fused / mx_gemm / mx_bwd /
              group_gemm / mx_quant)
  interpret   the same kernels under the Pallas interpreter — CPU
              parity testing of the *kernel* path (REPRO_KERNELS=interpret)
  ref         the pure-jnp semantic reference in repro.core.quant —
              the CPU execution default (XLA fuses it)

The kernel paths impose TPU-friendly alignment (M/N blocks of 128, K
micro-group multiples, and multiples of 256 past 512 — ``_k_pad``);
this module zero-pads operands up to block
multiples and slices results back, so callers see one shape contract
across backends.  Zero padding is exact under every quantizer here
(amax of an all-zero group clamps to TINY → q = 0 → contributes 0).

Kernels hardcode the paper's micro-group of 32 and COAT group of 128.
Non-default geometries exist only for ablations and must ask for
``backend="ref"``: a kernel backend that cannot take a call raises
instead of quietly running the reference.

Weight operands always arrive here as fp8 payload + f32 scale
(``PerTensorQ``) — whether quantized in-graph by ``core.linear``
(training) or once at server build time (``PrequantParams``,
docs/serving.md) is invisible at this layer.  The full shape/padding
contract is written down in docs/kernel-contract.md.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quant as Q
from repro.core.quant import MxQ, PerGroupQ, PerTensorQ
from repro.core.runtime_flags import KERNEL_BACKENDS, kernel_backend
from repro.obs.metrics import get_registry
from . import ref
from .decode_attn import decode_attn_paged_pallas, decode_attn_pallas
from .group_gemm import GROUP, group_gemm_pallas
from .moe_gmm import moe_dw_gemm_pallas, moe_gmm_pallas
from .mx_bwd import mx_dw_gemm_pallas
from .mx_fused import fused_quant_gemm_pallas
from .mx_gemm import mx_gemm_pallas
from .mx_quant import mx_quant_pallas

MICRO = 32


def _resolve(backend: str | None) -> str:
    if backend is None:
        return kernel_backend()
    if backend not in KERNEL_BACKENDS:
        raise ValueError(
            f"backend={backend!r}: expected one of {KERNEL_BACKENDS}")
    return backend


def _ceil_to(v: int, mult: int) -> int:
    return v + (-v) % mult


def _pad_to(x: jax.Array, axis: int, target: int) -> jax.Array:
    if x.shape[axis] == target:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, widths)


def _kernel_only(backend: str, ok: bool, what: str) -> None:
    """Kernel backends take only the kernel geometry — no silent
    reference fallback under ``pallas``/``interpret``."""
    if not ok:
        raise ValueError(
            f"backend={backend!r} has no kernel for {what}; ask for "
            f"backend='ref' explicitly")


def _k_pad(k: int) -> int:
    """K padded for the MX kernels: a micro-group multiple, and past
    one 512-wide block a multiple of 256, so every K block holds
    bk/32 ≥ 8 exponent sublanes (kernels/mx_tile.py)."""
    return _ceil_to(k, MICRO) if k <= 512 else _ceil_to(k, 256)


def _k_block(kp: int) -> int:
    """K block for a ``_k_pad``-ed K: 512 or 256, else all of K (a
    block equal to the full dim is always legal)."""
    for b in (512, 256):
        if kp % b == 0:
            return b
    assert kp % MICRO == 0, f"K={kp} not a multiple of {MICRO}"
    return kp


def _per_shard(fn, *args, rows: tuple[int, ...],
               summed_rows: int | None = None):
    """``fn(*args)`` once per batch shard when the caller is partitioned
    over a mesh (GSPMD cannot partition a Pallas call, so the kernel
    runs inside ``shard_map``): the operands at positions ``rows`` are
    split along their leading token dim over the mesh's batch axes, the
    rest replicated (weights are all-gathered).  Outputs are row-split
    the same way; or, given ``summed_rows`` (the output's leading dim),
    summed over those axes — the dW contraction over tokens — and left
    scattered along that dim where it divides, as a data-parallel
    gradient is.  A plain call without such a mesh, and inside a
    ``shard_map`` (already per device).

    Only data parallelism: a mesh axis > 1 the token rows are not split
    over (tensor parallelism over ``model``) raises rather than run
    every kernel on the full weight on each of its devices."""
    import math

    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import active_mesh, resolve_spec

    mesh = active_mesh()
    if (mesh is None or jax.sharding.get_abstract_mesh().manual_axes
            or mesh.size == 1):
        return fn(*args)
    row = resolve_spec(("batch",), mesh, (args[rows[0]].shape[0],))
    axes = row[0] if len(row) else None
    split = (axes,) if isinstance(axes, str) else tuple(axes or ())
    dropped = {ax: n for ax, n in mesh.shape.items()
               if ax not in split and n > 1}
    if dropped:
        raise NotImplementedError(
            f"the MOSS kernels run per batch shard only; mesh axes "
            f"{dropped} do not split the {args[rows[0]].shape[0]} token "
            f"rows — use backend='ref' on this mesh")
    in_specs = tuple(row if i in rows else P() for i in range(len(args)))
    if summed_rows is None:
        local, out_spec = fn, row
    elif summed_rows % math.prod(mesh.shape[ax] for ax in split) == 0:
        def local(*a):
            return jax.lax.psum_scatter(fn(*a), split, scatter_dimension=0,
                                        tiled=True)
        out_spec = P(axes)
    else:
        def local(*a):
            return jax.lax.psum(fn(*a), split)
        out_spec = P()
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec, check_vma=False)(*args)


# VMEM for the fused kernel's resident row-block operand (Kp·bm bf16):
# bm 256 where it fits FUSED_SCRATCH_BUDGET, else bm 128 up to
# FUSED_RESIDENT_MAX; past that the kernel quantizes on every N block
FUSED_SCRATCH_BUDGET = 8 * 2 ** 20
FUSED_RESIDENT_MAX = 32 * 2 ** 20


def _fused_blocks(mp: int, np_: int,
                  kp: int) -> tuple[int, int, int, bool]:
    """(bm, bn, bk, resident) for ``fused_quant_gemm_pallas`` on
    128-padded M and N and a ``_k_pad``-ed K: the widest N block of
    512/384/256/128 that divides N; bm 256 where M allows it and the row
    block's operand fits ``FUSED_SCRATCH_BUDGET``, else 128; the operand
    stays resident while it fits ``FUSED_RESIDENT_MAX`` (K up to 131072),
    so the kernel's VMEM is bounded at any K."""
    bn = next(b for b in (512, 384, 256, 128) if np_ % b == 0)
    bm = 256 if mp % 256 == 0 and kp * 256 * 2 <= FUSED_SCRATCH_BUDGET \
        else 128
    return bm, bn, _k_block(kp), kp * bm * 2 <= FUSED_RESIDENT_MAX


def _m_block(mp: int, min_mult: int = 8) -> int:
    for b in (256, 128, 64, 32, 16, 8):
        if b >= min_mult and mp % b == 0:
            return b
    raise AssertionError(f"M={mp} not a multiple of {min_mult}")


# ---------------------------------------------------------------------------
# MOSS (two-level microscaling) path
# ---------------------------------------------------------------------------


def mx_quantize(x2d: jax.Array, fmt: str = "e4m3",
                micro_group: int = MICRO,
                backend: str | None = None) -> MxQ:
    """Two-level microscaling quantize of a (M, K) tensor (K % micro)."""
    backend = _resolve(backend)
    assert x2d.shape[-1] % micro_group == 0, \
        f"K={x2d.shape[-1]} not divisible by micro_group={micro_group}"
    if backend == "ref":
        return Q.quant_mx(x2d, micro_group, fmt)
    _kernel_only(backend, micro_group == MICRO,
                 f"micro_group={micro_group}")
    m, k = x2d.shape
    s = ref.global_scale_ref(x2d, fmt)
    mp, kp = _ceil_to(m, 8), _k_pad(k)
    q, e = mx_quant_pallas(_pad_to(_pad_to(x2d, 0, mp), 1, kp), s,
                           fmt=fmt, bm=_m_block(mp), bk=_k_block(kp),
                           interpret=backend == "interpret")
    return MxQ(q=q[:m, :k], sexp=e[:m, :k // MICRO], s=s)


def mx_matmul(xq: MxQ, wq: PerTensorQ, out_dtype=jnp.bfloat16,
              backend: str | None = None) -> jax.Array:
    """MOSS GEMM (paper Fig. 3b): (Qx·2^sexp) @ Qw · s_x·s_w — the
    level-2 rescale rides the operand, one f32 epilogue multiply."""
    backend = _resolve(backend)
    micro = xq.q.shape[-1] // xq.sexp.shape[-1]
    if backend == "ref":
        return Q.mx_gemm(xq, wq, out_dtype=out_dtype)
    _kernel_only(backend, micro == MICRO and xq.q.ndim == 2,
                 f"micro_group={micro}, {xq.q.ndim}-D operand")
    m, k = xq.q.shape
    n = wq.q.shape[-1]
    mp, np_, kp = _ceil_to(m, 128), _ceil_to(n, 128), _k_pad(k)
    acc = mx_gemm_pallas(
        _pad_to(_pad_to(xq.q, 0, mp), 1, kp),
        _pad_to(_pad_to(xq.sexp, 0, mp), 1, kp // MICRO),
        _pad_to(_pad_to(wq.q, 0, kp), 1, np_),
        bm=128, bn=128, bk=_k_block(kp),
        interpret=backend == "interpret")
    return (acc[:m, :n] * (xq.s * wq.s)).astype(out_dtype)


def fused_quant_matmul(x2d: jax.Array, wq: PerTensorQ,
                       fmt: str = "e4m3", micro_group: int = MICRO,
                       out_dtype=jnp.bfloat16,
                       backend: str | None = None
                       ) -> tuple[jax.Array, MxQ]:
    """Fused quantize + MOSS GEMM: x (M, K) bf16/f32 in, finished GEMM
    plus the FP8 residual (for the custom-VJP) out — one pass over x,
    matching the paper's Fig. 3b steady-state HLO.  Serves the forward
    (x @ W) and the dx backward (g @ Wᵀ, E5M2)."""
    backend = _resolve(backend)
    # uniform shape contract across backends: the residual's micro-group
    # boundaries must tile K exactly (callers pad — see linear._pad_axis)
    assert x2d.shape[-1] % micro_group == 0, \
        f"K={x2d.shape[-1]} not divisible by micro_group={micro_group}"
    if backend == "ref":
        xq = Q.quant_mx(x2d, micro_group, fmt)
        return Q.mx_gemm(xq, wq, out_dtype=out_dtype), xq
    _kernel_only(backend, micro_group == MICRO,
                 f"micro_group={micro_group}")
    s = ref.global_scale_ref(x2d, fmt)      # level 1: over all shards

    def local(x2d, qw, s, s_w):
        m, k = x2d.shape
        n = qw.shape[-1]
        mp, np_, kp = _ceil_to(m, 128), _ceil_to(n, 128), _k_pad(k)
        bm, bn, bk, resident = _fused_blocks(mp, np_, kp)
        # static counts of the traced call: the kernel quantizes each
        # LHS tile once, on the first of its N blocks, when resident
        tiles = mp // bm * (np_ // bn) * (kp // bk)
        reg = get_registry()
        reg.counter("mx_fused_lhs_tiles_quantized_total",
                    "LHS tiles fused_quant_matmul quantizes, per traced "
                    "call").inc(tiles // (np_ // bn) if resident else tiles)
        reg.counter("mx_fused_gemm_tiles_total",
                    "GEMM tiles fused_quant_matmul runs, per traced "
                    "call").inc(tiles)
        acc, q, sexp = fused_quant_gemm_pallas(
            _pad_to(_pad_to(x2d, 0, mp), 1, kp), s,
            _pad_to(_pad_to(qw, 0, kp), 1, np_),
            fmt=fmt, bm=bm, bn=bn, bk=bk, resident=resident,
            interpret=backend == "interpret")
        return ((acc[:m, :n] * (s * s_w)).astype(out_dtype), q[:m, :k],
                sexp[:m, :k // MICRO])

    y, q, sexp = _per_shard(local, x2d, wq.q, s, wq.s, rows=(0,))
    return y, MxQ(q=q, sexp=sexp, s=s)


def mx_matmul_dw(xq: MxQ, gq: PerTensorQ, fmt: str = "e4m3",
                 out_dtype=jnp.float32, out_rows: int | None = None,
                 backend: str | None = None) -> jax.Array:
    """The dW backward GEMM: requant_M(x̂)ᵀ @ Qg · s_x·s_g, where x̂ is
    the FP8 forward residual and the re-quantization (micro-groups along
    the token dim, level-1 scale pinned to s_x so it cancels — see
    kernels/mx_bwd.py) is fused into the kernel.

    ``out_rows`` is the caller's true (unpadded) K: the residual's K dim
    carries the micro-group padding, so both branches slice the result
    to ``[:out_rows, :n]`` here — one shape contract, no caller-side
    defensive slicing."""
    backend = _resolve(backend)
    micro = xq.q.shape[-1] // xq.sexp.shape[-1]
    m, k = xq.q.shape
    n = gq.q.shape[-1]
    rows_out = k if out_rows is None else out_rows
    if backend == "ref":
        mp = _ceil_to(m, micro)
        x_unit = MxQ(_pad_to(xq.q, 0, mp), _pad_to(xq.sexp, 0, mp),
                     jnp.float32(1.0)).dequant(jnp.float32)  # Qx·2^sexp
        xt = Q.quant_mx(x_unit.T, micro, fmt,
                        global_scale=jnp.float32(1.0))
        acc = Q.mx_gemm(xt, PerTensorQ(q=_pad_to(gq.q, 0, mp),
                                       s=jnp.float32(1.0)),
                        out_dtype=jnp.float32)[:rows_out, :n]
    else:
        _kernel_only(backend, micro == MICRO, f"micro_group={micro}")

        def local(qx, sexp, qg):
            mp = _ceil_to(qx.shape[0], 128)
            np_, kp = _ceil_to(n, 128), _k_pad(k)
            acc = mx_dw_gemm_pallas(
                _pad_to(_pad_to(qx, 0, mp), 1, kp),
                _pad_to(_pad_to(sexp, 0, mp), 1, kp // MICRO),
                _pad_to(_pad_to(qg, 0, mp), 1, np_),
                fmt=fmt, bm=128, bn=128, bko=_k_block(kp),
                interpret=backend == "interpret")
            return acc[:rows_out, :n]

        acc = _per_shard(local, xq.q, xq.sexp, gq.q, rows=(0, 1, 2),
                         summed_rows=rows_out)
    return (acc * (xq.s * gq.s)).astype(out_dtype)


# ---------------------------------------------------------------------------
# MOSS grouped-expert (MoE) path — one ragged kernel for every expert
# ---------------------------------------------------------------------------


def _count_grouped_tiles(tiles: int) -> None:
    """Static count of a traced grouped-kernel call's grid steps, empty
    row blocks (whose dot the kernel skips) included."""
    get_registry().counter(
        "moe_grouped_gemm_tiles_total",
        "grid tiles of the grouped MoE kernels, per traced call").inc(tiles)


def moe_grouped_matmul(x2d: jax.Array, group_sizes: jax.Array,
                       qw_stack: jax.Array, w_scales: jax.Array, *,
                       capacity: int, fmt: str = "e4m3",
                       micro_group: int = MICRO, out_dtype=jnp.bfloat16,
                       backend: str | None = None
                       ) -> tuple[jax.Array, MxQ]:
    """Fused two-level quantize + grouped-expert GEMM.

    ``x2d`` is the flat sorted token buffer ``(E·C, K)`` — expert ``e``
    owns rows ``[e·C, e·C + group_sizes[e])``, the rest of each capacity
    slot must be zero; with ``E·S`` group sizes, each of an expert's S
    segments of C/S rows holds its own count (``moe_gmm_pallas``).  One
    global amax reduction covers the whole buffer (vs E per-expert
    reductions on the vmapped path); per-expert
    weight scales ``w_scales (E,)`` are applied row-wise in the
    epilogue.  Returns the finished GEMM ``(E·C, N)`` plus the fp8
    residual of the whole buffer (for the grouped custom-VJP)."""
    backend = _resolve(backend)
    t, k = x2d.shape
    e, kw, n = qw_stack.shape
    assert kw == k and t == e * capacity, (x2d.shape, qw_stack.shape)
    assert k % micro_group == 0, \
        f"K={k} not divisible by micro_group={micro_group}"
    s = ref.global_scale_ref(x2d, fmt)
    if backend == "ref":
        xq = Q.quant_mx(x2d, micro_group, fmt, global_scale=s)
        acc = ref.moe_gmm_ref(xq.q, xq.sexp, qw_stack, capacity)
    else:
        _kernel_only(backend, micro_group == MICRO,
                     f"micro_group={micro_group}")
        np_, kp = _ceil_to(n, 128), _k_pad(k)
        bm = _m_block(capacity * e // group_sizes.shape[0])
        bk = _k_block(kp)
        _count_grouped_tiles(t // bm * (np_ // 128) * (kp // bk))
        acc, q, sexp = moe_gmm_pallas(
            _pad_to(x2d, 1, kp), s,
            _pad_to(_pad_to(qw_stack, 1, kp), 2, np_),
            group_sizes.astype(jnp.int32), capacity=capacity, fmt=fmt,
            bm=bm, bn=128, bk=bk, interpret=backend == "interpret")
        acc = acc[:, :n]
        xq = MxQ(q=q[:, :k], sexp=sexp[:, :k // MICRO], s=s)
    row_scale = s * jnp.repeat(w_scales.astype(jnp.float32), capacity)
    y = (acc * row_scale[:, None]).astype(out_dtype)
    return y, xq


def moe_grouped_matmul_dw(xq: MxQ, gq: PerTensorQ,
                          group_sizes: jax.Array, *, capacity: int,
                          fmt: str = "e4m3", out_dtype=jnp.float32,
                          out_rows: int | None = None,
                          backend: str | None = None) -> jax.Array:
    """The grouped dW backward: per expert, requant_M(x̂_e)ᵀ @ Qg_e over
    that expert's row range — all experts in one launch, gradient
    quantized with ONE per-tensor scale.  Returns ``(E, K, N)`` (K
    sliced to ``out_rows`` when the residual carries micro padding).
    Per-expert rows are padded here to a micro-group multiple so the
    along-token requantization never straddles an expert boundary; an
    expert's segments (``E·S`` group sizes) must each be one already."""
    backend = _resolve(backend)
    t, k = xq.q.shape
    assert t % capacity == 0
    e = t // capacity
    n = gq.q.shape[-1]
    micro = xq.q.shape[-1] // xq.sexp.shape[-1]
    use_ref = backend == "ref"
    if not use_ref:
        _kernel_only(backend, micro == MICRO, f"micro_group={micro}")
    # per-expert rows padded so the along-token requant groups (micro
    # tokens each) never straddle an expert boundary
    segs = group_sizes.shape[0] // e
    cp = _ceil_to(capacity, micro if use_ref else MICRO)
    assert segs == 1 or cp == capacity and (capacity // segs) % MICRO == 0, \
        f"C/S={capacity}/{segs} must be a multiple of {MICRO}"

    def _pad_rows(a):
        if cp == capacity:
            return a
        return _pad_to(a.reshape(e, capacity, *a.shape[1:]), 1,
                       cp).reshape(e * cp, *a.shape[1:])

    qx, sexp, qg = _pad_rows(xq.q), _pad_rows(xq.sexp), _pad_rows(gq.q)
    if use_ref:
        acc = ref.moe_dw_ref(qx, sexp, qg, cp, fmt, micro)
    else:
        np_, kp = _ceil_to(n, 128), _k_pad(k)
        bm, bko = _m_block(cp // segs, min_mult=MICRO), _k_block(kp)
        _count_grouped_tiles(e * (cp // bm) * (np_ // 128) * (kp // bko))
        acc = moe_dw_gemm_pallas(
            _pad_to(qx, 1, kp), _pad_to(sexp, 1, kp // MICRO),
            _pad_to(qg, 1, np_),
            group_sizes.astype(jnp.int32), capacity=cp, fmt=fmt,
            bm=bm, bn=128, bko=bko, interpret=backend == "interpret")
    acc = acc[:, :k if out_rows is None else out_rows, :n]
    return (acc * (xq.s * gq.s)).astype(out_dtype)


# ---------------------------------------------------------------------------
# Serving: fused decode attention over the (fp8 | bf16) KV cache
# ---------------------------------------------------------------------------


def decode_attention(q, k, v, k_scale, v_scale, n_valid, *,
                     sm_scale: float | None = None,
                     backend: str | None = None) -> jax.Array:
    """Single-step decode attention against the kv-head-major cache.

    ``q`` is (B, KV, G, Dh) — queries grouped by kv head (GQA); ``k`` /
    ``v`` are the cache payloads (B, KV, C, Dh) in e4m3 (with
    per-(token, kv-head) f32 ``k_scale``/``v_scale`` (B, KV, C)) or
    bf16 (scales None); ``n_valid`` is the cache ``idx`` — a scalar
    shared by every row (legacy ring) or a (B,) per-slot length
    vector (continuous-batching engine, docs/continuous-batching.md:
    slots at different depths coexist in one decode batch); every
    entry must be ≥ 1.  A scalar is broadcast to (B,) here, so both
    backends see one contract.
    Returns (B, KV, G, Dh) f32 — the caller reshapes heads and casts.

    The kernel path fuses scale application, ring-validity masking,
    softmax and the value combine into one launch reading the payload
    at 1 byte/element; the ref path is the scale-folding einsum oracle
    (``kernels/ref.py``), bitwise-identical on a bf16 cache with one C
    block (docs/decode-attention.md).  G is padded to the 8-row
    sublane tile here and sliced back; C and Dh pass through unpadded
    (the kernel masks the trailing partial block) so the cache is
    never copied.

    Batched-query (speculative verify) form: a 5-D ``q``
    (B, KV, S, G, Dh) carries S draft queries per row; ``n_valid`` is
    the POST-write depth (every entry ≥ S) and draft j's validity is
    ``slot < min(n_valid[b] - (S-1-j), C)`` — the in-step causal mask
    (docs/speculative-decoding.md).  Returns (B, KV, S, G, Dh) f32.
    The kernel path flattens the drafts into S·Gp draft-major rows
    sharing ONE cache read."""
    backend = _resolve(backend)
    s_len = q.shape[2] if q.ndim == 5 else 1
    b, kvh, g, dh = q.shape[0], q.shape[1], q.shape[-2], q.shape[-1]
    if sm_scale is None:
        sm_scale = dh ** -0.5
    nv = jnp.asarray(n_valid, jnp.int32).reshape(-1)
    assert nv.shape[0] in (1, b), \
        f"n_valid shape {nv.shape}: expected () / (1,) / ({b},)"
    nv = jnp.broadcast_to(nv, (b,))
    if backend == "ref":
        return ref.decode_attn_ref(q, k, v, k_scale, v_scale, nv,
                                   sm_scale=sm_scale)
    gp = _ceil_to(max(g, 8), 8)
    if q.ndim == 5:
        qf = _pad_to(q, 3, gp).reshape(b, kvh, s_len * gp, dh)
        out = decode_attn_pallas(
            qf, k, v, k_scale, v_scale, nv, sm_scale=sm_scale,
            interpret=backend == "interpret", q_len=s_len)
        return out.reshape(b, kvh, s_len, gp, dh)[:, :, :, :g]
    out = decode_attn_pallas(
        _pad_to(q, 2, gp), k, v, k_scale, v_scale, nv,
        sm_scale=sm_scale, interpret=backend == "interpret")
    return out[:, :, :g]


def decode_attention_paged(q, k, v, k_scale, v_scale, n_valid,
                           block_table, *,
                           sm_scale: float | None = None,
                           backend: str | None = None) -> jax.Array:
    """Single-step decode attention over the floating page pool.

    Same contract as :func:`decode_attention` except the cache arrives
    as a GLOBAL page pool — ``k`` / ``v`` are (P, KV, T, Dh) physical
    pages (e4m3 with (P, KV, T) f32 scales, or bf16 with scales None)
    shared by every slot, and ``block_table`` (B, NP) int32 maps
    logical page j of batch row b to physical row
    ``block_table[b, j]``.  ``n_valid`` must be per-slot (B,) (the
    engine's length vector); a scalar broadcasts as before.  Logical
    capacity is C = NP·T; validity is ``slot < min(n_valid[b], C)``.
    Returns (B, KV, G, Dh) f32.

    The ref path gathers the pages into the contiguous layout and
    reuses the contiguous oracle (bitwise-equal by construction); the
    kernel path threads ``block_table`` in as a second scalar-prefetch
    operand so its index maps perform the same gather inside the DMA
    schedule — nothing cache-sized is materialized in HBM
    (docs/paged-attention.md).  A 5-D ``q`` (B, KV, S, G, Dh) is the
    batched-query verify form, exactly as in
    :func:`decode_attention`."""
    backend = _resolve(backend)
    s_len = q.shape[2] if q.ndim == 5 else 1
    b, kvh, g, dh = q.shape[0], q.shape[1], q.shape[-2], q.shape[-1]
    if sm_scale is None:
        sm_scale = dh ** -0.5
    nv = jnp.asarray(n_valid, jnp.int32).reshape(-1)
    assert nv.shape[0] in (1, b), \
        f"n_valid shape {nv.shape}: expected () / (1,) / ({b},)"
    nv = jnp.broadcast_to(nv, (b,))
    bt = jnp.asarray(block_table, jnp.int32)
    assert bt.shape[0] == b, (bt.shape, b)
    if backend == "ref":
        return ref.decode_attn_paged_ref(q, k, v, k_scale, v_scale, nv,
                                         bt, sm_scale=sm_scale)
    gp = _ceil_to(max(g, 8), 8)
    if q.ndim == 5:
        qf = _pad_to(q, 3, gp).reshape(b, kvh, s_len * gp, dh)
        out = decode_attn_paged_pallas(
            qf, k, v, k_scale, v_scale, nv, bt, sm_scale=sm_scale,
            interpret=backend == "interpret", q_len=s_len)
        return out.reshape(b, kvh, s_len, gp, dh)[:, :, :, :g]
    out = decode_attn_paged_pallas(
        _pad_to(q, 2, gp), k, v, k_scale, v_scale, nv, bt,
        sm_scale=sm_scale, interpret=backend == "interpret")
    return out[:, :, :g]


# ---------------------------------------------------------------------------
# COAT (per-group) and TE (per-tensor) baselines
# ---------------------------------------------------------------------------


def group_matmul(xq: PerGroupQ, wq: PerTensorQ, out_dtype=jnp.bfloat16,
                 backend: str | None = None) -> jax.Array:
    """COAT-style GEMM (paper Fig. 3a): per-group f32 rescale of every
    partial sum inside the K loop — the overhead MOSS removes."""
    backend = _resolve(backend)
    group = xq.q.shape[-1] // xq.s.shape[-1]
    if backend == "ref":
        return Q.group_gemm(xq, wq, out_dtype=out_dtype)
    _kernel_only(backend, group == GROUP and xq.q.ndim == 2,
                 f"group={group}, {xq.q.ndim}-D operand")
    m, k = xq.q.shape
    n = wq.q.shape[-1]
    mp, np_ = _ceil_to(m, 128), _ceil_to(n, 128)
    acc = group_gemm_pallas(
        _pad_to(xq.q, 0, mp),
        _pad_to(xq.s, 0, mp),
        _pad_to(wq.q, 1, np_),
        bm=128, bn=128, bk=GROUP,
        interpret=backend == "interpret")
    return (acc[:m, :n] * wq.s).astype(out_dtype)


def pt_matmul(xq: PerTensorQ, wq: PerTensorQ, out_dtype=jnp.bfloat16,
              backend: str | None = None) -> jax.Array:
    """TE-style per-tensor GEMM.  Epilogue-only dequant: this is a plain
    FP8 matmul XLA already maps to the MXU, so every backend takes the
    reference path (there is nothing for a hand-written kernel to fuse)."""
    del backend
    return Q.pt_gemm(xq, wq, out_dtype=out_dtype)
