"""Pallas TPU kernel: fused decode attention over the (fp8 | bf16) KV
cache — the serving hot path after weight pre-quantization.

The einsum decode path dequantizes the fp8 cache *structurally*: XLA
upcasts the whole e4m3 K and V payloads to bf16/f32 to feed the MXU
(two full-cache ``convert_element_type`` ops per layer per step), folds
the per-(token, kv-head) scales into the scores / combine weights with
separate broadcast multiplies, and runs the masked softmax as its own
fusion.  This kernel collapses all of it into ONE launch per
(batch, kv-head) cell:

  read e4m3 payload → upcast in VMEM → Q·Kᵀ → ×k_scale → ring-validity
  mask → softmax → ×v_scale → ·V → out

so the cache crosses HBM exactly once, at 1 byte/element, and nothing
cache-sized is ever materialized in HBM (``core/introspect.py`` counts
the removed upcasts/dots on the decode jaxpr).  A bf16 cache takes the
same kernel with the scale operands elided — one entry point for both
cache dtypes.

Operand contract (see docs/decode-attention.md)
-----------------------------------------------
  q         (B, KV, R, Dh)  f32/bf16 — queries grouped by kv head,
                            R = q_len · Gp rows: ``q_len`` queries
                            (draft-major) of Gp heads each (GQA:
                            G = n_heads // n_kv; dispatch pads G up to
                            the 8-row sublane tile).  q_len == 1 is
                            plain decode; q_len == k is the
                            speculative verify step — k draft queries
                            share ONE cache read
  k, v      (B, KV, C, Dh)  e4m3 or bf16 payloads — the cache layout
                            itself (kv-head-major), read in place
  k_scale,  (B, KV, C)      f32 per-(token, kv-head) scales; None for
  v_scale                   the bf16 cache.  The wrapper reshapes them
                            to (B, KV, 1, C) so a scale block
                            (1, 1, 1, bc) has legal TPU minor dims
                            (the full 1, and bc = C or a multiple of
                            128)
  n_valid   (B,)            int32 scalar-prefetch (SMEM): per-batch
                            absolute positions written so far AFTER
                            this step's q_len-token write (the
                            per-slot cache ``idx`` of the continuous-
                            batching engine — docs/continuous-
                            batching.md); each entry must be ≥ q_len
                            (decode attends after a write).  A scalar
                            (shared-ring legacy cache) broadcasts to
                            (B,) at dispatch.  For draft j of batch
                            row b (j = row // Gp), slot s is valid iff
                            s < min(n_valid[b] - (q_len-1-j), C) — the
                            in-step causal mask between drafts; at
                            q_len == 1 this reduces to the ring rule
                            s < min(n_valid[b], C) (a wrapped cache,
                            idx ≥ C, is fully valid; slot order is
                            irrelevant to softmax).  q_len > 1
                            requires an unwrapped cache
                            (n_valid ≤ C): rejection-truncation
                            semantics are undefined on a ring
  returns   (B, KV, R, Dh)  f32 UNCAST attention output

Grid is (B, KV, C/bc) — the third axis is the split-K dimension over
the context.  With one C block (``bc == C``, the common serving case)
the kernel computes the exact masked softmax in the same operation
order as the einsum path — bitwise-identical on a bf16 cache
(tests/test_decode_attn.py).  With several C blocks (C above the
MAX_SINGLE_BLOCK VMEM ceiling, or an explicit ``bc``) it switches to
revisiting-free online (flash) rescaling — each C block is visited
exactly once, m/l/acc carry across grid steps in VMEM scratch — which
matches to f32 round-off.

Alignment is CALLER-owned only for G (pad to ≥ 8 rows); C and Dh are
taken as-is — the trailing partial C block is masked in-kernel (scores
to NEG_INF, garbage V rows zeroed) so the cache is never padded or
copied in HBM.

Floating-page variant (``decode_attn_paged_pallas``)
----------------------------------------------------
The serving engine's floating-page pool (docs/paged-attention.md)
stores K/V as ``(P, KV, T, Dh)`` — P physical pages of T tokens each,
shared by every slot — and a per-slot block table maps logical page j
of batch row b to an arbitrary physical row.  The block table rides in
as a SECOND scalar-prefetch operand ``(B, pages_per_slot) int32``
right after ``n_valid``, and the K/V/scale index maps read it:

  block index (bi, ki, pi)  ->  (block_table[bi, pi], ki, 0, 0)

so the payload gather happens in the DMA schedule — each grid step
streams one physical ``(T, Dh)`` page tile into VMEM and no cache
payload is ever copied or materialized contiguously in HBM.  Grid is
(B, KV, pages_per_slot).  Up to C = MAX_SINGLE_BLOCK, each page's K and
V rows are stored into (C, Dh) VMEM scratch (a sublane-offset store,
which Mosaic accepts for any 8-row multiple T — a T-wide lane-offset
store of per-page scores is refused), and the LAST page step runs the
contiguous single-block body on the gathered rows: the exact masked
softmax in the same operation order, so paged-vs-contiguous decode is
bitwise-identical given identical page contents
(tests/test_paged_attn.py).  That path needs each row's scales as one
(1, C) lane vector, so the wrapper gathers the (P, KV, T) scale pool
through the block table in XLA — (B, KV, C) f32, 4/Dh of the fp8
payload's bytes.  Past the ceiling the gathered scratch no longer
fits, so the kernel switches to the same revisiting-free online-softmax
accumulation as the contiguous multi-block path (one C block == one
page, scales read per page from the pool as (1, 1, 1, T) blocks),
keeping long contexts VMEM-resident page by page with no cache copy —
matching the exact path to f32 round-off.  Both kernels take the same
``q_len`` batched-query extension (see operand contract above).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
_TINY = 1e-30

# C blocking, checked against the v5e compiler at Dh 128 (the
# single-block (C, Dh) K/V tiles, double-buffered, plus the paged
# path's two (C, Dh) f32 gather buffers stay inside the default scoped
# VMEM — tests/test_tpu_compile.py)
MAX_SINGLE_BLOCK = 2048
MULTI_BLOCK = 1024


def _scores(q, k, ks, *, sm_scale: float):
    """(R, Dh) · (c, Dh)ᵀ scores with the per-token K scale folded in —
    the payload itself is never dequantized."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale
    return s if ks is None else s * ks


def _valid(nv_b, slot, *, c_true: int, q_len: int, gp: int):
    """Slot-validity mask of the ``n_valid`` contract (module
    docstring), broadcastable to (R, c): ``slot < min(n_valid[b], C)``
    at ``q_len == 1``, per draft row (in-step causal mask) otherwise.
    ``slot`` is the (1, c) row of absolute slot indices."""
    if q_len == 1:
        return slot < jnp.minimum(nv_b, c_true)
    # row r holds draft j = r // Gp, whose query position is
    # n_valid[b]-q_len+j, so it may attend slots < n_valid[b] -
    # (q_len-1-j) — including its OWN freshly-written K
    draft = jax.lax.broadcasted_iota(jnp.int32, (q_len * gp, 1), 0) // gp
    lim = jnp.minimum(nv_b - (q_len - 1 - draft), c_true)
    return slot < lim


def _exact_combine(s, v, vs, *, op_dtype):
    """Exact masked softmax + value combine in the einsum reference's
    operation order (max → exp → sum → divide → ×v_scale → dot): on a
    bf16 cache the result is bitwise-identical to the ref path."""
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    w = p / jnp.sum(p, axis=-1, keepdims=True)
    if vs is not None:
        w = w * vs
    return jax.lax.dot_general(w.astype(jnp.bfloat16).astype(op_dtype), v,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _online_step(s, v, vs, valid, start, nv_b, scratch, *, c_true: int,
                 first, last, o_ref, op_dtype):
    """One C block (slots ``start`` onward) of the revisiting-free
    online (flash) softmax: m / l / acc carry across grid steps in VMEM
    scratch; ``last`` writes the normalized output."""
    m_ref, l_ref, acc_ref = scratch
    c = s.shape[-1]
    # the trailing partial block may hold garbage V rows (Pallas pads
    # the edge); their weights are exactly 0 but 0·NaN would poison, so
    # zero them explicitly.  Zeroing keys off the widest draft's window
    # (a column a stricter draft row masks contributes exp-underflowed
    # exact 0 × finite V = 0 to that row).  The row mask comes from its
    # own (c, 1) iota: Mosaic cannot reshape a lane vector to a column.
    row_slot = start + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    v = jnp.where(row_slot < jnp.minimum(nv_b, c_true), v, 0.0)

    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m_prev = m_ref[:, :1]                                     # (R, 1)
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                    # (R, c)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    if vs is not None:
        # re-mask after the scale fold: a garbage-padded v_scale is
        # NaN under the interpreter and 0 · NaN would poison the dot
        p = jnp.where(valid, p * vs, 0.0)
    pv = jax.lax.dot_general(p.astype(jnp.bfloat16).astype(op_dtype),
                             v.astype(op_dtype), (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(last)
    def _done():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[:, :1], _TINY)


def _online_scratch(rows: int, dh: int):
    return [
        pltpu.VMEM((rows, 128), jnp.float32),    # running max (col 0)
        pltpu.VMEM((rows, 128), jnp.float32),    # running sum (col 0)
        pltpu.VMEM((rows, dh), jnp.float32),     # output accumulator
    ]


def _split_refs(rest, quantized: bool):
    if quantized:
        return rest[0], rest[1], rest[2], rest[3:]
    return None, None, rest[0], rest[1:]


def _decode_attn_kernel(nv_ref, q_ref, k_ref, v_ref, *rest, n_c: int,
                        bc: int, c_true: int, sm_scale: float,
                        quantized: bool, op_dtype, q_len: int, gp: int):
    ks_ref, vs_ref, o_ref, scratch = _split_refs(rest, quantized)
    ci = pl.program_id(2)
    ks = None if ks_ref is None else ks_ref[0, 0]             # (1, bc)
    vs = None if vs_ref is None else vs_ref[0, 0]

    # operands mirror runtime_flags.mm: bf16 values (fp8 casts are
    # exact in bf16), f32 accumulation — bf16 on the MXU, f32 under the
    # CPU interpreter, so interpret-vs-ref parity is bitwise
    q = q_ref[0, 0].astype(jnp.bfloat16).astype(op_dtype)     # (R, Dh)
    k = k_ref[0, 0].astype(jnp.bfloat16).astype(op_dtype)     # (bc, Dh)
    s = _scores(q, k, ks, sm_scale=sm_scale)                  # (R, bc)
    # ring-validity mask: covers the partial ring (idx < C), the
    # fully-wrapped ring (all C slots valid) and the trailing partial
    # block (slots ≥ C); n_valid is per batch row
    nv_b = nv_ref[pl.program_id(0)]
    slot = ci * bc + jax.lax.broadcasted_iota(jnp.int32, (1, bc), 1)
    valid = _valid(nv_b, slot, c_true=c_true, q_len=q_len, gp=gp)
    s = jnp.where(valid, s, NEG_INF)
    v = v_ref[0, 0].astype(jnp.bfloat16).astype(op_dtype)     # (bc, Dh)

    if n_c == 1:
        o_ref[0, 0] = _exact_combine(s, v, vs, op_dtype=op_dtype)
        return
    _online_step(s, v, vs, valid, ci * bc, nv_b, scratch, c_true=c_true,
                 first=ci == 0, last=ci == n_c - 1, o_ref=o_ref,
                 op_dtype=op_dtype)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "bc", "interpret",
                                    "q_len"))
def decode_attn_pallas(q, k, v, k_scale, v_scale, n_valid, *,
                       sm_scale: float, bc: int | None = None,
                       interpret: bool = False, q_len: int = 1):
    """q: (B, KV, R, Dh) with R = q_len·Gp, Gp % 8 == 0 (dispatch
    pads); k/v: (B, KV, C, Dh) e4m3|bf16 payloads; k_scale/v_scale:
    (B, KV, C) f32 or both None (bf16 cache); n_valid: (B,) int32
    scalar-prefetch — per-slot valid counts AFTER this step's write (a
    (1,) value broadcasts to every row); every entry must be ≥ q_len.
    Returns (B, KV, R, Dh) f32.  ``bc`` picks the C block: defaults
    to one block (exact softmax) up to MAX_SINGLE_BLOCK, else the
    online multi-block (split-K) path.  ``q_len`` > 1 is the
    speculative verify step: draft-major query rows under the in-step
    causal mask (see module docstring)."""
    from repro.core.runtime_flags import mm_operand_dtype

    b, kvh, rows, dh = q.shape
    c = k.shape[2]
    assert k.shape == v.shape == (b, kvh, c, dh), (q.shape, k.shape)
    assert rows % q_len == 0, (rows, q_len)
    gp = rows // q_len
    assert gp % 8 == 0, f"G={gp} not padded to the 8-row sublane tile"
    quantized = k_scale is not None
    if bc is None:
        bc = c if c <= MAX_SINGLE_BLOCK else MULTI_BLOCK
    bc = min(bc, c)
    n_c = pl.cdiv(c, bc)
    grid = (b, kvh, n_c)

    in_specs = [
        pl.BlockSpec((1, 1, rows, dh),
                     lambda bi, ki, ci, nv: (bi, ki, 0, 0)),
        pl.BlockSpec((1, 1, bc, dh), lambda bi, ki, ci, nv: (bi, ki, ci, 0)),
        pl.BlockSpec((1, 1, bc, dh), lambda bi, ki, ci, nv: (bi, ki, ci, 0)),
    ]
    args = [q, k, v]
    if quantized:
        assert k_scale.shape == v_scale.shape == (b, kvh, c)
        in_specs += 2 * [pl.BlockSpec(
            (1, 1, 1, bc), lambda bi, ki, ci, nv: (bi, ki, 0, ci))]
        args += [k_scale.reshape(b, kvh, 1, c),
                 v_scale.reshape(b, kvh, 1, c)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows, dh),
                               lambda bi, ki, ci, nv: (bi, ki, 0, 0)),
        scratch_shapes=[] if n_c == 1 else _online_scratch(rows, dh),
    )
    nv = jnp.broadcast_to(n_valid.astype(jnp.int32).reshape(-1), (b,))
    return pl.pallas_call(
        functools.partial(_decode_attn_kernel, n_c=n_c, bc=bc, c_true=c,
                          sm_scale=sm_scale, quantized=quantized,
                          op_dtype=mm_operand_dtype(), q_len=q_len,
                          gp=gp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, dh), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(nv, *args)


def _paged_decode_kernel(nv_ref, bt_ref, q_ref, k_ref, v_ref, *rest,
                         n_p: int, t: int, sm_scale: float,
                         quantized: bool, op_dtype, q_len: int,
                         gp: int, online: bool):
    ks_ref, vs_ref, o_ref, scratch = _split_refs(rest, quantized)
    del bt_ref          # consumed by the index maps, not the body
    pi = pl.program_id(2)
    c_true = n_p * t
    nv_b = nv_ref[pl.program_id(0)]
    ks = None if ks_ref is None else ks_ref[0, 0]             # (1, T|C)
    vs = None if vs_ref is None else vs_ref[0, 0]
    # identical operand casts / op order to the contiguous kernel:
    # bf16 values (fp8 casts are exact in bf16), f32 accumulation
    q = q_ref[0, 0].astype(jnp.bfloat16).astype(op_dtype)     # (R, Dh)
    k = k_ref[0, 0].astype(jnp.bfloat16).astype(jnp.float32)  # (t, Dh)
    v = v_ref[0, 0].astype(jnp.bfloat16).astype(jnp.float32)

    if not online:
        # gather this page's rows into the (C, Dh) scratch; every row
        # is freshly written once per (bi, ki) sweep, so no init step
        k_acc, v_acc = scratch
        k_acc[pl.ds(pi * t, t), :] = k
        v_acc[pl.ds(pi * t, t), :] = v

        @pl.when(pi == n_p - 1)
        def _done():
            # the contiguous single-block body over the gathered rows
            s = _scores(q, k_acc[...].astype(op_dtype), ks,
                        sm_scale=sm_scale)                    # (R, C)
            slot = jax.lax.broadcasted_iota(jnp.int32, (1, c_true), 1)
            valid = _valid(nv_b, slot, c_true=c_true, q_len=q_len, gp=gp)
            s = jnp.where(valid, s, NEG_INF)
            o_ref[0, 0] = _exact_combine(s, v_acc[...].astype(op_dtype),
                                         vs, op_dtype=op_dtype)
        return

    # split-K long-context path: C exceeds the gathered-scratch VMEM
    # ceiling, so accumulate online (flash) across pages instead — one
    # page per grid step, never revisited, mirroring the contiguous
    # multi-block path op for op (one C block == one page)
    s = _scores(q, k.astype(op_dtype), ks, sm_scale=sm_scale)  # (R, t)
    slot = pi * t + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
    valid = _valid(nv_b, slot, c_true=c_true, q_len=q_len, gp=gp)
    s = jnp.where(valid, s, NEG_INF)
    _online_step(s, v, vs, valid, pi * t, nv_b, scratch, c_true=c_true,
                 first=pi == 0, last=pi == n_p - 1, o_ref=o_ref,
                 op_dtype=op_dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret",
                                             "q_len"))
def decode_attn_paged_pallas(q, k, v, k_scale, v_scale, n_valid,
                             block_table, *, sm_scale: float,
                             interpret: bool = False, q_len: int = 1):
    """Fused decode attention over the floating-page pool.

    q: (B, KV, R, Dh) with R = q_len·Gp, Gp % 8 == 0 (dispatch pads);
    k/v: (P, KV, T, Dh) e4m3|bf16 page-pool payloads; k_scale/v_scale:
    (P, KV, T) f32 or both None (bf16 cache); n_valid: (B,) int32 and
    block_table: (B, pages_per_slot) int32 — BOTH scalar-prefetch
    (SMEM), in that order.  Logical tokens [j*T, (j+1)*T) of row b
    live in physical page block_table[b, j]; the index maps gather
    them page tile by page tile (see module docstring).  Up to
    C = MAX_SINGLE_BLOCK the gathered exact-softmax path runs; past it
    the online split-K path (f32 round-off vs the oracle).  ``q_len``
    > 1 is the speculative verify step (draft-major rows, in-step
    causal mask; every n_valid entry must be ≥ q_len).  Returns
    (B, KV, R, Dh) f32."""
    from repro.core.runtime_flags import mm_operand_dtype
    from .ref import gather_pages

    b, kvh, rows, dh = q.shape
    p_pool, kvh_k, t = k.shape[:3]
    assert k.shape == v.shape == (p_pool, kvh, t, dh), (q.shape, k.shape)
    assert rows % q_len == 0, (rows, q_len)
    gp = rows // q_len
    assert gp % 8 == 0, f"G={gp} not padded to the 8-row sublane tile"
    n_p = block_table.shape[1]
    assert block_table.shape == (b, n_p)
    quantized = k_scale is not None
    c_true = n_p * t
    online = c_true > MAX_SINGLE_BLOCK
    grid = (b, kvh, n_p)
    bt = block_table.astype(jnp.int32)

    in_specs = [
        pl.BlockSpec((1, 1, rows, dh),
                     lambda bi, ki, pi, nv, bt: (bi, ki, 0, 0)),
        pl.BlockSpec((1, 1, t, dh),
                     lambda bi, ki, pi, nv, bt: (bt[bi, pi], ki, 0, 0)),
        pl.BlockSpec((1, 1, t, dh),
                     lambda bi, ki, pi, nv, bt: (bt[bi, pi], ki, 0, 0)),
    ]
    args = [q, k, v]
    if quantized:
        assert k_scale.shape == v_scale.shape == (p_pool, kvh, t)
        if online:
            # per page, straight from the pool
            in_specs += 2 * [pl.BlockSpec(
                (1, 1, 1, t),
                lambda bi, ki, pi, nv, bt: (bt[bi, pi], ki, 0, 0))]
            args += [k_scale.reshape(p_pool, kvh, 1, t),
                     v_scale.reshape(p_pool, kvh, 1, t)]
        else:
            # each row's scales as one (1, C) vector (module docstring)
            in_specs += 2 * [pl.BlockSpec(
                (1, 1, 1, c_true),
                lambda bi, ki, pi, nv, bt: (bi, ki, 0, 0))]
            args += [gather_pages(sc, bt).reshape(b, kvh, 1, c_true)
                     for sc in (k_scale, v_scale)]
    if online:
        scratch = _online_scratch(rows, dh)
    else:
        scratch = [pltpu.VMEM((c_true, dh), jnp.float32),  # gathered K
                   pltpu.VMEM((c_true, dh), jnp.float32)]  # gathered V
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows, dh),
                               lambda bi, ki, pi, nv, bt: (bi, ki, 0, 0)),
        scratch_shapes=scratch,
    )
    nv = jnp.broadcast_to(n_valid.astype(jnp.int32).reshape(-1), (b,))
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, n_p=n_p, t=t,
                          sm_scale=sm_scale, quantized=quantized,
                          op_dtype=mm_operand_dtype(), q_len=q_len,
                          gp=gp, online=online),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, dh), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(nv, bt, *args)
