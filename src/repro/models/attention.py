"""Attention: GQA/MQA/MHA with RoPE, full/sliding-window/local variants,
flash-style chunked softmax (never materializes S×S), and a KV cache
with ring-buffer semantics for window attention.

All projections are MOSS-quantized linears.  Scores/softmax run in f32
(the paper keeps non-GEMM ops in high precision).  Decode attention
routes through ``repro.kernels.dispatch.decode_attention`` — the fused
Pallas kernel over the fp8/bf16 cache by default, the scale-folding
einsum path under ``REPRO_DECODE_ATTN=einsum``
(docs/decode-attention.md).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.formats import QuantConfig
from repro.core.linear import dense_general
from repro.core.runtime_flags import decode_attn_path
from repro.distributed.sharding import shard
from repro.kernels import dispatch
from repro.core.runtime_flags import einsum as rf_einsum
from .layers import PDef, apply_rope
from ._attn_core import NEG_INF, chunked_attention, _window


class KVCache(NamedTuple):
    """Decode KV cache, kv-head-major.

    Layout (one layer, pre-stacking; C = ``cache_len`` = min(max_len,
    window)):

      k, v      (B, KV, C, Dh)  payloads — e4m3 (fp8 cache, the
                                serving default) or bf16.  kv-head
                                major so the decode kernel reads
                                contiguous (C, Dh) tiles per
                                (batch, kv-head) with no transpose
      k_scale,  (B, KV, C)      f32 per-(token, kv-head) scales when
      v_scale                   fp8, else None — one scale per written
                                position's head vector (amax over Dh)
      idx       () | (B,)       int32: absolute position of the next
                                write (NOT mod C) — doubles as the
                                valid-token count: slot s holds a live
                                position iff s < min(idx, C).  Scalar:
                                one shared ring position for every
                                batch row (training-eval / legacy
                                serving).  Vector (``per_slot`` cache,
                                the continuous-batching engine): each
                                row tracks its own depth, so requests
                                with different prompt lengths coexist
                                (docs/continuous-batching.md)

    The fp8 layout halves the decode-step HBM read (the
    memory-roofline term that dominates decode cells —
    benchmarks/roofline.py); the scales add 4/Dh bytes/element.
    Scale convention: payload · scale reconstructs the stored vector;
    decode attention never materializes that product — the K scale
    folds into the score and the V scale into the combine weight
    (einsum path), or both fold inside the kernel (fused path).

    Ring append contract (``_cache_write``): position p lives in slot
    p % C; appends of S ≥ C positions keep the last C (prefill of a
    window cache), shorter appends write ``[idx % C, idx % C + S)``
    contiguously — the serving engine never wraps a multi-token append
    mid-stream (prefill starts at idx=0; decode appends S=1).

    Floating-page pool variant (``block_table`` not None —
    docs/paged-attention.md): the payload leaves change meaning to a
    GLOBAL pool shared by every slot —

      k, v          (P, KV, T, Dh)  P physical pages of T tokens
      k/v_scale     (P, KV, T)      per-(token, kv-head) scales (fp8)
      idx           (B,)            per-slot logical depth, as before
      block_table   (B, NP)         int32: logical page j of slot b is
                                    physical row ``block_table[b, j]``
                                    (NP = pages_per_slot = C/T)

    Decode attention gathers pages through the block table
    (``dispatch.decode_attention_paged``); a decode append writes one
    position into page ``block_table[b, idx[b]//T]`` at offset
    ``idx[b] % T``.  Ring semantics don't apply (the engine gates
    float mode to non-windowed families)."""

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array | None
    v_scale: jax.Array | None
    idx: jax.Array
    block_table: jax.Array | None = None


def _quant_kv(x):
    """(B, KV, S, Dh) -> (e4m3 payload, per-(B, KV, S) f32 scale).
    One amax over each position's head vector; TINY-clamped so zero
    vectors quantize to q=0 with a finite scale."""
    from repro.core.formats import E4M3_MAX, TINY, cast_fp8

    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = jnp.maximum(amax, TINY) / E4M3_MAX
    q = cast_fp8(x.astype(jnp.float32) / s[..., None], "e4m3")
    return q, s


def _dequant_kv(q, s, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)


def attn_defs(cfg):
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    defs = {
        "wq": PDef((d, h, dh), ("fsdp", "heads", None), quantized=True),
        "wk": PDef((d, kv, dh), ("fsdp", "kv_heads", None), quantized=True),
        "wv": PDef((d, kv, dh), ("fsdp", "kv_heads", None), quantized=True),
        "wo": PDef((h, dh, d), ("heads", None, "fsdp"), quantized=True),
    }
    if cfg.qk_norm:
        defs["q_norm"] = PDef((dh,), (None,), "ones")
        defs["k_norm"] = PDef((dh,), (None,), "ones")
    return defs


def cache_len(cfg, max_len: int) -> int:
    w = _window(cfg)
    return min(max_len, w) if w else max_len


def resolve_kv_cache_dtype(cfg) -> str:
    """Active KV-cache storage dtype: ``REPRO_KV_CACHE`` env override,
    else the per-arch config (default "fp8" — decode is memory-bound
    and the fp8 cache halves the dominant HBM-read term; docs/
    serving.md).  Only consulted at cache *init*: an existing cache
    keeps its layout.  MLA's absorbed latent cache ignores this (it is
    already ~an order of magnitude smaller than per-head K/V)."""
    from repro.core.runtime_flags import kv_cache_override

    return kv_cache_override() or cfg.kv_cache_dtype


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> KVCache:
    """Builds the shared-scalar-``idx`` cache; the serving engine's
    per-slot variant (``idx`` as a (B,) vector) is produced by
    ``transformer.init_caches(per_slot=True)``, which widens the idx
    of every cache node in one place."""
    c = cache_len(cfg, max_len)
    shape = (batch, cfg.n_kv, c, cfg.head_dim)
    idx = jnp.zeros((), jnp.int32)
    if resolve_kv_cache_dtype(cfg) == "fp8":
        return KVCache(k=jnp.zeros(shape, jnp.float8_e4m3fn),
                       v=jnp.zeros(shape, jnp.float8_e4m3fn),
                       k_scale=jnp.zeros(shape[:-1], jnp.float32),
                       v_scale=jnp.zeros(shape[:-1], jnp.float32),
                       idx=idx)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   k_scale=None, v_scale=None, idx=idx)


def init_page_pool(cfg, num_pages: int, pages_per_slot: int,
                   batch: int, page_size: int,
                   dtype=jnp.bfloat16) -> KVCache:
    """Builds ONE layer's floating-page pool cache (pre-stacking):
    payload (P, KV, T, Dh) + scales (P, KV, T) shared by every slot,
    per-slot depth ``idx`` (B,) and ``block_table`` (B, NP) int32.
    Storage dtype follows ``resolve_kv_cache_dtype`` exactly like
    ``init_cache``.  Physical page contents are zero-initialized; a
    page is only ever read through a block table whose slot depth
    covers it, so stale retired-page bytes are masked out by the
    kernel's validity mask regardless."""
    shape = (num_pages, cfg.n_kv, page_size, cfg.head_dim)
    idx = jnp.zeros((batch,), jnp.int32)
    bt = jnp.zeros((batch, pages_per_slot), jnp.int32)
    if resolve_kv_cache_dtype(cfg) == "fp8":
        return KVCache(k=jnp.zeros(shape, jnp.float8_e4m3fn),
                       v=jnp.zeros(shape, jnp.float8_e4m3fn),
                       k_scale=jnp.zeros(shape[:-1], jnp.float32),
                       v_scale=jnp.zeros(shape[:-1], jnp.float32),
                       idx=idx, block_table=bt)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   k_scale=None, v_scale=None, idx=idx, block_table=bt)


def cache_logical(cfg) -> KVCache:
    """Logical sharding axes for ONE layer's cache (pre-stacking).
    The seq dim carries the model axis when kv_heads can't (resolve_spec
    drops whichever doesn't divide)."""
    kv = ("batch", "kv_heads", "kv_seq", None)
    sc = ("batch", "kv_heads", "kv_seq")
    fp8 = resolve_kv_cache_dtype(cfg) == "fp8"
    return KVCache(k=kv, v=kv, k_scale=sc if fp8 else None,
                   v_scale=sc if fp8 else None, idx=())


def _qk_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32))


def _project_qkv(cfg, p, x, positions, qcfg: QuantConfig):
    q = dense_general(x, p["wq"], qcfg)                  # (B,S,H,Dh)
    k = dense_general(x, p["wk"], qcfg)                  # (B,S,KV,Dh)
    v = dense_general(x, p["wv"], qcfg)
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"], cfg.norm_eps).astype(x.dtype)
        k = _qk_norm(k, p["k_norm"], cfg.norm_eps).astype(x.dtype)
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pct)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_pct)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def _decode_attention(cfg, q, cache: KVCache, n_valid):
    """Single-step attention against the cache.

    q: (B,1,H,Dh).  GQA grouping: head h belongs to kv head h // G
    (G = H // KV), so the (B, KV, G, Dh) regroup is a free reshape.
    Routed through ``dispatch.decode_attention`` — fused Pallas kernel
    on the pallas/interpret backends, the scale-folding einsum oracle
    on ref; ``REPRO_DECODE_ATTN=einsum`` pins the einsum path."""
    b, _, h, dh = q.shape
    kvh = cache.k.shape[1]
    g = h // kvh
    qg = q.reshape(b, kvh, g, dh)
    backend = "ref" if decode_attn_path() == "einsum" else None
    if cache.block_table is not None:
        out = dispatch.decode_attention_paged(
            qg, cache.k, cache.v, cache.k_scale, cache.v_scale,
            n_valid, cache.block_table, sm_scale=dh ** -0.5,
            backend=backend)
    else:
        out = dispatch.decode_attention(
            qg, cache.k, cache.v, cache.k_scale, cache.v_scale, n_valid,
            sm_scale=dh ** -0.5, backend=backend)
    return out.reshape(b, 1, h, dh).astype(q.dtype)


def _verify_attention(cfg, q, cache: KVCache, n_valid):
    """Speculative verify: S draft queries per slot against the cache
    the drafts were just written into, in ONE fused step.

    q: (B,S,H,Dh); ``n_valid`` is the POST-write depth (every entry
    ≥ S).  The (B, KV, S, G, Dh) regroup feeds the batched-query 5-D
    entry of ``dispatch.decode_attention{,_paged}``: draft j attends
    ``slot < n_valid[b] - (S-1-j)`` — its own freshly-written position
    and everything before it, but no later draft's — so row j computes
    EXACTLY what sequential decode step j would, and greedy
    accept/reject on the outputs is token-for-token exact
    (docs/speculative-decoding.md).  Unlike ``_chunk_attention`` the
    history is never dequantized in HBM: the same fused-kernel /
    scale-folding-einsum contract as single-token decode applies, so
    the verify jaxpr keeps 0 cache-sized upcasts/dots."""
    b, s, h, dh = q.shape
    kvh = cache.k.shape[1]
    g = h // kvh
    # (B,S,H,Dh) -> (B,KV,S,G,Dh): head h of draft j is (kv h//G, g h%G)
    qg = q.reshape(b, s, kvh, g, dh).transpose(0, 2, 1, 3, 4)
    backend = "ref" if decode_attn_path() == "einsum" else None
    if cache.block_table is not None:
        out = dispatch.decode_attention_paged(
            qg, cache.k, cache.v, cache.k_scale, cache.v_scale,
            n_valid, cache.block_table, sm_scale=dh ** -0.5,
            backend=backend)
    else:
        out = dispatch.decode_attention(
            qg, cache.k, cache.v, cache.k_scale, cache.v_scale, n_valid,
            sm_scale=dh ** -0.5, backend=backend)
    out = out.transpose(0, 2, 1, 3, 4).reshape(b, s, h, dh)
    return out.astype(q.dtype)


def _chunk_attention(cfg, q, k_new, v_new, cache: KVCache, pos0):
    """Chunked-prefill attention: S new prompt tokens at each slot's
    depth against the already-resident history plus an in-chunk causal
    mask (docs/continuous-batching.md).

    q: (B,S,H,Dh); k_new/v_new: the chunk's pre-quantization bf16
    K/V in projection layout (B,S,KV,Dh) — the values ``_cache_write``
    just appended.  ``pos0`` is the PRE-write depth: history positions
    ``< pos0[b]`` are read back from the (post-write) cache — paged
    caches gather each slot's pages through the block table — so fresh
    and garbage-padded positions (all ≥ pos0) are masked regardless of
    content, while the chunk's diagonal block attends its exact bf16
    values, matching whole-prompt prefill's treatment.  One combined
    f32 softmax over history + chunk.  fp8 caches read history back
    dequantized (the accepted chunked-vs-whole difference; bf16 caches
    read back the exact original bytes).  Non-windowed families only
    (``transformer.chunk_prefill_supported``) — history positions are
    absolute, never ring-wrapped."""
    b, s, h, dh = q.shape
    kvh = k_new.shape[2]
    g = h // kvh
    scale = dh ** -0.5
    fp8 = cache.k_scale is not None
    pos0 = jnp.broadcast_to(jnp.atleast_1d(pos0), (b,))

    if cache.block_table is not None:
        def gather(pool):                     # (P,KV,T,...) -> (B,KV,C,...)
            x = pool[cache.block_table]       # (B,NP,KV,T,...)
            x = jnp.moveaxis(x, 2, 1)         # (B,KV,NP,T,...)
            return x.reshape(b, kvh, -1, *x.shape[4:])

        kh, vh = gather(cache.k), gather(cache.v)
        ksh = gather(cache.k_scale) if fp8 else None
        vsh = gather(cache.v_scale) if fp8 else None
    else:
        kh, vh = cache.k, cache.v
        ksh, vsh = cache.k_scale, cache.v_scale
    if fp8:
        kh = _dequant_kv(kh, ksh)
        vh = _dequant_kv(vh, vsh)
    c = kh.shape[2]

    qg = q.reshape(b, s, kvh, g, dh).transpose(0, 2, 3, 1, 4)
    kf = k_new.transpose(0, 2, 1, 3)          # (B,KV,S,Dh)
    vf = v_new.transpose(0, 2, 1, 3)

    s_hist = rf_einsum("bkgsd,bkcd->bkgsc", qg, kh) * scale
    s_self = rf_einsum("bkgsd,bktd->bkgst", qg, kf) * scale
    hist_ok = jnp.arange(c, dtype=jnp.int32)[None, :] < pos0[:, None]
    s_hist = jnp.where(hist_ok[:, None, None, None, :], s_hist, NEG_INF)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    s_self = jnp.where(causal[None, None, None], s_self, NEG_INF)
    scores = jnp.concatenate([s_hist, s_self], axis=-1)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = (rf_einsum("bkgsc,bkcd->bkgsd", p[..., :c], vh)
           + rf_einsum("bkgst,bktd->bkgsd", p[..., c:], vf))
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh)
    return out.astype(q.dtype)


def _cache_write(cfg, cache: KVCache, k_new, v_new) -> KVCache:
    """Append S_new positions (prefill: many; decode: 1) with ring
    semantics for window attention; fp8 caches quantize on write.

    ``k_new``/``v_new`` arrive in projection layout (B, S, KV, Dh) and
    are transposed once to the cache's kv-head-major layout — a
    prompt-sized copy at prefill, a single position at decode; the
    cache itself is only ever written in place."""
    fp8 = cache.k_scale is not None
    k_new = k_new.transpose(0, 2, 1, 3)                   # (B,KV,S,Dh)
    v_new = v_new.transpose(0, 2, 1, 3)
    if fp8:
        k_new, ks_new = _quant_kv(k_new)
        v_new, vs_new = _quant_kv(v_new)
    c = cache.k.shape[2]
    s_new = k_new.shape[2]

    if cache.block_table is not None:
        # floating-page pool: position p lands in physical page
        # block_table[b, p // T] at in-page offset p % T.  The engine
        # guarantees every target page is writable (refcount 1) via
        # copy-on-write BEFORE the step, so a scatter here never
        # aliases a shared page.  Advanced indices (page, off) with the
        # interior ':' put the batch dim first → (B[, S], KV, ...)
        # updates.
        assert cache.idx.ndim == 1, "paged cache uses per-slot depths"
        t = cache.k.shape[2]
        if s_new == 1:
            pos = cache.idx
            page = jnp.take_along_axis(
                cache.block_table, (pos // t)[:, None], axis=1)[:, 0]
            off = pos % t

            def put(pool, upd):
                return pool.at[page, :, off].set(upd.astype(pool.dtype))

            return cache._replace(
                k=put(cache.k, k_new[:, :, 0]),
                v=put(cache.v, v_new[:, :, 0]),
                k_scale=put(cache.k_scale, ks_new[:, :, 0]) if fp8
                else None,
                v_scale=put(cache.v_scale, vs_new[:, :, 0]) if fp8
                else None,
                idx=cache.idx + 1)

        # chunked prefill: S positions from each slot's depth into its
        # own pages.  Padded tail positions past the block-table width
        # are redirected to the pool's TRASH row (the one extra
        # physical page init_paged_pools allocates; explicit where —
        # a clipped gather would hit the request's own LAST real
        # page); in-table entries that aren't assigned yet already
        # hold the trash row id (the engine restamps them).  Trash
        # bytes are never read: history masking is `< pos0` and
        # n_valid never covers them.
        n_pages = cache.block_table.shape[1]
        trash = cache.k.shape[0] - 1
        pos = cache.idx[:, None] + jnp.arange(s_new, dtype=jnp.int32)
        lp = pos // t
        page = jnp.where(
            lp < n_pages,
            jnp.take_along_axis(cache.block_table,
                                jnp.clip(lp, 0, n_pages - 1), axis=1),
            trash)
        off = pos % t

        def put_s(pool, upd):                 # upd (B,KV,S,...)
            u = jnp.moveaxis(upd, 2, 1).astype(pool.dtype)
            return pool.at[page, :, off].set(u)

        return cache._replace(
            k=put_s(cache.k, k_new), v=put_s(cache.v, v_new),
            k_scale=put_s(cache.k_scale, ks_new) if fp8 else None,
            v_scale=put_s(cache.v_scale, vs_new) if fp8 else None,
            idx=cache.idx + s_new)

    if cache.idx.ndim == 1 and s_new > 1:
        # per-slot chunked-prefill append (identity placement): each
        # row writes S positions at its own depth.  Advanced-index
        # scatter with mode="drop" so a chunk's padded tail positions
        # (≥ C) vanish instead of clamping onto live slots
        # (dynamic_update_slice CLAMPS start indices).  No ring
        # semantics: the engine gates chunked prefill to non-windowed
        # families (C == max_len).
        pos = cache.idx[:, None] + jnp.arange(s_new, dtype=jnp.int32)
        b_idx = jnp.arange(cache.k.shape[0])[:, None]

        def put_p(buf, upd):                  # upd (B,KV,S,...)
            u = jnp.moveaxis(upd, 2, 1).astype(buf.dtype)
            return buf.at[b_idx, :, pos].set(u, mode="drop")

        return KVCache(put_p(cache.k, k_new), put_p(cache.v, v_new),
                       put_p(cache.k_scale, ks_new) if fp8 else None,
                       put_p(cache.v_scale, vs_new) if fp8 else None,
                       cache.idx + s_new)

    if s_new >= c:
        # keep the last C positions (prefill of a window cache);
        # ring layout: position p lives in slot p % C.  Never reached
        # with a per-slot idx vector: the engine prefills one request
        # at a time into a fresh scalar-idx cache and merges rows.
        assert cache.idx.ndim == 0, "multi-token ring append needs a " \
            "shared scalar idx (engine prefills per request)"
        start = (cache.idx + s_new - c) % c
        roll = lambda x: jnp.roll(x[:, :, -c:].astype(x.dtype), start,
                                  axis=2)
        return KVCache(roll(k_new).astype(cache.k.dtype),
                       roll(v_new).astype(cache.v.dtype),
                       roll(ks_new) if fp8 else None,
                       roll(vs_new) if fp8 else None,
                       cache.idx + s_new)
    start = cache.idx % c
    zero = jnp.zeros((), jnp.int32)

    if cache.idx.ndim == 1:
        # per-slot cache: every batch row writes at its own ring
        # position (decode slots at different depths).  vmap the
        # row-level dynamic_update_slice over the batched start —
        # lowers to a scatter (single-host serving; the SPMD caveat
        # below doesn't bite because the engine runs unsharded).
        assert s_new == 1, "per-slot cache appends decode one token"

        def dus_row(buf, upd, st):
            idxs = (zero, st) + (zero,) * (buf.ndim - 2)
            return jax.lax.dynamic_update_slice(
                buf, upd.astype(buf.dtype), idxs)

        dus_b = jax.vmap(dus_row, in_axes=(0, 0, 0))
        k = dus_b(cache.k, k_new, start)
        v = dus_b(cache.v, v_new, start)
        ks = dus_b(cache.k_scale, ks_new, start) if fp8 else None
        vs = dus_b(cache.v_scale, vs_new, start) if fp8 else None
        return KVCache(k, v, ks, vs, cache.idx + s_new)

    # contiguous in-place write (decode: one slot; prefill: [idx, idx+s))
    # via dynamic_update_slice — advanced-index scatter would lower to a
    # full-cache f32 select copy under SPMD.  Wraparound can only occur
    # for multi-token appends into a ring cache mid-stream, which the
    # serving engine never does (prefill starts at idx=0; decode s=1).
    def dus(buf, upd):
        idxs = (zero, zero, start) + (zero,) * (buf.ndim - 3)
        return jax.lax.dynamic_update_slice(buf, upd.astype(buf.dtype),
                                            idxs)

    k = dus(cache.k, k_new)
    v = dus(cache.v, v_new)
    ks = dus(cache.k_scale, ks_new) if fp8 else None
    vs = dus(cache.v_scale, vs_new) if fp8 else None
    return KVCache(k, v, ks, vs, cache.idx + s_new)


@jax.named_scope("attn")
def attention(cfg, p, x, positions, qcfg: QuantConfig,
              cache: KVCache | None = None, mode: str = "train"):
    """Returns (out, new_cache).  Modes:
      train   — chunked causal attention, no cache
      prefill — chunked causal attention + cache fill
      decode  — S == 1: single new token against the cache (the fused
                kernel); S > 1: a chunked-prefill step — S prompt
                tokens appended at the slot's depth, attending history
                + an in-chunk causal mask (non-windowed families only;
                the engine gates this)
      verify  — speculative verify: S = k tokens ([last committed,
                drafts...]) written to the cache then attended in one
                fused batched-query step under the in-step causal
                mask.  S == 1 degenerates to exactly the decode path.
                Non-windowed, unwrapped caches only (the engine gates
                this; docs/speculative-decoding.md)
    """
    if mode in ("decode", "verify"):
        q, k_new, v_new = _project_qkv(cfg, p, x, positions, qcfg)
        if x.shape[1] == 1 or mode == "verify":
            new_cache = _cache_write(cfg, cache, k_new, v_new)
            n_valid = new_cache.idx
            out = (_decode_attention(cfg, q, new_cache, n_valid)
                   if x.shape[1] == 1
                   else _verify_attention(cfg, q, new_cache, n_valid))
        else:
            pos0 = cache.idx
            new_cache = _cache_write(cfg, cache, k_new, v_new)
            out = _chunk_attention(cfg, q, k_new, v_new, new_cache, pos0)
    else:
        q, k, v = _project_qkv(cfg, p, x, positions, qcfg)
        out = chunked_attention(cfg, q, k, v)
        new_cache = None
        if mode == "prefill":
            new_cache = _cache_write(
                cfg, init_cache(cfg, x.shape[0], cache.k.shape[2]
                                if cache is not None else x.shape[1]),
                k, v)
    out = shard(out, "batch", None, "heads", None)
    y = dense_general(out.reshape(*out.shape[:-2], -1),
                      QTflat(p["wo"]), qcfg)
    return shard(y, "batch", "seq", "embed"), new_cache


def QTflat(wt):
    """wo is stored (H, Dh, d); flatten to (H·Dh, d) for the GEMM.
    Preserves the activation-scale field (delayed-scale serving)."""
    from repro.core.linear import QT
    w = wt.w if hasattr(wt, "w") else wt
    s = wt.s if hasattr(wt, "s") else None
    return QT(w.reshape(-1, w.shape[-1]), s, getattr(wt, "a", None))
