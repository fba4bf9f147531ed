"""Model assembly: family-specific blocks composed into segments, with
scan-over-layers (+ remat) so compile time and HLO size are
depth-independent.

A model = embed → [segments] → final norm → LM head.  Each segment is a
repeated block unit: params are stacked (n, ...) and applied with
lax.scan; per-unit KV caches / recurrent states are stacked the same
way and threaded through the scan.  Heterogeneous stacks (deepseek's
leading dense layer, recurrentgemma's trailing recurrent pair) are
separate segments.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.formats import QuantConfig
from repro.distributed.sharding import shard
from . import attention as attn_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import rwkv6 as rwkv_mod
from .layers import (
    apply_ffn,
    apply_norm,
    embed_defs,
    embed_tokens,
    ffn_defs,
    lm_head,
    norm_defs,
    sinusoidal_embedding,
    stack_defs,
)


class Segment(NamedTuple):
    name: str
    n: int                       # repeats
    defs: dict                   # one unit's param defs (unstacked)
    apply: Callable              # (cfg,qcfg,p,x,pos,cache,mode)->(x,cache,aux)
    init_cache: Callable | None  # (cfg,batch,max_len)->one unit's cache
    cache_logical: Callable | None = None  # cfg -> logical axes pytree


# ---------------------------------------------------------------------------
# Block bodies
# ---------------------------------------------------------------------------


def _dense_unit(cfg, d_ff=None):
    return {
        "ln1": norm_defs(cfg, cfg.d_model),
        "attn": attn_mod.attn_defs(cfg),
        "ln2": norm_defs(cfg, cfg.d_model),
        "ffn": ffn_defs(cfg, d_ff),
    }


def _dense_apply(cfg, qcfg, p, x, pos, cache, mode):
    # each sub-block's profiler scope holds its pre-norm and residual add
    with jax.named_scope("attn"):
        h, cache = attn_mod.attention(cfg, p["attn"],
                                      apply_norm(cfg, p["ln1"], x), pos,
                                      qcfg, cache, mode)
        x = x + h
    with jax.named_scope("mlp"):
        x = x + apply_ffn(cfg, p["ffn"], apply_norm(cfg, p["ln2"], x), qcfg)
    return x, cache, jnp.zeros((), jnp.float32)


def _moe_unit(cfg, use_mla: bool):
    unit = {
        "ln1": norm_defs(cfg, cfg.d_model),
        "attn": mla_mod.mla_defs(cfg) if use_mla else attn_mod.attn_defs(cfg),
        "ln2": norm_defs(cfg, cfg.d_model),
        "moe": moe_mod.moe_defs(cfg),
    }
    if cfg.n_shared > 0:
        unit["shared"] = ffn_defs(cfg, cfg.n_shared * cfg.d_ff)
    return unit


def _moe_apply_factory(use_mla: bool):
    def apply(cfg, qcfg, p, x, pos, cache, mode):
        att = mla_mod.mla_attention if use_mla else attn_mod.attention
        h, cache = att(cfg, p["attn"], apply_norm(cfg, p["ln1"], x), pos,
                       qcfg, cache, mode)
        x = x + h
        hn = apply_norm(cfg, p["ln2"], x)
        h, aux = moe_mod.moe_block(cfg, p["moe"], hn, qcfg, mode)
        if cfg.n_shared > 0:
            h = h + apply_ffn(cfg, p["shared"], hn, qcfg)
        return x + h, cache, aux
    return apply


def _rec_unit(cfg):
    return {
        "ln1": norm_defs(cfg, cfg.d_model),
        "rec": rglru_mod.rglru_defs(cfg),
        "ln2": norm_defs(cfg, cfg.d_model),
        "ffn": ffn_defs(cfg),
    }


def _rec_apply(cfg, qcfg, p, x, pos, cache, mode):
    h, cache = rglru_mod.rglru_block(cfg, p["rec"],
                                     apply_norm(cfg, p["ln1"], x), qcfg,
                                     cache, mode)
    x = x + h
    h = apply_ffn(cfg, p["ffn"], apply_norm(cfg, p["ln2"], x), qcfg)
    return x + h, cache, jnp.zeros((), jnp.float32)


def _griffin_unit(cfg):
    """RecurrentGemma repeating unit: (rec, rec, local-attn), each with
    its own FFN sub-block (1:2 attention:recurrence ratio)."""
    return {
        "rec0": _rec_unit(cfg),
        "rec1": _rec_unit(cfg),
        "attn0": _dense_unit(cfg),
    }


def _griffin_apply(cfg, qcfg, p, x, pos, cache, mode):
    cache = cache if cache is not None else (None, None, None)
    x, c0, _ = _rec_apply(cfg, qcfg, p["rec0"], x, pos, cache[0], mode)
    x, c1, _ = _rec_apply(cfg, qcfg, p["rec1"], x, pos, cache[1], mode)
    x, c2, _ = _dense_apply(cfg, qcfg, p["attn0"], x, pos, cache[2], mode)
    return x, (c0, c1, c2), jnp.zeros((), jnp.float32)


def _rwkv_unit(cfg):
    return {
        "ln1": norm_defs(cfg, cfg.d_model),
        "tm": rwkv_mod.timemix_defs(cfg),
        "ln2": norm_defs(cfg, cfg.d_model),
        "cm": rwkv_mod.chanmix_defs(cfg),
    }


def _rwkv_apply(cfg, qcfg, p, x, pos, cache, mode):
    st = cache if cache is not None else rwkv_mod.init_rwkv_state(
        cfg, x.shape[0])
    h, st = rwkv_mod.time_mix(cfg, p["tm"],
                              apply_norm(cfg, p["ln1"], x), qcfg, st, mode)
    x = x + h
    h, st = rwkv_mod.channel_mix(cfg, p["cm"],
                                 apply_norm(cfg, p["ln2"], x), qcfg, st, mode)
    return x + h, st, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Segments per family
# ---------------------------------------------------------------------------


def build_segments(cfg) -> list[Segment]:
    if cfg.family in ("dense", "audio", "vlm"):
        return [Segment("blocks", cfg.n_layers, _dense_unit(cfg),
                        _dense_apply, attn_mod.init_cache,
                        attn_mod.cache_logical)]
    if cfg.family == "moe":
        return [Segment("blocks", cfg.n_layers, _moe_unit(cfg, False),
                        _moe_apply_factory(False), attn_mod.init_cache,
                        attn_mod.cache_logical)]
    if cfg.family == "mla_moe":
        segs = []
        if cfg.first_dense:
            dense_cfg = {
                "ln1": norm_defs(cfg, cfg.d_model),
                "attn": mla_mod.mla_defs(cfg),
                "ln2": norm_defs(cfg, cfg.d_model),
                "ffn": ffn_defs(cfg, cfg.dense_ff or cfg.d_ff),
            }

            def dense_mla_apply(cfg_, qcfg, p, x, pos, cache, mode):
                h, cache = mla_mod.mla_attention(
                    cfg_, p["attn"], apply_norm(cfg_, p["ln1"], x), pos,
                    qcfg, cache, mode)
                x = x + h
                h = apply_ffn(cfg_, p["ffn"], apply_norm(cfg_, p["ln2"], x),
                              qcfg)
                return x + h, cache, jnp.zeros((), jnp.float32)

            segs.append(Segment("dense0", cfg.first_dense, dense_cfg,
                                dense_mla_apply, mla_mod.init_mla_cache,
                                mla_mod.cache_logical))
        segs.append(Segment("blocks", cfg.n_layers - cfg.first_dense,
                            _moe_unit(cfg, True), _moe_apply_factory(True),
                            mla_mod.init_mla_cache, mla_mod.cache_logical))
        return segs
    if cfg.family == "hybrid":
        n_units, rem = divmod(cfg.n_layers, 3)
        segs = [Segment("griffin", n_units, _griffin_unit(cfg),
                        _griffin_apply, _griffin_cache,
                        _griffin_cache_logical)]
        if rem:
            segs.append(Segment("tail_rec", rem, _rec_unit(cfg),
                                _rec_apply, _rec_cache,
                                rglru_mod.cache_logical))
        return segs
    if cfg.family == "ssm":
        return [Segment("blocks", cfg.n_layers, _rwkv_unit(cfg),
                        _rwkv_apply,
                        lambda c, b, m: rwkv_mod.init_rwkv_state(c, b),
                        rwkv_mod.cache_logical)]
    raise ValueError(cfg.family)


def _rec_cache(cfg, batch, max_len):
    return rglru_mod.init_rglru_state(cfg, batch)


def _griffin_cache(cfg, batch, max_len):
    return (rglru_mod.init_rglru_state(cfg, batch),
            rglru_mod.init_rglru_state(cfg, batch),
            attn_mod.init_cache(cfg, batch, max_len))


def _griffin_cache_logical(cfg):
    return (rglru_mod.cache_logical(cfg), rglru_mod.cache_logical(cfg),
            attn_mod.cache_logical(cfg))


def cache_logical_tree(cfg):
    """Logical sharding axes matching init_caches (stacked: leading
    'layers' axis on array leaves)."""
    out = {}
    for seg in build_segments(cfg):
        if seg.cache_logical is None:
            out[seg.name] = None
            continue
        one = seg.cache_logical(cfg)
        out[seg.name] = jax.tree.map(
            lambda ax: ("layers", *ax), one,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x))
    return out


# ---------------------------------------------------------------------------
# Whole-model defs / forward
# ---------------------------------------------------------------------------


def model_defs(cfg) -> dict:
    segs = build_segments(cfg)
    defs: dict[str, Any] = {
        "embed": embed_defs(cfg),
        "final_norm": norm_defs(cfg, cfg.d_model),
    }
    for seg in segs:
        defs[seg.name] = stack_defs(seg.defs, seg.n)
    return defs


def map_cache_nodes(tree, fn):
    """Apply ``fn`` to every cache/state NamedTuple (KVCache, MLACache,
    RGLRUState, RWKVState — anything with an ``idx`` field) inside a
    caches pytree, preserving the surrounding dict/tuple structure."""
    if hasattr(tree, "_replace") and hasattr(tree, "idx"):
        return fn(tree)
    if isinstance(tree, dict):
        return {k: map_cache_nodes(v, fn) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(map_cache_nodes(v, fn) for v in tree)
    return tree


def iter_cache_nodes(tree):
    """Yield every cache/state NamedTuple (see ``map_cache_nodes``)."""
    if hasattr(tree, "_replace") and hasattr(tree, "idx"):
        yield tree
    elif isinstance(tree, dict):
        for v in tree.values():
            yield from iter_cache_nodes(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from iter_cache_nodes(v)


def init_caches(cfg, batch: int, max_len: int, per_slot: bool = False):
    """Stacked caches for every segment (decode/prefill).

    ``per_slot=True`` builds the serving-engine variant: every cache
    node's ``idx`` becomes a (batch,) vector so decode slots track
    independent depths (docs/continuous-batching.md).  The payload
    layout is identical — only the write-position/validity bookkeeping
    widens."""
    caches = {}
    for seg in build_segments(cfg):
        if seg.init_cache is None:
            caches[seg.name] = None
            continue
        one = seg.init_cache(cfg, batch, max_len)
        if per_slot:
            one = map_cache_nodes(
                one, lambda n: n._replace(
                    idx=jnp.zeros((batch,), jnp.int32)))
        caches[seg.name] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (seg.n, *x.shape)).copy()
            if hasattr(x, "shape") else x, one)
    return caches


def paged_decode_supported(cfg, max_len: int, page_size: int) -> bool:
    """True iff this (cfg, max_len) can decode from a floating page
    pool (docs/paged-attention.md): per-head KVCache families only
    (MLA's latent cache and the ssm/hybrid recurrent states have no
    page structure), no window/ring semantics (the pool append writes
    ``idx // T`` directly), and C a whole number of pages."""
    if cfg.family not in ("dense", "audio", "vlm", "moe"):
        return False
    c = attn_mod.cache_len(cfg, max_len)
    return c == max_len and c % page_size == 0


def chunk_prefill_supported(cfg, max_len: int) -> bool:
    """True iff chunked prefill can write prompt chunks at an offset
    into this family's cache (docs/continuous-batching.md): per-head
    KVCache families only, and no window/ring semantics — chunk
    positions map to absolute cache slots, never wrap."""
    if cfg.family not in ("dense", "audio", "vlm", "moe"):
        return False
    return attn_mod.cache_len(cfg, max_len) == max_len


def spec_verify_supported(cfg, max_len: int) -> bool:
    """True iff the speculative verify step can run against this
    (cfg, max_len) (docs/speculative-decoding.md): the same gate as
    chunked prefill — per-head KVCache families with an unwrapped
    (C == max_len) cache, since a k-token verify write lands at
    absolute positions and rejection truncates the length vector,
    neither of which has ring semantics."""
    return chunk_prefill_supported(cfg, max_len)


def init_paged_pools(cfg, max_len: int, num_pages: int,
                     page_size: int) -> dict:
    """Stacked floating-page pool caches for every segment — the
    serving engine's float-placement variant of ``init_caches``.
    Each array leaf gains the leading layers axis exactly like
    ``init_caches``; per-slot ``idx`` / ``block_table`` leaves start
    at batch 0 (the engine restamps them from host state every step).
    The pool carries ``num_pages + 1`` physical rows: the extra last
    row is the TRASH page — chunked-prefill padding positions and
    unassigned block-table entries point at it, so garbage scatters
    never land in another request's page (its bytes are never read;
    docs/continuous-batching.md).  Requires
    ``paged_decode_supported``."""
    assert paged_decode_supported(cfg, max_len, page_size)
    pps = attn_mod.cache_len(cfg, max_len) // page_size
    caches = {}
    for seg in build_segments(cfg):
        one = attn_mod.init_page_pool(cfg, num_pages + 1, pps, 0,
                                      page_size)
        caches[seg.name] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (seg.n, *x.shape)).copy()
            if hasattr(x, "shape") else x, one)
    return caches


def _qh_drain():
    # quant-health ys hook: None (the slot's historical value) unless a
    # collection window is open — see repro.obs.quant_health.
    from repro.obs.quant_health import QH
    return QH.drain_layer()


def _qh_stash(tree) -> None:
    if tree:
        from repro.obs.quant_health import QH
        QH.stash_stacked(tree)


def forward(cfg, qcfg: QuantConfig, params, batch: dict,
            caches=None, mode: str = "train"):
    """Returns (logits, new_caches, aux_loss).

    batch: {"tokens": (B,S) int32} or {"embeds": (B,S,d)}; decode mode
    additionally relies on caches' idx for positions.
    """
    if cfg.input_mode == "embeddings" and "embeds" in batch:
        x = batch["embeds"].astype(jnp.bfloat16)
        x = shard(x, "batch", "seq", "embed")
        b, s = x.shape[:2]
    else:
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_tokens(cfg, params["embed"], tokens)

    if mode in ("decode", "verify") and caches is not None:
        pos0 = _first_idx(caches)
        if pos0.ndim:        # per-slot cache: (B,) depths -> (B, S)
            positions = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)
        else:
            positions = pos0 + jnp.arange(s, dtype=jnp.int32)
    else:
        positions = jnp.arange(s, dtype=jnp.int32)

    if cfg.pos_embedding == "sinusoidal":
        pe = sinusoidal_embedding(positions, cfg.d_model)
        x = x + (pe if positions.ndim > 1 else pe[None]).astype(x.dtype)

    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {}
    for seg in build_segments(cfg):
        p_seg = params[seg.name]
        c_seg = caches.get(seg.name) if caches is not None else None

        if c_seg is None:
            # train: no cache threaded; params are the scan xs
            def body(carry, p_l, seg=seg):
                x_, aux_ = carry
                x_, _, aux_l = seg.apply(cfg, qcfg, p_l, x_, positions,
                                         None, mode)
                return (x_, aux_ + aux_l), None

            if cfg.remat and mode == "train":
                body = jax.checkpoint(body, prevent_cse=False)
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), p_seg,
                                             length=seg.n)
            new_caches[seg.name] = None
        else:
            # serving: the stacked cache rides in the CARRY (not xs/ys)
            # so the while loop aliases it in place — one copy of the
            # multi-GB KV cache instead of separate in/out stacks.
            # The ys slot carries the quant-health stats when a
            # collection window is open (stacked to (layers, ...) by
            # scan itself) and stays None — the jaxpr it always had —
            # otherwise (repro.obs.quant_health).
            def body(carry, p_l, seg=seg):
                x_, aux_, c_stack, li = carry
                c_l = jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(
                        c, li, 0, keepdims=False), c_stack)
                x_, c_new, aux_l = seg.apply(cfg, qcfg, p_l, x_,
                                             positions, c_l, mode)
                c_stack = jax.tree.map(
                    lambda c, u: jax.lax.dynamic_update_index_in_dim(
                        c, u.astype(c.dtype), li, 0), c_stack, c_new)
                return (x_, aux_ + aux_l, c_stack, li + 1), _qh_drain()

            (x, aux_total, c_seg, _), hs = jax.lax.scan(
                body, (x, aux_total, c_seg, jnp.zeros((), jnp.int32)),
                p_seg, length=seg.n)
            _qh_stash(hs)
            new_caches[seg.name] = c_seg

    with jax.named_scope("head"):
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_head(cfg, params["embed"], x, qcfg)
    return logits, (new_caches if caches is not None else None), aux_total


def _first_idx(caches):
    # every cache tracks the same absolute position(s); take any `idx`.
    # Stacked over layers: (L,) shared scalar -> (), (L, B) per-slot
    # vector -> (B,) — strip the layer dim and return the rest.
    for c in caches.values():
        if c is None:
            continue
        for node in iter_cache_nodes(c):
            if node.idx is not None:
                return node.idx[0]
    return jnp.zeros((), jnp.int32)


@jax.named_scope("loss")
def ce_loss(cfg, logits, labels, mask=None):
    """Token cross-entropy in f32.

    The label pick is an iota-compare + masked sum (not take_along_axis)
    so a vocab-sharded logits tensor reduces locally + all-reduces a
    scalar instead of being gathered (GSPMD-friendly)."""
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    picked = jnp.sum(jnp.where(vocab_iota == labels[..., None], shifted,
                               0.0), axis=-1)
    ll = picked - lse
    if mask is None:
        return -ll.mean()
    mask = mask.astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
