"""Multi-head Latent Attention (DeepSeek-V2), kv_lora_rank=512.

Train/prefill materialize per-head K/V from the latent; decode uses the
*absorbed* form so the cache is just (c_kv, k_rope) — (512+64) values
per token shared across all heads.  Projections are MOSS-quantized; the
tiny absorbed einsums stay bf16.  With ``cfg.yarn_factor`` the rope dims
take YaRN's frequencies and the softmax YaRN's scale, in every mode.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.formats import QuantConfig
from repro.core.linear import QT, qlinear, dense_general
from repro.core.runtime_flags import einsum as rf_einsum
from repro.distributed.sharding import shard
from .layers import PDef, apply_rope, rmsnorm, rope_for, yarn_mscale
from ._attn_core import chunked_attention


class MLACache(NamedTuple):
    c_kv: jax.Array    # (B, T, kv_lora)
    k_rope: jax.Array  # (B, T, q_rope)
    idx: jax.Array     # () shared write position, or (B,) per-slot
                       # lengths (continuous-batching engine — rows at
                       # different depths; docs/continuous-batching.md)


def mla_defs(cfg):
    d, h = cfg.d_model, cfg.n_heads
    dq = cfg.q_nope + cfg.q_rope
    return {
        "wq": PDef((d, h, dq), ("fsdp", "heads", None), quantized=True),
        "w_dkv": PDef((d, cfg.kv_lora), ("fsdp", "latent"), quantized=True),
        "w_kr": PDef((d, cfg.q_rope), ("fsdp", None), quantized=True),
        "kv_norm": PDef((cfg.kv_lora,), (None,), "zeros"),
        "w_uk": PDef((cfg.kv_lora, h, cfg.q_nope), ("latent", "heads", None),
                     quantized=True),
        "w_uv": PDef((cfg.kv_lora, h, cfg.v_head), ("latent", "heads", None),
                     quantized=True),
        "wo": PDef((h, cfg.v_head, d), ("heads", None, "fsdp"),
                   quantized=True),
    }


def init_mla_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    # per-slot idx (B,) is widened by transformer.init_caches(per_slot=)
    return MLACache(
        c_kv=jnp.zeros((batch, max_len, cfg.kv_lora), dtype),
        k_rope=jnp.zeros((batch, max_len, cfg.q_rope), dtype),
        idx=jnp.zeros((), jnp.int32))


def cache_logical(cfg) -> MLACache:
    return MLACache(c_kv=("batch", "kv_seq", None),
                    k_rope=("batch", "kv_seq", None), idx=())


def _rope(cfg, x, positions):
    freqs, mag = rope_for(cfg, cfg.q_rope)
    return apply_rope(x, positions, cfg.rope_theta, freqs=freqs, mag=mag)


def softmax_scale(cfg) -> float:
    """(qk_nope + qk_rope)^-0.5, times YaRN's m² where it scales rope."""
    m = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
         if cfg.yarn_factor and cfg.yarn_mscale_all_dim else 1.0)
    return (cfg.q_nope + cfg.q_rope) ** -0.5 * m * m


def _latent(cfg, p, x, positions, qcfg):
    c_kv = qlinear(x, p["w_dkv"], qcfg)                       # (B,S,512)
    c_kv = rmsnorm(c_kv, p["kv_norm"].w if isinstance(p["kv_norm"], QT)
                   else p["kv_norm"], cfg.norm_eps)
    k_r = qlinear(x, p["w_kr"], qcfg)[..., None, :]           # (B,S,1,64)
    k_r = _rope(cfg, k_r, positions)[..., 0, :]
    return c_kv, k_r


def _queries(cfg, p, x, positions, qcfg):
    q = dense_general(x, p["wq"], qcfg)                       # (B,S,H,192)
    q_n, q_r = q[..., :cfg.q_nope], q[..., cfg.q_nope:]
    q_r = _rope(cfg, q_r, positions)
    return q_n, q_r


def mla_attention(cfg, p, x, positions, qcfg: QuantConfig,
                  cache: MLACache | None = None, mode: str = "train"):
    b, s, _ = x.shape
    h = cfg.n_heads
    q_n, q_r = _queries(cfg, p, x, positions, qcfg)
    c_kv, k_r = _latent(cfg, p, x, positions, qcfg)

    if mode == "decode":
        t = cache.c_kv.shape[1]
        start = cache.idx % t
        zero = jnp.zeros((), jnp.int32)
        if cache.idx.ndim == 1:
            # per-slot cache: each batch row appends at its own depth
            dus_row = jax.vmap(
                lambda buf, upd, st: jax.lax.dynamic_update_slice(
                    buf, upd.astype(buf.dtype), (st, zero)),
                in_axes=(0, 0, 0))
            new_cache = MLACache(c_kv=dus_row(cache.c_kv, c_kv, start),
                                 k_rope=dus_row(cache.k_rope, k_r, start),
                                 idx=cache.idx + s)
        else:
            new_cache = MLACache(
                c_kv=jax.lax.dynamic_update_slice(
                    cache.c_kv, c_kv.astype(cache.c_kv.dtype),
                    (zero, start, zero)),
                k_rope=jax.lax.dynamic_update_slice(
                    cache.k_rope, k_r.astype(cache.k_rope.dtype),
                    (zero, start, zero)),
                idx=cache.idx + s)
        # absorbed decode: q_lat[b,h,L] = q_nope · W_uk
        q_lat = rf_einsum("bshn,lhn->bshl", q_n, p["w_uk"].w,
                          out_dtype=jnp.float32)
        scores = (rf_einsum("bshl,btl->bsht", q_lat, new_cache.c_kv,
                            out_dtype=jnp.float32)
                  + rf_einsum("bshr,btr->bsht", q_r, new_cache.k_rope,
                              out_dtype=jnp.float32))
        scores *= softmax_scale(cfg)
        nv = jnp.broadcast_to(new_cache.idx.reshape(-1), (b,))
        valid = jnp.arange(t)[None, :] < jnp.minimum(nv, t)[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        ctx_lat = rf_einsum("bsht,btl->bshl", w, new_cache.c_kv,
                            out_dtype=jnp.float32)            # (B,1,H,512)
        out = rf_einsum("bshl,lhv->bshv", ctx_lat, p["w_uv"].w,
                        out_dtype=jnp.float32).astype(x.dtype)
    else:
        # materialized K/V per head for chunked attention
        k_n = dense_general(c_kv, p["w_uk"], qcfg)            # (B,S,H,128)
        v = dense_general(c_kv, p["w_uv"], qcfg)              # (B,S,H,128)
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r[:, :, None, :], (b, s, h, cfg.q_rope))],
            axis=-1)
        q = jnp.concatenate([q_n, q_r], axis=-1)
        q = shard(q, "batch", None, "heads", None)
        k = shard(k, "batch", None, "heads", None)
        v = shard(v, "batch", None, "heads", None)
        out = chunked_attention(cfg, q, k, v, scale=softmax_scale(cfg))
        new_cache = None
        if mode == "prefill":
            fresh = init_mla_cache(cfg, b, cache.c_kv.shape[1]
                                   if cache is not None else s)
            zero = jnp.zeros((), jnp.int32)
            new_cache = MLACache(
                c_kv=jax.lax.dynamic_update_slice(
                    fresh.c_kv, c_kv.astype(fresh.c_kv.dtype),
                    (zero, zero, zero)),
                k_rope=jax.lax.dynamic_update_slice(
                    fresh.k_rope, k_r.astype(fresh.k_rope.dtype),
                    (zero, zero, zero)),
                idx=jnp.asarray(s, jnp.int32))

    wo = p["wo"]
    y = qlinear(out.reshape(b, s, -1),
                QT(wo.w.reshape(-1, cfg.d_model), wo.s), qcfg)
    return shard(y, "batch", "seq", "embed"), new_cache
