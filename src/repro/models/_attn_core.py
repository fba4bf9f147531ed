"""Shared flash-style chunked-attention core (used by GQA and MLA)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.runtime_flags import einsum as rf_einsum

NEG_INF = -1e30


def _window(cfg):
    if cfg.attn_type in ("swa", "local"):
        return cfg.window
    return None


@jax.named_scope("attn_core")
def chunked_attention(cfg, q, k, v, q_pos0: int = 0):
    """Flash-style causal attention.

    q: (B,S,H,Dh); k,v: (B,T,KV,Dh).  Outer lax.map over query chunks,
    inner lax.scan over KV chunks with an online softmax — peak score
    memory is (B, Cq, H, Ck) instead of (B, S, H, T).
    """
    b, s, h, dh = q.shape
    dv = v.shape[-1]                     # may differ from dh (MLA)
    t = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    window = _window(cfg)
    scale = dh ** -0.5

    cq = min(cfg.attn_chunk, s)
    ck = min(cfg.attn_chunk, t)
    nq, nk = -(-s // cq), -(-t // ck)
    q = jnp.pad(q, ((0, 0), (0, nq * cq - s), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, nk * ck - t), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, nk * ck - t), (0, 0), (0, 0)))
    # repeat kv->h heads (GQA); fused per-chunk below to bound memory
    kc = k.reshape(b, nk, ck, kvh, dh)
    vc = v.reshape(b, nk, ck, kvh, dv)
    qc = q.reshape(b, nq, cq, h, dh)

    q_positions = q_pos0 + jnp.arange(nq * cq).reshape(nq, cq)
    k_positions = jnp.arange(nk * ck).reshape(nk, ck)
    t_valid = t  # mask out kv padding

    def q_chunk(args):
        qi, qpos = args                                  # (B,Cq,H,Dh),(Cq,)

        @jax.checkpoint
        def kv_step(carry, xs):
            m, l, acc = carry
            kj, vj, kpos = xs                            # (B,Ck,KV,Dh),(Ck,)
            kj = jnp.repeat(kj, g, axis=2)               # (B,Ck,H,Dh)
            vj = jnp.repeat(vj, g, axis=2)
            scores = rf_einsum("bqhd,bkhd->bhqk", qi, kj,
                               out_dtype=jnp.float32) * scale
            mask = qpos[:, None] >= kpos[None, :]        # causal
            if window is not None:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            mask &= (kpos < t_valid)[None, :]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            p_ = jnp.exp(scores - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p_.sum(axis=-1)
            acc_new = (acc * corr[..., None]
                       + rf_einsum("bhqk,bkhd->bhqd", p_, vj,
                                   out_dtype=jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, cq), jnp.float32)
        a0 = jnp.zeros((b, h, cq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
             k_positions))
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # (B,H,Cq,Dh)
        return out.transpose(0, 2, 1, 3)                 # (B,Cq,H,Dh)

    # checkpoint both loop levels: scan/map autodiff otherwise stacks the
    # per-step softmax residuals into an (nq, nk, B, H, Cq, Ck) tensor —
    # the flash-attention backward instead recomputes scores per chunk.
    outs = jax.lax.map(jax.checkpoint(q_chunk),
                       (qc.transpose(1, 0, 2, 3, 4), q_positions))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * cq, h, dv)
    return out[:, :s].astype(q.dtype)


