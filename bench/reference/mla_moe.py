"""Plain reference of DeepSeek-V2's decoder (arXiv:2405.04434): multi-
head latent attention with YaRN rope, and a mixture of experts with
shared experts, in float32 ``jax.numpy`` at the highest matmul
precision: no kernels, no cache, no dispatch, nothing imported from the
program under test.

  x = embed[tokens]
  per layer:  x = x + MLA(RMSNorm1(x))
              h = RMSNorm2(x)
              dense layers (the first ``first_k_dense_replace``):
                x = x + FFN(h)
              the others:
                x = x + Σ_{held e} g_e(h) FFN_e(h) + FFN_shared(h)
  logits = RMSNormF(x) @ head

FFN(h) = W_down(silu(h W_gate) * h W_up).  MLA: q = h W_q split into
q_nope (128) and q_rope (64) per head; c = RMSNorm(h W_dkv) (the 512-
wide latent); k = [c W_uk, rope(h W_kr) shared by the heads], v = c W_uv;
softmax((q·k) · scale) v per head, then W_o.  YaRN (``rope_scaling``):
frequency f·mask + (f/factor)(1 - mask) of each rope pair i, with f =
θ^(-2i/64), mask = 1 - clip((i - low)/(high - low), 0, 1), low =
floor(c(β_fast)), high = ceil(c(β_slow)), c(n) = 64 ln(L/(2πn)) /
(2 ln θ); scale = 192^-0.5 · m², m = 0.1·mscale_all_dim·ln(factor) + 1;
cos and sin times m(mscale)/m(mscale_all_dim).

Routing: p = softmax(h W_router) over all ``n_routed_experts_published``
experts; g_e = p_e where e is among the token's top-k, else 0, not
renormalised (``norm_topk_prob`` false).  Only the experts this chip
holds (``n_routed_experts``, the first ones) are summed, as the program
sums them.  The expert balance loss (``seq_aux``) per sequence:
E Σ_e f_e P_e with f_e the share of the sequence's top-k picks that go
to e and P_e its mean probability, averaged over the sequences and
summed over the MoE layers; the objective is the mean cross-entropy plus
``aux_coef`` times it.

RMSNorm gains are trained as their offsets from 1, as the program
holds them: weight decay pulls them toward 1.

Departures, each a fixed relabelling of random weights: rope rotates
the two halves of the 64 rope dims (the program's layout), where
DeepSeek's checkpoints pair interleaved dims — a fixed permutation of
the random ``wq`` / ``w_kr`` rope columns; the shared experts are one
FFN of width ``n_shared_experts`` x 1408, which is what two FFNs whose
outputs are added compute.

Computed in blocks so that it fits one chip beside its gradients: each
layer, each block of queries of the attention, each expert and each
block of rows of the head is recomputed in the backward pass instead of
kept.  ``rounding`` (``quantize.Rounding``) turns it into a control:
every GEMM the program quantizes on rounded operands (the router stays
f32, as in the program).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import program_moe, weights_moe

from .quantize import HIGHEST, Rounding, linear
from .train import _adamw, _split, _unflat, flat, learning_rate


def rmsnorm(conf: dict, x, offset):
    """RMSNorm with gain 1 + ``offset`` (see ``Maker``)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + conf["rms_norm_eps"]) * (1.0 + offset)


class Maker(weights_moe.LeafMaker):
    """The seed's leaves with each RMSNorm gain held as its offset from
    1, the parameter the program trains (its RMSNorm is x·(1 + scale)),
    so AdamW's weight decay pulls gains toward 1 on both sides."""

    def __call__(self, key, name: str, layer: int = 0):
        leaf = super().__call__(key, name, layer)
        return leaf - 1.0 if name.endswith(".gamma") else leaf


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(conf: dict):
    """(frequencies of the rope pairs, cos/sin magnitude, softmax
    scale) of the configuration's YaRN rope."""
    rs, dim, theta = (conf["rope_scaling"], conf["qk_rope_head_dim"],
                      conf["rope_theta"])

    def c(n):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (2 * math.pi * n)) / (2 * math.log(theta)))

    low = max(math.floor(c(rs["beta_fast"])), 0)
    high = min(math.ceil(c(rs["beta_slow"])), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / dim)
    mask = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    freqs = f * mask + f / rs["factor"] * (1.0 - mask)
    m_all = _mscale(rs["factor"], rs["mscale_all_dim"])
    scale = ((conf["qk_nope_head_dim"] + dim) ** -0.5
             * (m_all * m_all if rs["mscale_all_dim"] else 1.0))
    return freqs, _mscale(rs["factor"], rs["mscale"]) / m_all, scale


def rope(x, positions, freqs, mag):
    """x (B, S, ..., 64), positions (S,): the halves rotated."""
    ang = positions[:, None].astype(jnp.float32) * freqs      # (S, 32)
    ang = ang.reshape(1, ang.shape[0], *([1] * (x.ndim - 3)), -1)
    cos, sin = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _proj(x, w, r):
    """x (..., K) @ w (K, *out) -> (..., *out)."""
    return linear(x, w.reshape(w.shape[0], -1), r).reshape(
        *x.shape[:-1], *w.shape[1:])


def attention(conf: dict, lw: dict, h, r: Rounding | None,
              q_block: int):
    b, s, _ = h.shape
    nope = conf["qk_nope_head_dim"]
    freqs, mag, scale = yarn(conf)
    pos = jnp.arange(s)
    q = _proj(h, lw["wq"], r)                                 # (B,S,H,192)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, freqs,
                                             mag)], -1)
    c = rmsnorm(conf, linear(h, lw["w_dkv"], r), lw["kv_norm.gamma"])
    k_r = rope(linear(h, lw["w_kr"], r), pos, freqs, mag)     # (B,S,64)
    k = _proj(c, lw["w_uk"], r)                               # (B,S,H,128)
    k = jnp.concatenate(
        [k, jnp.broadcast_to(k_r[:, :, None], (*k.shape[:3],
                                               k_r.shape[-1]))], -1)
    v = _proj(c, lw["w_uv"], r)                               # (B,S,H,128)

    @jax.checkpoint
    def block(qb, start):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                            precision=HIGHEST) * scale
        causal = (start + jnp.arange(qb.shape[1]))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    out = jnp.concatenate([block(q[:, i:i + q_block], i)
                           for i in range(0, s, q_block)], axis=1)
    return _proj(out.reshape(b, s, -1), lw["wo"].reshape(-1, h.shape[-1]),
                 r)


def ffn(h, w_gate, w_up, w_down, r):
    return linear(jax.nn.silu(linear(h, w_gate, r)) * linear(h, w_up, r),
                  w_down, r)


def balance(conf: dict, probs, top_ids):
    """Per-sequence expert balance loss, averaged over the sequences:
    probs (B, S, E), top_ids (B, S, k)."""
    e, k = probs.shape[-1], top_ids.shape[-1]
    counts = jax.nn.one_hot(top_ids, e, dtype=jnp.float32).sum(axis=(1, 2))
    f = counts / (top_ids.shape[1] * k)                       # (B, E)
    return jnp.mean(e * jnp.sum(f * probs.mean(axis=1), axis=-1))


def moe(conf: dict, lw: dict, h, r: Rounding | None):
    """(held experts' routed sum + shared experts, balance loss)."""
    probs = jax.nn.softmax(jnp.matmul(h, lw["router"], precision=HIGHEST),
                           axis=-1)                           # (B,S,E)
    top, ids = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    gate = jnp.sum(jax.nn.one_hot(ids, probs.shape[-1], dtype=jnp.float32)
                   * top[..., None], axis=-2)                 # g_e, (B,S,E)
    expert = jax.checkpoint(lambda h, wg, wu, wd: ffn(h, wg, wu, wd, r))
    y = ffn(h, lw["s_gate"], lw["s_up"], lw["s_down"], r)
    for e in range(conf["n_routed_experts"]):
        y = y + gate[..., e:e + 1] * expert(h, lw["e_gate"][e],
                                            lw["e_up"][e], lw["e_down"][e])
    return y, balance(conf, probs, ids)


def layer(conf: dict, i: int, lw: dict, x, r: Rounding | None,
          q_block: int):
    """One layer on the residual x (B, S, d): (x, balance loss)."""
    x = x + attention(conf, lw, rmsnorm(conf, x, lw["ln1.gamma"]), r,
                      q_block)
    h = rmsnorm(conf, x, lw["ln2.gamma"])
    if weights_moe.is_dense(conf, i):
        return x + ffn(h, lw["w_gate"], lw["w_up"], lw["w_down"], r), 0.0
    y, bal = moe(conf, lw, h, r)
    return x + y, bal


def cross_entropy(logits, labels):
    """Summed token cross-entropy (f32) of logits (N, V), labels (N,)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


def loss(conf: dict, job: dict, params: dict, tokens, labels,
         r: Rounding | None = None):
    """(objective, mean cross-entropy) of a whole model (params in the
    layout of ``bench.lib.weights_moe``, layers as a list)."""
    b, s = tokens.shape
    program_moe.check_conf(conf, s)
    x = jnp.take(params["embed"], tokens, axis=0)
    bal = 0.0
    for i, lw in enumerate(params["layers"]):
        step = jax.checkpoint(lambda lw, x, i=i: layer(
            conf, i, lw, x, r, job["reference_query_block"]))
        x, bl = step(lw, x)
        bal = bal + bl
    x = x.reshape(b * s, -1)
    lab = labels.reshape(b * s)
    n = job["reference_head_blocks"]
    rows = x.shape[0] // n
    head = jax.checkpoint(lambda xb, lb: cross_entropy(
        linear(rmsnorm(conf, xb, params["final_norm.gamma"]),
               params["head"], r), lb))
    ce = sum(head(x[i * rows:(i + 1) * rows], lab[i * rows:(i + 1) * rows])
             for i in range(n)) / (b * s)
    return ce + job["aux_coef"] * bal, ce


def run(conf: dict, job: dict, key, batches, rounding: Rounding | None = None,
        keep_rows: int | None = None) -> dict:
    """The readings ``bench.reference.train.run`` returns (loss per step,
    norm of the first clipped gradient per leaf, norm of each leaf's
    change) over ``batches`` ((tokens, labels) per step), from its
    written-out AdamW and schedule, with this model's leaves and loss.
    ``keep_rows`` keeps only the first rows of every batch: the planted
    fault "half of the batch left out, the mean taken over the rest"."""
    with jax.default_matmul_precision("highest"):
        return _run(conf, job, key, batches, rounding, keep_rows)


def _run(conf, job, key, batches, rounding, keep_rows) -> dict:
    opt = job["optimizer"]
    maker = Maker(conf)
    params = maker.tree(key)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss(conf, job, p, t, l, rounding), has_aux=True))
    sq = jax.jit(lambda x: jnp.sum(jnp.square(x)))
    upd = jax.jit(_adamw, static_argnames=("b1", "b2", "eps", "wd"),
                  donate_argnums=(0, 2, 3))
    moments: dict = {}
    losses, grad_norms = [], {}
    for t, (tokens, labels) in enumerate(batches):
        if keep_rows is not None:
            tokens, labels = tokens[:keep_rows], labels[:keep_rows]
        (_, ce), grads = grad_fn(params, tokens, labels)
        losses.append(float(ce))
        fp, fg = flat(params), flat(grads)
        del grads
        sqs = {n: float(sq(g)) for n, g in fg.items()}
        gnorm = math.sqrt(sum(sqs.values()))
        factor = min(1.0, opt["grad_clip"] / max(gnorm, 1e-9))
        if t == 0:
            grad_norms = {n: math.sqrt(v) * factor for n, v in sqs.items()}
        lr = learning_rate(opt, t)
        for n in fp:
            m, v = moments.get(n, (None, None))
            if m is None:
                m = np.zeros(fp[n].shape, np.float32)
                v = np.zeros(fp[n].shape, np.float32)
            new_p, m, v = upd(fp[n], fg.pop(n), jnp.asarray(m), jnp.asarray(v),
                              jnp.float32(factor), jnp.float32(lr),
                              jnp.float32(t + 1), b1=opt["b1"], b2=opt["b2"],
                              eps=opt["eps"], wd=opt["weight_decay"])
            moments[n] = (np.asarray(m), np.asarray(v))
            fp[n] = new_p
        params = _unflat(fp, conf["num_hidden_layers"])
        del fp
    moments.clear()
    change = {}
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    for n, p in flat(params).items():
        name, layer = _split(n)
        change[n] = float(diff(p, maker(key, name, layer)))
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}
