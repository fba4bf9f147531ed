"""Plain reference of a pre-norm decoder-only transformer in float32.

Follows the published architectures of the configurations that name
``"reference": "decoder"`` (OLMo-7B, arXiv:2402.00838), with the
departures each configuration file lists under ``assumed``.  Straightforward ``jax.numpy`` at the highest matmul
precision: no kernels, no cache, no batching tricks, nothing imported
from the program under test.

  x = embed[tokens]
  per layer:  x = x + Attn(Norm1(x));  x = x + W_down(silu(Norm2(x) W_gate) * Norm2(x) W_up)
  logits = NormF(x) @ head

Norm is LayerNorm (gamma, beta) or RMSNorm (gamma); attention is causal
multi-head attention with rotary position embedding (rotate-half form
over the whole head, base ``rope_theta``) and scale 1/sqrt(head_dim);
K/V heads are shared by ``num_attention_heads / num_key_value_heads``
query heads.  A configuration key this reference does not implement
(another ``hidden_act``, tied embeddings, a sequence past
``max_position_embeddings``) is refused (``check_conf``), never ignored.

``rounding`` (a ``quantize.Rounding``) turns the reference into a
control: every linear layer on rounded operands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .quantize import HIGHEST, Rounding, linear

# the configuration's keys of the architecture, and the values this
# reference implements
IMPLEMENTED = {"hidden_act": ("silu",), "tie_word_embeddings": (False,),
               "norm": ("layernorm", "rmsnorm")}


def check_conf(conf: dict, seq: int) -> None:
    """Raise unless the reference implements what ``conf`` states, at
    sequence length ``seq``."""
    for key, values in IMPLEMENTED.items():
        if conf[key] not in values:
            raise ValueError(f"{key} = {conf[key]!r}: the reference "
                             f"implements {values}")
    if seq > conf["max_position_embeddings"]:
        raise ValueError(f"sequence {seq} > max_position_embeddings "
                         f"{conf['max_position_embeddings']}")


def norm(conf: dict, x, gamma, beta=None):
    x = x.astype(jnp.float32)
    eps = conf["norm_eps"]
    if conf["norm"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gamma


def rope(x, positions, theta: float):
    """x (B, S, H, Dh), positions (B, S): rotate-half RoPE."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions[..., None].astype(jnp.float32) * freqs   # (B, S, Dh/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _proj(x, w, r):
    """x (..., d) @ w (d, *out) -> (..., *out)."""
    return linear(x, w.reshape(w.shape[0], -1), r).reshape(
        *x.shape[:-1], *w.shape[1:])


def attention(conf: dict, lw: dict, x, positions, r: Rounding | None):
    b, s, _ = x.shape
    h, kvh = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh = conf["head_dim"]
    q = rope(_proj(x, lw["wq"], r), positions, conf["rope_theta"])
    k = rope(_proj(x, lw["wk"], r), positions, conf["rope_theta"])
    v = _proj(x, lw["wv"], r)
    g = h // kvh
    q = q.reshape(b, s, kvh, g, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        precision=HIGHEST) * dh ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v, precision=HIGHEST)
    out = out.reshape(b, s, h * dh)
    return linear(out, lw["wo"].reshape(h * dh, -1), r)


def layer(conf: dict, lw: dict, x, positions, r: Rounding | None = None):
    """One decoder layer on the f32 residual stream x (B, S, d)."""
    x = x + attention(conf, lw, norm(conf, x, lw["ln1.gamma"],
                                     lw.get("ln1.beta")), positions, r)
    hn = norm(conf, x, lw["ln2.gamma"], lw.get("ln2.beta"))
    hid = jax.nn.silu(linear(hn, lw["w_gate"], r)) * linear(hn, lw["w_up"], r)
    return x + linear(hid, lw["w_down"], r)


def final_logits(conf: dict, w: dict, x, r: Rounding | None = None):
    x = norm(conf, x, w["final_norm.gamma"], w.get("final_norm.beta"))
    return linear(x, w["head"], r)


def embed(w: dict, tokens):
    return jnp.take(w["embed"].astype(jnp.float32), tokens, axis=0)


def cross_entropy(logits, labels):
    """Summed token cross-entropy (f32) of logits (N, V), labels (N,)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


def loss(conf: dict, params: dict, tokens, labels,
         r: Rounding | None = None, head_blocks: int = 4):
    """Mean cross-entropy of a whole model (params in the layout of
    ``bench.lib.weights``, layers unstacked as a list).  Each layer and
    each block of rows of the head is recomputed in the backward pass
    instead of kept, so the reference fits beside its gradients."""
    b, s = tokens.shape
    check_conf(conf, s)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = embed(params, tokens)
    step = jax.checkpoint(lambda lw, x: layer(conf, lw, x, positions, r))
    for lw in params["layers"]:
        x = step(lw, x)
    x = x.reshape(b * s, -1)
    lab = labels.reshape(b * s)
    rows = x.shape[0] // head_blocks
    head = jax.checkpoint(lambda xb, lb: cross_entropy(
        final_logits(conf, params, xb, r), lb))
    total = sum(head(x[i * rows:(i + 1) * rows], lab[i * rows:(i + 1) * rows])
                for i in range(head_blocks))
    return total / (b * s)
