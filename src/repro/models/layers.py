"""Shared model components: parameter definitions, norms, RoPE,
activations, embeddings, FFNs — all linear layers route through the MOSS
quantized ``qlinear``.

Parameter system
----------------
``PDef`` is the single source of truth per parameter: shape, logical
sharding axes, initializer, and whether the tensor is a *quantized
linear weight* (participates in FP8 + automatic scaling).  From a pytree
of PDefs we derive materialized params, ShapeDtypeStructs (dry-run),
PartitionSpecs, and the autoscale mask.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.formats import QuantConfig
from repro.core.linear import QT, qlinear
from repro.distributed.sharding import resolve_spec, shard


class PDef(NamedTuple):
    shape: tuple[int, ...]
    logical: tuple[str | None, ...]   # logical sharding axes per dim
    init: str = "normal"              # normal | zeros | ones | embed | small
    quantized: bool = False           # FP8 linear weight (autoscaled)
    dtype: Any = jnp.float32


def is_pdef(x) -> bool:
    return isinstance(x, PDef)


def _fan_in(shape: tuple[int, ...]) -> int:
    return shape[0] if len(shape) > 1 else shape[0]


def init_param(key, d: PDef) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "embed":
        return jax.random.normal(key, d.shape, d.dtype) * 0.02
    if d.init == "small":
        return jax.random.normal(key, d.shape, d.dtype) * 0.006
    # truncated-normal fan-in init for linear weights
    std = 1.0 / math.sqrt(max(_fan_in(d.shape), 1))
    return jax.random.truncated_normal(key, -2, 2, d.shape, d.dtype) * std


def init_tree(defs, key):
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_pdef)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [init_param(k, d) for k, d in zip(keys, leaves)])


def abstract_tree(defs):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs,
        is_leaf=is_pdef)


def spec_tree(defs, mesh):
    return jax.tree.map(
        lambda d: resolve_spec(d.logical, mesh, d.shape), defs,
        is_leaf=is_pdef)


def quant_mask_tree(defs):
    return jax.tree.map(lambda d: d.quantized, defs, is_leaf=is_pdef)


def stack_defs(defs, n: int, axis_name: str = "layers"):
    """Prepend a stacked-layer dim to every PDef (scan-over-layers)."""
    return jax.tree.map(
        lambda d: PDef((n, *d.shape), (axis_name, *d.logical), d.init,
                       d.quantized, d.dtype),
        defs, is_leaf=is_pdef)


def wrap_qt(params, scales, mask):
    """Bundle quantized weights with their predicted scales: quantized
    leaves become QT(w, s); others stay raw arrays."""
    return jax.tree.map(
        lambda w, s, m: QT(w, s) if m else w, params, scales, mask)


def wrap_qt_nojit(params, mask):
    """QT-wrap without precomputed scales (jit scaling / eval)."""
    return jax.tree.map(lambda w, m: QT(w, None) if m else w, params, mask)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def norm_defs(cfg, d: int):
    if cfg.norm == "layernorm":
        return {"scale": PDef((d,), (None,), "ones"),
                "bias": PDef((d,), (None,), "zeros")}
    return {"scale": PDef((d,), (None,), "zeros")}   # rmsnorm (1+scale)


def apply_norm(cfg, p, x):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rmsnorm(x, p["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Rotary / sinusoidal position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, jnp.float32) / dim))


def apply_rope(x, positions, theta: float = 1e4, pct: float = 1.0):
    """x: (..., S, H, Dh); positions: (..., S) int32.  Rotates the first
    ``pct`` fraction of head dims (partial rotary for stablelm)."""
    dh = x.shape[-1]
    rot = int(dh * pct)
    rot -= rot % 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    freqs = rope_frequencies(rot, theta)                      # (rot/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :]                          # (..., S, 1, r/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


def sinusoidal_embedding(positions, d: int):
    """MusicGen-style sinusoidal position embedding: (..., S) -> (..., S, d)."""
    half = d // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# FFN (dense) — SwiGLU / GeGLU / GELU-MLP / squared-ReLU
# ---------------------------------------------------------------------------


def ffn_defs(cfg, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    gated = cfg.act in ("swiglu", "geglu")
    defs = {"w_up": PDef((d, f), ("fsdp", "mlp"), quantized=True),
            "w_down": PDef((f, d), ("mlp", "fsdp"), quantized=True)}
    if gated:
        defs["w_gate"] = PDef((d, f), ("fsdp", "mlp"), quantized=True)
    return defs


@jax.named_scope("mlp")
def apply_ffn(cfg, p, x, qcfg: QuantConfig):
    up = qlinear(x, p["w_up"], qcfg)
    if cfg.act == "swiglu":
        gate = qlinear(x, p["w_gate"], qcfg)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    elif cfg.act == "geglu":
        gate = qlinear(x, p["w_gate"], qcfg)
        h = jax.nn.gelu(gate.astype(jnp.float32)).astype(x.dtype) * up
    elif cfg.act == "relu2":
        h = jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(x.dtype)
    else:  # gelu_mlp
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    h = shard(h, "batch", None, "mlp")
    return qlinear(h, p["w_down"], qcfg)


# ---------------------------------------------------------------------------
# Embedding + LM head
# ---------------------------------------------------------------------------


def embed_defs(cfg):
    defs = {"embedding": PDef((cfg.vocab, cfg.d_model), ("vocab", "fsdp"),
                              "embed")}
    if not cfg.tie_embeddings:
        defs["head"] = PDef((cfg.d_model, cfg.vocab), ("fsdp", "vocab"),
                            quantized=True)
    return defs


@jax.named_scope("embed")
def embed_tokens(cfg, p, tokens):
    emb = p["embedding"]
    emb = emb.w if isinstance(emb, QT) else emb
    x = jnp.take(emb, tokens, axis=0).astype(jnp.bfloat16)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), jnp.bfloat16)
    return shard(x, "batch", "seq", "embed")


def lm_head(cfg, p, x, qcfg: QuantConfig):
    if cfg.tie_embeddings:
        if "head_t" in p:
            # prequantized transposed head (serving): the fp8 payload
            # was cast at build time, so no vocab-sized quantize (or
            # its amax reduction) appears in the decode graph
            logits = qlinear(x, p["head_t"], qcfg)
        else:
            emb = p["embedding"]
            w = (emb.w if isinstance(emb, QT) else emb).T
            logits = qlinear(x, QT(w, None), qcfg)
    else:
        logits = qlinear(x, p["head"], qcfg)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits.astype(jnp.float32) / c) * c
    return shard(logits.astype(jnp.float32), "batch", "seq", "vocab")
