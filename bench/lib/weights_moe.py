"""Weights made from ``--seed`` for a latent-attention MoE decoder
(DeepSeek-V2 configurations), in the benchmark's own layout, by the
per-leaf scheme of ``bench.lib.weights``: leaf ``name`` of layer ``i``
is drawn from ``fold_in(fold_in(seed key, LEAVES.index(name)), i)``,
uniform on [-a, a) with ``a = std * sqrt(3)`` from exact operations on
the random bits, so one leaf of one layer can be made again alone, bit
for bit, long after the program that was handed the whole tree is gone.

Layers ``[0, first_k_dense_replace)`` have a dense FFN, the rest
experts.  Norms are RMSNorm gains (exactly 1).  Expert leaves are
stacked over the experts this chip holds (``n_routed_experts``); the
router scores all ``n_routed_experts_published``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import sub_key

# the id of a leaf is its index here, so add new names only at the end
LEAVES = ("embed", "head", "final_norm.gamma",
          "ln1.gamma", "wq", "w_dkv", "w_kr", "kv_norm.gamma", "w_uk",
          "w_uv", "wo", "ln2.gamma",
          "w_gate", "w_up", "w_down",
          "router", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
GLOBAL = LEAVES[:3]
ATTN = LEAVES[3:12]
DENSE_FFN = LEAVES[12:15]
MOE = LEAVES[15:]


def is_dense(conf: dict, layer: int) -> bool:
    return layer < conf["first_k_dense_replace"]


def layer_leaf_names(conf: dict, layer: int) -> tuple[str, ...]:
    return ATTN + (DENSE_FFN if is_dense(conf, layer) else MOE)


def flat_names(conf: dict) -> list[str]:
    """Every leaf, per-layer ones as ``layers.<i>.<name>``."""
    names = list(GLOBAL)
    for i in range(conf["num_hidden_layers"]):
        names += [f"layers.{i}.{n}" for n in layer_leaf_names(conf, i)]
    return names


def leaf_shape(conf: dict, name: str) -> tuple[int, ...]:
    d, h, v = (conf["hidden_size"], conf["num_attention_heads"],
               conf["vocab_size"])
    lora, nope, rope = (conf["kv_lora_rank"], conf["qk_nope_head_dim"],
                        conf["qk_rope_head_dim"])
    dv, f, ff = (conf["v_head_dim"], conf["moe_intermediate_size"],
                 conf["intermediate_size"])
    held, shared = conf["n_routed_experts"], conf["n_shared_experts"] * f
    return {
        "embed": (v, d), "head": (d, v), "kv_norm.gamma": (lora,),
        "wq": (d, h, nope + rope), "w_dkv": (d, lora), "w_kr": (d, rope),
        "w_uk": (lora, h, nope), "w_uv": (lora, h, dv), "wo": (h, dv, d),
        "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d),
        "router": (d, conf["n_routed_experts_published"]),
        "e_gate": (held, d, f), "e_up": (held, d, f), "e_down": (held, f, d),
        "s_gate": (d, shared), "s_up": (d, shared), "s_down": (shared, d),
    }.get(name, (d,))


def _std(conf: dict, name: str) -> float:
    """1/sqrt(fan-in) for projections (over one expert's input for the
    stacks), ``embed_std`` for the token embedding."""
    if name == "embed":
        return conf["init"]["embed_std"]
    shape = leaf_shape(conf, name)
    if name == "wo":
        fan_in = shape[0] * shape[1]
    elif name.startswith("e_"):
        fan_in = shape[1]
    else:
        fan_in = shape[0]
    return 1.0 / math.sqrt(fan_in)


def make_leaf(conf: dict, key: jax.Array, name: str, layer=0,
              dtype=jnp.float32) -> jax.Array:
    """One leaf (of one layer) from the seed's key; ``key`` and ``layer``
    may be traced."""
    shape = leaf_shape(conf, name)
    if name.endswith(".gamma"):
        return jnp.ones(shape, dtype)
    bits = jax.random.bits(sub_key(key, LEAVES.index(name), layer), shape,
                           jnp.uint32)
    one_two = jax.lax.bitcast_convert_type(
        (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32)   # [1, 2)
    sym = (one_two - 1.0) * 2.0 - 1.0                         # [-1, 1)
    a = _std(conf, name) * math.sqrt(3.0)
    return (sym * jnp.float32(a)).astype(dtype)


def make_stack(conf: dict, key, name: str, layers: range,
               dtype=jnp.float32) -> jax.Array:
    """Leaf ``name`` of each of ``layers``, stacked (traceable); entry j
    equals ``make_leaf(conf, key, name, layers[j])``."""
    idx = jnp.arange(layers.start, layers.stop, dtype=jnp.int32)
    return jax.vmap(lambda i: make_leaf(conf, key, name, i, dtype))(idx)


class LeafMaker:
    """Jitted makers of single leaves from the seed's key (one compile
    per leaf name, shared by every seed and layer)."""

    def __init__(self, conf: dict, dtype=jnp.float32):
        self.conf = conf
        self.dtype = dtype
        self._fns = {}

    def __call__(self, key, name: str, layer: int = 0):
        if name not in self._fns:
            self._fns[name] = jax.jit(lambda k, i, name=name: make_leaf(
                self.conf, k, name, i, self.dtype))
        return self._fns[name](key, jnp.int32(layer))

    def tree(self, key) -> dict:
        """Every leaf, layers as a list of dicts."""
        out = {n: self(key, n) for n in GLOBAL}
        out["layers"] = [{n: self(key, n, i)
                          for n in layer_leaf_names(self.conf, i)}
                         for i in range(self.conf["num_hidden_layers"])]
        return out
