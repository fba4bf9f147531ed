"""Weights and keys made from ``--seed``, in the benchmark's own layout.

Every leaf of a decoder is named here (``LEAVES``) and drawn from its
own key: ``fold_in(fold_in(seed key, leaf id), layer)``.  So one leaf of
one layer can be made again alone, bit for bit, long after the program
that was handed the whole tree has been freed: the reference rebuilds
the model layer by layer from the seed and takes nothing the program
made.

Values are uniform on [-a, a) with ``a = std * sqrt(3)``, built from the
random bits by exact operations (a power-of-two affine map, then one
multiply), so a leaf made inside a large jitted program equals the same
leaf made alone.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# name -> (kind, per layer); the id of a leaf is its index here, so add
# new names only at the end.
LEAVES = ("embed", "head", "final_norm.gamma", "final_norm.beta",
          "ln1.gamma", "ln1.beta", "wq", "wk", "wv", "wo",
          "ln2.gamma", "ln2.beta", "w_gate", "w_up", "w_down")
LAYER_LEAVES = LEAVES[4:]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def sub_key(key: jax.Array, *path) -> jax.Array:
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def leaf_names(conf: dict) -> list[str]:
    """Global and per-layer leaf names this configuration has."""
    ln = conf["norm"] == "layernorm"
    names = [n for n in LEAVES[:4] if ln or not n.endswith(".beta")]
    return names


def layer_leaf_names(conf: dict) -> list[str]:
    ln = conf["norm"] == "layernorm"
    return [n for n in LAYER_LEAVES if ln or not n.endswith(".beta")]


def leaf_shape(conf: dict, name: str) -> tuple[int, ...]:
    d, h, kv = (conf["hidden_size"], conf["num_attention_heads"],
                conf["num_key_value_heads"])
    dh, f, v = conf["head_dim"], conf["intermediate_size"], conf["vocab_size"]
    return {"embed": (v, d), "head": (d, v), "wq": (d, h, dh),
            "wk": (d, kv, dh), "wv": (d, kv, dh), "wo": (h, dh, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)
            }.get(name, (d,))


def _std(conf: dict, name: str) -> float:
    """Standard deviation of a weight: 1/sqrt(fan-in) for projections,
    ``embed_std`` for the token embedding; norms are exact (1 and 0)."""
    if name == "embed":
        return conf["init"]["embed_std"]
    shape = leaf_shape(conf, name)
    fan_in = {"wo": shape[0] * shape[1]}.get(name, shape[0])
    return 1.0 / math.sqrt(fan_in)


def make_leaf(conf: dict, key: jax.Array, name: str, layer=0,
              dtype=jnp.float32) -> jax.Array:
    """One leaf (of one layer) from the seed's key, traceable (``key``
    and ``layer`` may be traced, so one compiled program serves every
    seed); f32 values cast to ``dtype``."""
    shape = leaf_shape(conf, name)
    if name.endswith(".gamma"):
        return jnp.ones(shape, dtype)
    if name.endswith(".beta"):
        return jnp.zeros(shape, dtype)
    bits = jax.random.bits(sub_key(key, LEAVES.index(name), layer), shape,
                           jnp.uint32)
    one_two = jax.lax.bitcast_convert_type(
        (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32)   # [1, 2)
    sym = (one_two - 1.0) * 2.0 - 1.0                         # [-1, 1)
    a = _std(conf, name) * math.sqrt(3.0)
    return (sym * jnp.float32(a)).astype(dtype)


def make_all(conf: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """Every leaf, layers stacked on a leading axis (traceable).  The
    stack is drawn per layer under ``vmap``, so layer ``i`` of it equals
    ``make_leaf(conf, key, name, i)``."""
    out = {n: make_leaf(conf, key, n, 0, dtype) for n in leaf_names(conf)}
    layers = jnp.arange(conf["num_hidden_layers"], dtype=jnp.int32)
    out["layers"] = {
        n: jax.vmap(lambda i, n=n: make_leaf(conf, key, n, i, dtype))(layers)
        for n in layer_leaf_names(conf)}
    return out


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
        out = {n: self(key, n) for n in leaf_names(self.conf)}
        out["layers"] = [{n: self(key, n, i)
                          for n in layer_leaf_names(self.conf)}
                         for i in range(self.conf["num_hidden_layers"])]
        return out
