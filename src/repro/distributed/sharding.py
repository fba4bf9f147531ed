"""Logical-axis sharding rules (MaxText-style).

Model code annotates tensors with *logical* axis names; the active rule
set maps them to physical mesh axes.  Rules drop axes that don't divide
evenly (e.g. musicgen's 24 heads on a 16-way model axis) instead of
failing, so one model definition serves every mesh.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# logical name -> tuple of candidate mesh axes (joined as a tuple spec
# entry).  "batch" spans pod+data so the pod axis is pure DP.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),                  # replicated by default
    "kv_seq": ("model",),       # decode KV caches shard their seq dim
    "embed": (),                # activation d_model: replicated
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_tokens": ("pod", "data"),
    "fsdp": ("data",),          # weight dim sharded for ZeRO-3
    "lru": ("model",),
    "conv": (),
    "latent": (),               # MLA kv_lora dim
    "layers": (),               # stacked-layer leading axis
    "tokens_ep": ("pod", "data", "model"),  # MoE token parallelism
}

_state = threading.local()


def _rules() -> dict[str, tuple[str, ...]]:
    return getattr(_state, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def sharding_rules(overrides: dict[str, tuple[str, ...]] | None = None):
    old = _rules()
    merged = dict(old)
    if overrides:
        merged.update(overrides)
    _state.rules = merged
    try:
        yield
    finally:
        _state.rules = old


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate a mesh for logical-axis sharding AND as the jax mesh
    context (collectives, shard_map).  The framework's single entry
    point for mesh scoping."""
    old = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _state.mesh = old


def active_mesh() -> Mesh | None:
    mesh = getattr(_state, "mesh", None)
    if mesh is not None:
        return mesh
    am = jax.sharding.get_abstract_mesh()       # a jax.set_mesh scope
    return None if am.empty else am


def resolve_spec(logical: tuple[str | None, ...],
                 mesh: Mesh,
                 dims: tuple[int, ...] | None = None) -> P:
    """Map logical names to a PartitionSpec, dropping axes that are
    missing from the mesh or that don't divide the dim size."""
    rules = _rules()
    entries = []
    used: set[str] = set()
    for i, name in enumerate(logical):
        if name is None:
            entries.append(None)
            continue
        axes = []
        shards = 1
        for ax in rules.get(name, ()):
            if ax not in mesh.axis_names or ax in used:
                continue
            # greedy: take each axis only while divisibility holds
            if dims is not None and dims[i] % (shards
                                               * mesh.shape[ax]) != 0:
                continue
            axes.append(ax)
            shards *= mesh.shape[ax]
        if not axes:
            entries.append(None)
            continue
        for ax in axes:
            used.add(ax)
        entries.append(tuple(axes) if len(axes) > 1 else axes[0])
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """with_sharding_constraint by logical names; no-op outside a mesh."""
    mesh = active_mesh()
    if mesh is None:
        return x
    spec = resolve_spec(tuple(logical), mesh, tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, spec)


def named_sharding(mesh: Mesh, logical: tuple[str | None, ...],
                   dims: tuple[int, ...] | None = None) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(logical, mesh, dims))
