"""GPipe-style pipeline parallelism over the ``pod`` axis.

The multi-pod mesh's pod axis defaults to pure data-parallel; this
module provides the alternative mapping: each pod holds a contiguous
slice of layers (a *stage*), microbatches stream through stages with
``jax.lax.ppermute`` moving activations pod-to-pod, and the classic
GPipe schedule (fill, steady state, drain) is expressed as one
``lax.scan`` over ``n_micro + n_stages - 1`` ticks.

Implemented with shard_map over ("pod",): inside, each device executes
its own stage's layer stack (params arrive pod-sharded along the stacked
layer axis).  Forward-only here — the framework's default remains
DP-over-pods for training (DESIGN.md §4); the pipeline path exists for
inference/scale-out experiments and compiles in the multi-pod dry-run
(tests/test_pipeline.py).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(mesh, stage_fn, params_stacked, x_micro,
                     *, n_stages: int):
    """Run ``stage_fn(stage_params, x) -> x`` as a pipeline over pods.

    params_stacked: pytree with leading dim n_stages (stage-major layer
    stacks), sharded P("pod", ...).
    x_micro: (n_micro, mb, ...) microbatched activations, replicated.
    Returns (n_micro, mb, ...) outputs (from the last stage).
    """
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def body(p_stage, xm):
        # inside shard_map: p_stage is THIS pod's stage params (leading
        # stage dim of size 1), xm the full microbatch stream.
        p_stage = jax.tree.map(lambda a: a[0], p_stage)
        stage_id = jax.lax.axis_index("pod")
        buf = jnp.zeros_like(xm[0])
        outs = jnp.zeros_like(xm)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when in range)
            take = jnp.clip(t, 0, n_micro - 1)
            buf = jnp.where(stage_id == 0, xm[take], buf)
            y = stage_fn(p_stage, buf)
            # pass activations to the next stage
            y_next = jax.lax.ppermute(
                y, "pod",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage emits microbatch t-(n_stages-1)
            emit = t - (n_stages - 1)
            emit_ok = (emit >= 0) & (stage_id == n_stages - 1)
            slot = jnp.clip(emit, 0, n_micro - 1)
            outs = jnp.where(
                emit_ok,
                jax.lax.dynamic_update_index_in_dim(
                    outs, y, slot, 0),
                outs)
            return (y_next, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(ticks))
        # only the last stage's outs are real — zero the rest and psum
        # so the result is replicated over pods
        outs = jnp.where(stage_id == n_stages - 1, outs,
                         jnp.zeros_like(outs))
        return jax.lax.psum(outs, "pod")

    spec_p = jax.tree.map(lambda _: P("pod"), params_stacked)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_p, P()), out_specs=P(),
        check_vma=False,
    )(params_stacked, x_micro)
