"""Compile-only guards: every main-path Pallas kernel, at published
widths, through ``repro.kernels.dispatch`` with ``backend="pallas"``,
for a TPU v5e that is described but not attached.

Interpret mode never checks what the chip's compiler checks (block
tiling, lane-dim reshapes, VMEM); these compiles do, at no chip time.
Nothing runs, so they say nothing about results or speed.

The TPU topology is described inside a module-scoped fixture, never
while this module is imported: only one process may hold the TPU
library, and test workers each import every test file.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import MxQ, PerTensorQ
from repro.kernels import dispatch as D

F8, F5, BF16, F32, I8, I32 = (jnp.float8_e4m3fn, jnp.float8_e5m2,
                              jnp.bfloat16, jnp.float32, jnp.int8,
                              jnp.int32)


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 topology, with the persistent compilation
    cache off (a TPU executable written there could not be read back
    here) and bf16 MXU operands forced, as on the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from repro.core import runtime_flags

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_cached = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        runtime_flags.force_bf16_operands(True)
        try:
            yield topo
        finally:
            runtime_flags.force_bf16_operands(False)
            jax.config.update("jax_enable_compilation_cache", was_cached)


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def dp_mesh(topo):
    """The four chips as a (4, 1) data x model mesh."""
    from repro.launch.mesh import mesh_from_devices

    return mesh_from_devices(np.asarray(topo.devices).reshape(4, 1),
                             ("data", "model"))


def _mx_fused_fwd(x, w, s):
    return D.fused_quant_matmul(x, PerTensorQ(w, s), backend="pallas")


def _mx_fused_dx(g, wt, s):
    return D.fused_quant_matmul(g, PerTensorQ(wt, s), fmt="e5m2",
                                backend="pallas")


def _mx_bwd(q, se, g, s):
    return D.mx_matmul_dw(MxQ(q, se, s), PerTensorQ(g, s),
                          backend="pallas")


def _mx_quant(x):
    return D.mx_quantize(x, backend="pallas")


def _mx_gemm(q, se, w, s):
    return D.mx_matmul(MxQ(q, se, s), PerTensorQ(w, s), backend="pallas")


def _moe_gmm(x, sizes, w, ws):
    return D.moe_grouped_matmul(x, sizes, w, ws, capacity=256,
                                backend="pallas")


def _moe_gmm_dw(q, se, g, sizes, s):
    return D.moe_grouped_matmul_dw(MxQ(q, se, s), PerTensorQ(g, s), sizes,
                                   capacity=256, backend="pallas")


def _decode(q, k, v, ks, vs, nv):
    return D.decode_attention(q, k, v, ks, vs, nv, backend="pallas")


def _decode_paged(q, k, v, ks, vs, nv, bt):
    return D.decode_attention_paged(q, k, v, ks, vs, nv, bt,
                                    backend="pallas")


def _decode_args(b, kv, g, c, dh):
    return [((b, kv, g, dh), BF16), ((b, kv, c, dh), F8),
            ((b, kv, c, dh), F8), ((b, kv, c), F32), ((b, kv, c), F32),
            ((b,), I32)]


def _paged_args(b, kv, g, pool, t, pages, dh):
    return [((b, kv, g, dh), BF16), ((pool, kv, t, dh), F8),
            ((pool, kv, t, dh), F8), ((pool, kv, t), F32),
            ((pool, kv, t), F32), ((b,), I32), ((b, pages), I32)]


M, K, N = 4096, 4096, 11008                 # olmo-7b d_model / d_ff
V = 50304                                   # olmo-7b vocab (the head)
V_MAX = 256_000                             # the widest vocab (minitron-8b)
K_RES = 131_072                             # widest K with a resident operand
E, C, F = 16, 256, 6400                     # phi3.5-moe experts / d_ff
CASES = {
    # olmo-7b training GEMMs: forward, dx, dW
    "mx_fused_fwd": (_mx_fused_fwd, [((M, K), BF16), ((K, N), F8),
                                     ((), F32)]),
    "mx_fused_dx": (_mx_fused_dx, [((M, N), F32), ((N, K), F8),
                                   ((), F32)]),
    "mx_bwd": (_mx_bwd, [((M, K), F8), ((M, K // 32), I8), ((M, N), F5),
                         ((), F32)]),
    # the head: an N of 393 x 128 blocks, and the widest resident K
    "mx_fused_fwd_head": (_mx_fused_fwd, [((M, K), BF16), ((K, V), F8),
                                          ((), F32)]),
    "mx_fused_dx_head": (_mx_fused_dx, [((M, V), F32), ((V, K), F8),
                                        ((), F32)]),
    # the widest resident operand (32 MiB at bm 128), and a K past it,
    # where the kernel quantizes on every N block in bounded VMEM
    "mx_fused_dx_resident_max": (_mx_fused_dx, [((M, K_RES), F32),
                                                ((K_RES, K), F8), ((), F32)]),
    "mx_fused_dx_vocab_256k": (_mx_fused_dx, [((M, V_MAX), F32),
                                              ((V_MAX, K), F8), ((), F32)]),
    "mx_quant": (_mx_quant, [((M, K), BF16)]),
    # serving decode GEMM on delayed-scale activations (8 rows)
    "mx_gemm": (_mx_gemm, [((8, K), F8), ((8, K // 32), I8), ((K, N), F8),
                           ((), F32)]),
    "moe_gmm": (_moe_gmm, [((E * C, K), BF16), ((E,), I32),
                           ((E, K, F), F8), ((E,), F32)]),
    "moe_gmm_dw": (_moe_gmm_dw, [((E * C, K), F8), ((E * C, K // 32), I8),
                                 ((E * C, F), F5), ((E,), I32), ((), F32)]),
    # expert parallelism over 4 shards: 4 counted segments per expert
    "moe_gmm_segments": (_moe_gmm, [((E * C, K), BF16), ((E * 4,), I32),
                                    ((E, K, F), F8), ((E,), F32)]),
    "moe_gmm_dw_segments": (_moe_gmm_dw, [((E * C, K), F8),
                                          ((E * C, K // 32), I8),
                                          ((E * C, F), F5), ((E * 4,), I32),
                                          ((), F32)]),
    # one C block; and the single-block ceiling (C = MAX_SINGLE_BLOCK)
    "decode_contiguous": (_decode, _decode_args(8, 8, 4, 1024, 128)),
    "decode_contiguous_ceiling": (_decode, _decode_args(8, 8, 4, 2048, 128)),
    # phi3-mini cache past the ceiling: split-K over MULTI_BLOCK blocks
    "decode_split_k": (_decode, _decode_args(8, 32, 1, 4096, 96)),
    # floating pages: gathered exact path, its ceiling, and split-K
    "decode_paged": (_decode_paged, _paged_args(8, 32, 1, 512, 16, 64, 96)),
    "decode_paged_ceiling": (_decode_paged,
                             _paged_args(8, 8, 4, 1024, 16, 128, 128)),
    "decode_paged_split_k": (_decode_paged,
                             _paged_args(8, 32, 1, 2048, 16, 256, 96)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = CASES[name]
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


# data-parallel training GEMMs: rows over the data axis, weights
# replicated — GSPMD cannot partition a Pallas call, so dispatch must
# run it per shard
DP_CASES = {
    "mx_fused_fwd": (_mx_fused_fwd, [(("batch", None), (4 * M, K), BF16),
                                     ((), (K, N), F8), ((), (), F32)]),
    "mx_bwd": (_mx_bwd, [(("batch", None), (4 * M, K), F8),
                         (("batch", None), (4 * M, K // 32), I8),
                         (("batch", None), (4 * M, N), F5),
                         ((), (), F32)]),
}


@pytest.mark.parametrize("name", sorted(DP_CASES))
def test_kernel_compiles_data_parallel_v5e(dp_mesh, name):
    from repro.distributed.sharding import named_sharding, use_mesh

    fn, args = DP_CASES[name]
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=named_sharding(
        dp_mesh, logical, shape)) for logical, shape, dtype in args]
    with use_mesh(dp_mesh):
        compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_fp8_cast_is_materialized_for_v5e(one_chip):
    """An fp8 cast followed by its upcast (the gradient compression's
    quantize -> error-feedback residual): inside one fusion XLA:TPU
    would keep f32 and skip the E5M2 rounding, so ``cast_fp8`` must
    leave the payload as a fusion's fp8 output."""
    from repro.core.quant import quant_per_tensor

    def residual(g):
        return g - quant_per_tensor(g, "e5m2").dequant()

    g = jax.ShapeDtypeStruct((M, K), F32, sharding=one_chip)
    hlo = jax.jit(residual).lower(g).compile().as_text()
    assert re.search(r"= f8e5m2\[\d+,\d+\]\S* fusion\(", hlo), hlo
