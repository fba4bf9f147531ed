"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
ref.py oracles, with hypothesis shape/dtype sweeps (fixed-grid sweep
when hypothesis is not installed — see tests/_hypo.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.core.quant import (PerTensorQ, quant_mx, quant_per_group,
                              quant_per_tensor)
from repro.kernels import ops, ref
from repro.kernels.group_gemm import group_gemm_pallas
from repro.kernels.mx_gemm import mx_gemm_pallas
from repro.kernels.mx_quant import mx_quant_pallas


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype) * scale


class TestMxQuantKernel:
    @settings(max_examples=8, deadline=None)
    @given(m=st.sampled_from([128, 256]), k=st.sampled_from([512, 1024]),
           fmt=st.sampled_from(["e4m3", "e5m2"]))
    def test_matches_ref(self, m, k, fmt):
        x = _rand(m * 7 + k, (m, k))
        s = ref.global_scale_ref(x, fmt)
        q_p, e_p = mx_quant_pallas(x, s, fmt=fmt, interpret=True,
                                   bm=128, bk=256)
        q_r, e_r = ref.mx_quant_ref(x, s, fmt)
        assert (np.asarray(e_p) == np.asarray(e_r)).all()
        np.testing.assert_array_equal(
            np.asarray(q_p.astype(jnp.float32)),
            np.asarray(q_r.astype(jnp.float32)))

    def test_outlier_tensor(self):
        x = _rand(0, (128, 512))
        x = x.at[3, 100].set(1e4)
        s = ref.global_scale_ref(x)
        q_p, e_p = mx_quant_pallas(x, s, interpret=True)
        q_r, e_r = ref.mx_quant_ref(x, s)
        assert (np.asarray(e_p) == np.asarray(e_r)).all()

    def test_bf16_input(self):
        x = _rand(1, (128, 512), jnp.bfloat16)
        s = ref.global_scale_ref(x)
        q_p, e_p = mx_quant_pallas(x, s, interpret=True)
        q_r, e_r = ref.mx_quant_ref(x, s)
        assert (np.asarray(e_p) == np.asarray(e_r)).all()


class TestMxGemmKernel:
    @settings(max_examples=8, deadline=None)
    @given(m=st.sampled_from([128, 256]), n=st.sampled_from([128, 256]),
           k=st.sampled_from([512, 1024]))
    def test_matches_ref(self, m, n, k):
        x = _rand(m + n, (m, k))
        w = _rand(k, (k, n), scale=0.05)
        xq = quant_mx(x)
        wq = quant_per_tensor(w)
        acc_p = mx_gemm_pallas(xq.q, xq.sexp, wq.q, interpret=True,
                               bm=128, bn=128, bk=256)
        acc_r = ref.mx_gemm_ref(xq.q, xq.sexp, wq.q)
        np.testing.assert_allclose(np.asarray(acc_p), np.asarray(acc_r),
                                   rtol=1e-5, atol=1e-2 * float(
                                       jnp.abs(acc_r).max()) * 1e-3)

    def test_block_shape_sweep(self):
        x = _rand(7, (256, 1024))
        w = _rand(8, (1024, 256), scale=0.05)
        xq, wq = quant_mx(x), quant_per_tensor(w)
        ref_acc = ref.mx_gemm_ref(xq.q, xq.sexp, wq.q)
        for bm, bn, bk in [(128, 128, 512), (256, 128, 1024),
                           (128, 256, 128), (64, 64, 32)]:
            acc = mx_gemm_pallas(xq.q, xq.sexp, wq.q, bm=bm, bn=bn,
                                 bk=bk, interpret=True)
            np.testing.assert_allclose(
                np.asarray(acc), np.asarray(ref_acc), rtol=1e-5,
                atol=abs(float(jnp.abs(ref_acc).max())) * 1e-5)


class TestGroupGemmKernel:
    @settings(max_examples=6, deadline=None)
    @given(m=st.sampled_from([128, 256]), n=st.sampled_from([128]),
           k=st.sampled_from([512, 1024]),
           bk=st.sampled_from([128, 256]))
    def test_matches_ref(self, m, n, k, bk):
        x = _rand(m * 3 + k, (m, k))
        w = _rand(k + 1, (k, n), scale=0.05)
        xq = quant_per_group(x, 128)
        wq = quant_per_tensor(w)
        acc_p = group_gemm_pallas(xq.q, xq.s, wq.q, bk=bk,
                                  interpret=True)
        acc_r = ref.group_gemm_ref(xq.q, xq.s, wq.q)
        np.testing.assert_allclose(
            np.asarray(acc_p), np.asarray(acc_r), rtol=1e-4,
            atol=abs(float(jnp.abs(acc_r).max())) * 1e-5)


def _pallas_call(fn, *args):
    """The one ``pallas_call`` equation in ``fn``'s traced program."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", None)
                if sub is not None:
                    yield from walk(getattr(sub, "jaxpr", sub))

    (eqn,) = walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return eqn


class TestFusedQuantGemmKernel:
    """mx_fused: quantize+GEMM in one kernel == quant_mx ∘ mx_gemm_ref."""

    @settings(max_examples=6, deadline=None)
    @given(m=st.sampled_from([128, 256]), n=st.sampled_from([128, 256]),
           k=st.sampled_from([512, 1024]),
           fmt=st.sampled_from(["e4m3", "e5m2"]))
    def test_matches_quant_then_gemm(self, m, n, k, fmt):
        from repro.kernels.mx_fused import fused_quant_gemm_pallas

        x = _rand(m * 5 + n + k, (m, k))
        w = _rand(k + 9, (k, n), scale=0.05)
        wq = quant_per_tensor(w)
        s = ref.global_scale_ref(x, fmt)
        acc, q, e = fused_quant_gemm_pallas(x, s, wq.q, fmt=fmt,
                                            interpret=True, bk=256)
        q_r, e_r = ref.mx_quant_ref(x, s, fmt)
        assert (np.asarray(e) == np.asarray(e_r)).all()
        np.testing.assert_array_equal(
            np.asarray(q.astype(jnp.float32)),
            np.asarray(q_r.astype(jnp.float32)))
        acc_r = ref.mx_gemm_ref(q_r, e_r, wq.q)
        np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_r),
                                   rtol=1e-5,
                                   atol=float(jnp.abs(acc_r).max()) * 1e-5)

    @pytest.mark.parametrize("fmt", ["e4m3", "e5m2"])
    @pytest.mark.parametrize("blocks", ["parent", "planned"])
    def test_resident_operand_over_n_blocks(self, blocks, fmt):
        """Two row blocks, 3+ N blocks and 3 K blocks: each N block after
        a row's first reads the operand quantized on the first.  q and
        sexp are the reference's bit for bit.  At the 128 x 128 blocks of
        a kernel that quantizes on every step, acc is bitwise that
        kernel's (``mx_gemm_pallas`` on the same q, sexp and blocks); at
        the planned bm 256 / bn 384 it is within the tolerance above."""
        from repro.kernels.dispatch import _fused_blocks
        from repro.kernels.mx_fused import fused_quant_gemm_pallas

        m, n, k = 512, 1152, 768
        if blocks == "parent":
            bm, bn, bk = 128, 128, 256
        else:
            bm, bn, bk, resident = _fused_blocks(m, n, k)
            assert (bm, bn, bk, resident) == (256, 384, 256, True)
        dtype = jnp.bfloat16 if fmt == "e4m3" else jnp.float32
        x = _rand(17, (m, k), dtype)
        wq = quant_per_tensor(_rand(18, (k, n), scale=0.05))
        s = ref.global_scale_ref(x, fmt)
        acc, q, e = fused_quant_gemm_pallas(x, s, wq.q, fmt=fmt, bm=bm,
                                            bn=bn, bk=bk, interpret=True)
        q_r, e_r = ref.mx_quant_ref(x, s, fmt)
        np.testing.assert_array_equal(np.asarray(e), np.asarray(e_r))
        np.testing.assert_array_equal(
            np.asarray(q.astype(jnp.float32)),
            np.asarray(q_r.astype(jnp.float32)))
        if blocks == "parent":
            acc_p = mx_gemm_pallas(q_r, e_r, wq.q, bm=bm, bn=bn, bk=bk,
                                   interpret=True)
            np.testing.assert_array_equal(np.asarray(acc),
                                          np.asarray(acc_p))
        acc_r = ref.mx_gemm_ref(q_r, e_r, wq.q)
        np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_r),
                                   rtol=1e-5,
                                   atol=float(jnp.abs(acc_r).max()) * 1e-5)

    @pytest.mark.parametrize("fmt", ["e4m3", "e5m2"])
    @pytest.mark.parametrize("bm,resident", [(128, True), (256, False)])
    def test_results_independent_of_row_block(self, bm, resident, fmt):
        """The dot runs per 128-row slice, so each output row sums in the
        same order at any bm; and quantizing on every N block (a K too
        wide to keep resident) gives the resident kernel's results.
        acc, q and sexp are bitwise those at bm 256, resident (at bn 128
        the interpreter's dot over a whole 256-row block sums in another
        order than over 128 rows)."""
        from repro.kernels.mx_fused import fused_quant_gemm_pallas

        m, n, k, bn, bk = 512, 384, 768, 128, 256
        dtype = jnp.bfloat16 if fmt == "e4m3" else jnp.float32
        x = _rand(19, (m, k), dtype)
        wq = quant_per_tensor(_rand(20, (k, n), scale=0.05))
        s = ref.global_scale_ref(x, fmt)
        want = fused_quant_gemm_pallas(x, s, wq.q, fmt=fmt, bm=256, bn=bn,
                                       bk=bk, interpret=True)
        got = fused_quant_gemm_pallas(x, s, wq.q, fmt=fmt, bm=bm, bn=bn,
                                      bk=bk, resident=resident,
                                      interpret=True)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                          np.asarray(b.astype(jnp.float32)))

    def test_lhs_blocks_move_once_per_row_tile(self):
        """Walking the grid in order, the x, q and sexp block indices
        change only on the first N block: Pallas fetches each x tile
        once and writes each q / sexp tile once."""
        from repro.kernels.mx_fused import fused_quant_gemm_pallas

        m, n, k, bm, bn, bk = 512, 1152, 768, 256, 384, 256
        eqn = _pallas_call(
            lambda x, s, w: fused_quant_gemm_pallas(
                x, s, w, bm=bm, bn=bn, bk=bk, interpret=True),
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float8_e4m3fn))
        gm = eqn.params["grid_mapping"]
        assert gm.grid == (m // bm, n // bn, k // bk)
        lhs = [gm.block_mappings[i] for i in (0, 4, 5)]     # x, q, sexp
        moves = [0] * len(lhs)
        last = [None] * len(lhs)
        for p in np.ndindex(*gm.grid):
            for t, b in enumerate(lhs):
                idx = tuple(int(v) for v in jax.core.eval_jaxpr(
                    b.index_map_jaxpr.jaxpr, b.index_map_jaxpr.consts, *p))
                moves[t] += idx != last[t]
                last[t] = idx
        assert moves == [(m // bm) * (k // bk)] * 3


def _olmo_fused_gemms():
    """(M, K, N, x dtype, fmt) of every fused GEMM of an olmo-7b step at
    batch 2 x 2048: forward (bf16, e4m3) and input gradient (f32, e5m2)
    of q/k/v/o, gate/up, down and the head."""
    from repro.configs.registry import get_config

    cfg = get_config("olmo-7b")
    d, f, v, m = cfg.d_model, cfg.d_ff, cfg.vocab, 2 * 2048
    shapes = {"qkvo": (d, d), "gate_up": (d, f), "down": (f, d),
              "head": (d, v)}
    out = {}
    for name, (k, n) in shapes.items():
        out[f"{name}_fwd"] = (m, k, n, jnp.bfloat16, "e4m3")
        out[f"{name}_dx"] = (m, n, k, jnp.float32, "e5m2")
    return out


OLMO_FUSED = _olmo_fused_gemms()


class TestFusedBlockPlan:
    """dispatch's block plan for the fused kernel at the olmo-7b shapes:
    each LHS tile is quantized once (the counters' ratio is bn/Np), and
    the kernel's VMEM limit covers its working set."""

    @pytest.mark.parametrize("name", sorted(OLMO_FUSED))
    def test_olmo7b_quantizes_each_lhs_tile_once(self, name):
        from repro.kernels import dispatch as D
        from repro.kernels.mx_fused import vmem_bytes
        from repro.obs.metrics import get_registry

        m, k, n, dtype, fmt = OLMO_FUSED[name]
        reg = get_registry()
        quantized = reg.counter("mx_fused_lhs_tiles_quantized_total")
        tiles = reg.counter("mx_fused_gemm_tiles_total")
        q0, t0 = quantized.value(), tiles.value()
        eqn = _pallas_call(
            lambda x, w, s: D.fused_quant_matmul(
                x, PerTensorQ(w, s), fmt=fmt, backend="interpret"),
            jax.ShapeDtypeStruct((m, k), dtype),
            jax.ShapeDtypeStruct((k, n), jnp.float8_e4m3fn),
            jax.ShapeDtypeStruct((), jnp.float32))
        mp, np_, kp = D._ceil_to(m, 128), D._ceil_to(n, 128), D._k_pad(k)
        bm, bn, bk, resident = D._fused_blocks(mp, np_, kp)
        assert resident
        assert bn == {4096: 512, 11008: 256, 50304: 384}[n]
        assert bm == (128 if name == "head_dx" else 256)
        grid = eqn.params["grid_mapping"].grid
        assert grid == (mp // bm, np_ // bn, kp // bk)
        dq, dt = quantized.value() - q0, tiles.value() - t0
        assert dq == grid[0] * grid[2]
        assert dt == grid[0] * grid[1] * grid[2]
        assert dq / dt == bn / np_
        limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        assert vmem_bytes(bm, bn, bk, kp, jnp.dtype(dtype).itemsize) \
            < limit <= 64 * 2 ** 20

    @pytest.mark.parametrize("k", [131_072, 131_328, 256_000])
    def test_wide_k_keeps_vmem_bounded(self, k):
        """The input gradient of a head with a K-wide vocabulary: the
        operand stays resident up to FUSED_RESIDENT_MAX (K 131072 at bm
        128); past it the kernel quantizes on every N block, so its VMEM
        limit stays bounded at any K (the 256k vocabulary included)."""
        from repro.kernels import dispatch as D
        from repro.kernels.mx_fused import vmem_bytes
        from repro.obs.metrics import get_registry

        m, n = 4096, 4096
        reg = get_registry()
        quantized = reg.counter("mx_fused_lhs_tiles_quantized_total")
        tiles = reg.counter("mx_fused_gemm_tiles_total")
        q0, t0 = quantized.value(), tiles.value()
        eqn = _pallas_call(
            lambda x, w, s: D.fused_quant_matmul(
                x, PerTensorQ(w, s), fmt="e5m2", backend="interpret"),
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float8_e4m3fn),
            jax.ShapeDtypeStruct((), jnp.float32))
        bm, bn, bk, resident = D._fused_blocks(m, n, D._k_pad(k))
        assert (bm, bn) == (128, 512)
        assert resident == (k * bm * 2 <= D.FUSED_RESIDENT_MAX)
        assert resident == (k == 131_072)
        dq, dt = quantized.value() - q0, tiles.value() - t0
        assert dq / dt == (bn / n if resident else 1)
        limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        assert vmem_bytes(bm, bn, bk, D._k_pad(k), 4, resident) \
            < limit <= 48 * 2 ** 20


class TestDwGemmKernel:
    """mx_bwd: fused dequant→transpose→requant_M→GEMM against the
    explicit composition with unit level-1 scale (it cancels)."""

    @settings(max_examples=6, deadline=None)
    @given(m=st.sampled_from([128, 256]), n=st.sampled_from([128]),
           k=st.sampled_from([256, 512]))
    def test_matches_requant_composition(self, m, n, k):
        from repro.core.quant import MxQ, PerTensorQ, mx_gemm
        from repro.kernels.mx_bwd import mx_dw_gemm_pallas

        x = _rand(m + 2 * k, (m, k))
        g = _rand(m + 3 * n, (m, n), scale=0.1)
        xq = quant_mx(x)
        gq = quant_per_tensor(g, "e5m2")
        acc_p = mx_dw_gemm_pallas(xq.q, xq.sexp, gq.q, interpret=True,
                                  bko=128)
        x_unit = MxQ(xq.q, xq.sexp, jnp.float32(1.0)).dequant()
        xt = quant_mx(x_unit.T, 32, "e4m3",
                      global_scale=jnp.float32(1.0))
        acc_r = mx_gemm(xt, PerTensorQ(gq.q, jnp.float32(1.0)),
                        out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(acc_p), np.asarray(acc_r),
                                   rtol=1e-5,
                                   atol=float(jnp.abs(acc_r).max()) * 1e-5)


class TestOpsDispatch:
    def test_end_to_end_linear_close_to_exact(self):
        x = _rand(0, (256, 1024))
        w = _rand(1, (1024, 512), scale=0.03)
        y = ops.moss_linear(x, w, jnp.float32)
        exact = x @ w
        rel = float(jnp.linalg.norm(y - exact) / jnp.linalg.norm(exact))
        assert rel < 0.1

    def test_interpret_equals_ref_mode(self, monkeypatch):
        x = _rand(3, (128, 512))
        w = _rand(4, (512, 128), scale=0.05)
        monkeypatch.setenv("REPRO_KERNELS", "ref")
        y_ref = ops.moss_linear(x, w, jnp.float32)
        monkeypatch.setenv("REPRO_KERNELS", "interpret")
        y_int = ops.moss_linear(x, w, jnp.float32)
        np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)
