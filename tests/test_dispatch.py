"""Kernel-dispatch parity: the full qmm custom-VJP (forward, dx, dW)
under ``REPRO_KERNELS=interpret`` (Pallas kernels via the interpreter)
must match the pure-jnp reference path to fp8-noise tolerance for every
quantized mode.  This is the test that proves the training hot path
actually exercises the kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import (
    MOSS_CONFIG,
    PER_GROUP_CONFIG,
    PER_TENSOR_CONFIG,
)
from repro.core.linear import qmm
from repro.core.quant import (
    MxQ,
    PerTensorQ,
    quant_mx,
    quant_per_tensor,
)
from repro.kernels import dispatch

MODES = {
    "moss": MOSS_CONFIG,
    "per_group": PER_GROUP_CONFIG,
    "per_tensor": PER_TENSOR_CONFIG,
}


def _problem(m=128, k=512, n=256):
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    # sparse outliers: the regime that separates the schemes
    x = x * (1 + 100.0 * jax.random.bernoulli(jax.random.PRNGKey(1),
                                              0.002, x.shape))
    w = jax.random.normal(jax.random.PRNGKey(2), (k, n),
                          jnp.float32) * 0.05
    return x, w


def _fwd_bwd(cfg, x, w, backend, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", backend)

    def loss(x, w):
        s = jnp.max(jnp.abs(w)) / 448.0
        return jnp.sum(qmm(cfg, x, w, s) ** 2)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
    return float(val), grads


@pytest.mark.parametrize("mode", list(MODES))
def test_qmm_interpret_matches_ref(mode, monkeypatch):
    cfg = MODES[mode]
    x, w = _problem()
    v_ref, (gx_ref, gw_ref) = _fwd_bwd(cfg, x, w, "ref", monkeypatch)
    v_int, (gx_int, gw_int) = _fwd_bwd(cfg, x, w, "interpret", monkeypatch)
    assert abs(v_int - v_ref) <= 1e-4 * abs(v_ref)
    for g_i, g_r in ((gx_int, gx_ref), (gw_int, gw_ref)):
        rel = float(jnp.linalg.norm(g_i - g_r)
                    / (jnp.linalg.norm(g_r) + 1e-9))
        assert rel < 1e-4, (mode, rel)


@pytest.mark.parametrize("mode", list(MODES))
def test_qmm_interpret_matches_ref_ragged_shapes(mode, monkeypatch):
    """Non-block-aligned M/N/K exercise the dispatch padding layer."""
    cfg = MODES[mode]
    x, w = _problem(m=96, k=384, n=160)
    v_ref, (gx_ref, gw_ref) = _fwd_bwd(cfg, x, w, "ref", monkeypatch)
    v_int, (gx_int, gw_int) = _fwd_bwd(cfg, x, w, "interpret", monkeypatch)
    assert abs(v_int - v_ref) <= 1e-4 * abs(v_ref)
    for g_i, g_r in ((gx_int, gx_ref), (gw_int, gw_ref)):
        rel = float(jnp.linalg.norm(g_i - g_r)
                    / (jnp.linalg.norm(g_r) + 1e-9))
        assert rel < 1e-4, (mode, rel)


def test_fused_quant_matmul_residual_matches_quant_mx(monkeypatch):
    """The fused kernel's emitted residual must equal a standalone
    two-level quantization (same global scale, exponents, payload)."""
    x, w = _problem()
    wq = quant_per_tensor(w)
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    y, xq = dispatch.fused_quant_matmul(x, wq, out_dtype=jnp.float32)
    q_ref = quant_mx(x)
    assert float(xq.s) == float(q_ref.s)
    assert (np.asarray(xq.sexp) == np.asarray(q_ref.sexp)).all()
    np.testing.assert_array_equal(
        np.asarray(xq.q.astype(jnp.float32)),
        np.asarray(q_ref.q.astype(jnp.float32)))
    # and the GEMM itself matches the reference composition
    from repro.core.quant import mx_gemm
    y_ref = mx_gemm(q_ref, wq, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-3)


def test_dw_kernel_matches_ref_composition(monkeypatch):
    """mx_matmul_dw (fused dequant→transpose→requant_M→GEMM) against
    the explicit reference composition with level-1 scale s_x."""
    x, _ = _problem(m=128, k=256)
    g = jax.random.normal(jax.random.PRNGKey(3), (128, 192), jnp.float32)
    xq = quant_mx(x)
    gq = quant_per_tensor(g, "e5m2")
    dw_ref = dispatch.mx_matmul_dw(xq, gq, backend="ref")
    dw_int = dispatch.mx_matmul_dw(xq, gq, backend="interpret")
    rel = float(jnp.linalg.norm(dw_int - dw_ref)
                / (jnp.linalg.norm(dw_ref) + 1e-9))
    assert rel < 1e-5, rel


def test_backend_env_is_respected_per_call(monkeypatch):
    """Flipping REPRO_KERNELS between calls must not be shadowed by a
    stale jit cache (regression for the old jit-wrapped ops)."""
    x, w = _problem(m=64, k=128, n=64)
    wq = quant_per_tensor(w)
    xq = quant_mx(x)
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    y_ref = dispatch.mx_matmul(xq, wq, out_dtype=jnp.float32)
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    y_int = dispatch.mx_matmul(xq, wq, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("entry", ["quantize", "fused", "matmul", "dw"])
def test_kernel_backend_refuses_other_micro_group(entry):
    """An ablation geometry (micro-group 16) has no kernel: under a
    kernel backend the call raises instead of silently running the
    reference; ``backend="ref"`` takes it."""
    x, w = _problem(m=64, k=128, n=64)
    wq = quant_per_tensor(w)
    xq = quant_mx(x, 16)
    calls = {
        "quantize": lambda b: dispatch.mx_quantize(x, micro_group=16,
                                                   backend=b),
        "fused": lambda b: dispatch.fused_quant_matmul(
            x, wq, micro_group=16, backend=b),
        "matmul": lambda b: dispatch.mx_matmul(xq, wq, backend=b),
        "dw": lambda b: dispatch.mx_matmul_dw(
            xq, quant_per_tensor(x, "e5m2"), backend=b),
    }
    calls[entry]("ref")
    with pytest.raises(ValueError, match="backend='ref'"):
        calls[entry]("interpret")


def test_unknown_backend_rejected(monkeypatch):
    from repro.core.runtime_flags import kernel_backend

    monkeypatch.setenv("REPRO_KERNELS", "cuda")
    with pytest.raises(ValueError):
        kernel_backend()


@pytest.mark.parametrize("mode", list(MODES))
def test_train_step_runs_under_interpret(mode, monkeypatch):
    """One real train step with the kernel path active end-to-end."""
    from repro.configs.registry import get_config
    from repro.train.steps import (TrainHParams, init_train_state,
                                   make_train_step)

    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    cfg = get_config("olmo-7b", smoke=True)
    from repro.launch.train import quant_from_name
    cfg = cfg.replace(quant=quant_from_name(mode))
    hp = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=4)
    state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, hp))
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32),
             "labels": jnp.zeros((2, 64), jnp.int32)}
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))


def test_qt_carries_mxq_semantics():
    """Doc-pin: fused path residual really is the 1.8× saving carrier —
    fp8 payload + int8 exponents, no bf16 activation retained."""
    x, w = _problem(m=64, k=128, n=64)
    wq = quant_per_tensor(w)
    _, xq = dispatch.fused_quant_matmul(x, wq, backend="ref")
    assert isinstance(xq, MxQ)
    assert xq.q.dtype == jnp.float8_e4m3fn
    assert xq.sexp.dtype == jnp.int8
    assert isinstance(wq, PerTensorQ)
