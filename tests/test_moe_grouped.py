"""Grouped-expert MOSS GEMM (the MoE hot path): ref-vs-interpret kernel
parity on ragged group sizes (including a zero-size expert and a
full-capacity expert), grad-checks of ``qmm_grouped`` against the
per-expert vmapped path, and the MoE train step with the grouped
kernels active end-to-end under ``REPRO_KERNELS=interpret``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant as Q
from repro.core.formats import BF16_CONFIG, MOSS_CONFIG
from repro.core.linear import QT, qlinear, qmm_grouped
from repro.core.quant import quant_per_tensor
from repro.kernels import dispatch

E, C, D, F = 4, 32, 64, 48
# ragged: one full-capacity expert, one empty expert, two partial
SIZES = jnp.array([C, 0, 5, 19], jnp.int32)


def _buffer(key=0, sizes=SIZES, d=D):
    """A dispatch-shaped (E·C, d) buffer: rows past each expert's valid
    count are zero, and every token carries a ±3.0 entry so all level-1
    amaxes coincide (see test_grouped_matches_vmapped_bitexact)."""
    x = jax.random.normal(jax.random.PRNGKey(key), (E * C, d), jnp.float32)
    x = jnp.clip(x, -2.5, 2.5).at[:, 0].set(3.0)
    pos = jnp.arange(E * C) % C
    valid = pos < sizes[jnp.arange(E * C) // C]
    return jnp.where(valid[:, None], x, 0.0)


def _weights(key=1, d=D, f=F):
    return jax.random.normal(jax.random.PRNGKey(key), (E, d, f),
                             jnp.float32) * 0.05


def _fwd_bwd(x, w, backend, monkeypatch, sizes=SIZES):
    monkeypatch.setenv("REPRO_KERNELS", backend)

    def loss(x, w):
        ws = jnp.max(jnp.abs(w), axis=(1, 2)) / 448.0
        y = qmm_grouped(MOSS_CONFIG, C, x, w, ws, sizes)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
    return float(val), grads


def test_grouped_interpret_matches_ref(monkeypatch):
    """fwd, dx and dW of the grouped custom-VJP: the Pallas kernels
    (interpreted) against the jnp reference, on ragged sizes with an
    empty and a full-capacity expert."""
    x, w = _buffer(), _weights()
    v_ref, (gx_ref, gw_ref) = _fwd_bwd(x, w, "ref", monkeypatch)
    v_int, (gx_int, gw_int) = _fwd_bwd(x, w, "interpret", monkeypatch)
    assert abs(v_int - v_ref) <= 1e-5 * abs(v_ref)
    for g_i, g_r in ((gx_int, gx_ref), (gw_int, gw_ref)):
        rel = float(jnp.linalg.norm(g_i - g_r)
                    / (jnp.linalg.norm(g_r) + 1e-9))
        assert rel < 1e-5, rel


def test_grouped_interpret_matches_ref_unaligned_capacity(monkeypatch):
    """C=24 (not a micro-group multiple) exercises the per-expert row
    padding of the grouped dW dispatch; K=80 exercises K padding."""
    cap, d = 24, 80
    sizes = jnp.array([cap, 0, 3, 11], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(3), (E * cap, d), jnp.float32)
    pos = jnp.arange(E * cap) % cap
    x = jnp.where((pos < sizes[jnp.arange(E * cap) // cap])[:, None], x, 0.0)
    w = jax.random.normal(jax.random.PRNGKey(4), (E, d, F), jnp.float32)

    def run(backend):
        monkeypatch.setenv("REPRO_KERNELS", backend)

        def loss(x, w):
            ws = jnp.max(jnp.abs(w), axis=(1, 2)) / 448.0
            y = qmm_grouped(MOSS_CONFIG, cap, x, w, ws, sizes)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1))(x, w)

    v_r, g_r = run("ref")
    v_i, g_i = run("interpret")
    assert abs(float(v_i) - float(v_r)) <= 1e-5 * abs(float(v_r))
    for a, b in zip(g_i, g_r):
        rel = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-9))
        assert rel < 1e-5, rel


# the expert-parallel layout: each expert's C = 64 rows arrive as S = 2
# segments of 32, one from each source shard, each with its own count
SEG_C, SEG_SIZES = 64, jnp.array([32, 3, 0, 0, 17, 32, 5, 0], jnp.int32)


def _segment_buffer(key=5):
    x = jax.random.normal(jax.random.PRNGKey(key), (E * SEG_C, D),
                          jnp.float32)
    row = jnp.arange(E * SEG_C)
    valid = row % 32 < SEG_SIZES[row // 32]
    return x, jnp.where(valid[:, None], x, 0.0)


def test_grouped_segments_match_ref(monkeypatch):
    """With one count per (expert, segment), as the expert-parallel
    all_to_all delivers them, the interpreted kernels' fwd, dx and dW
    equal the jnp reference, which computes every row."""
    _, x = _segment_buffer()
    w = _weights()

    def run(backend):
        monkeypatch.setenv("REPRO_KERNELS", backend)

        def loss(x, w):
            ws = jnp.max(jnp.abs(w), axis=(1, 2)) / 448.0
            y = qmm_grouped(MOSS_CONFIG, SEG_C, x, w, ws, SEG_SIZES)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1))(x, w)

    v_r, g_r = run("ref")
    v_i, g_i = run("interpret")
    assert abs(float(v_i) - float(v_r)) <= 1e-5 * abs(float(v_r))
    for a, b in zip(g_i, g_r):
        rel = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-9))
        assert rel < 1e-5, rel


def test_grouped_kernel_skips_each_empty_segment(monkeypatch):
    """The skip reads each row block's own segment count: rows of a
    segment whose count is 0 are never multiplied (they come out 0 even
    where the buffer holds data), and every other row is the
    reference's."""
    full, _ = _segment_buffer()
    wq = jax.vmap(lambda wi: quant_per_tensor(wi, "e4m3"))(_weights())
    y = {}
    for backend in ("ref", "interpret"):
        monkeypatch.setenv("REPRO_KERNELS", backend)
        y[backend], _ = dispatch.moe_grouped_matmul(
            full, SEG_SIZES, wq.q, wq.s, capacity=SEG_C,
            out_dtype=jnp.float32)
    empty = np.repeat(np.asarray(SEG_SIZES) == 0, 32)
    got, want = np.asarray(y["interpret"]), np.asarray(y["ref"])
    assert (got[empty] == 0).all() and (want[empty] != 0).any()
    np.testing.assert_allclose(got[~empty], want[~empty], rtol=1e-5,
                               atol=1e-5)


def test_grouped_residual_matches_quant_mx(monkeypatch):
    """The grouped kernel's emitted residual must equal a standalone
    two-level quantization of the whole token buffer (one level-1
    scale, per-micro-group exponents)."""
    x, w = _buffer(), _weights()
    wq = jax.vmap(lambda wi: quant_per_tensor(wi, "e4m3"))(w)
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    _, xq = dispatch.moe_grouped_matmul(x, SIZES, wq.q, wq.s, capacity=C)
    q_ref = Q.quant_mx(x)
    assert float(xq.s) == float(q_ref.s)
    assert (np.asarray(xq.sexp) == np.asarray(q_ref.sexp)).all()
    np.testing.assert_array_equal(
        np.asarray(xq.q.astype(jnp.float32)),
        np.asarray(q_ref.q.astype(jnp.float32)))


def test_grouped_fwd_bitexact_vs_per_expert_shared_scale(monkeypatch):
    """With the level-1 scale shared, the grouped forward must be
    BITWISE identical to E independent per-expert MX GEMMs — the
    grouped kernel changes the launch structure, not the math."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    x, w = _buffer(), _weights()
    s = jnp.max(jnp.abs(x)) / 448.0
    ws = jnp.max(jnp.abs(w), axis=(1, 2)) / 448.0
    y_grp = qmm_grouped(MOSS_CONFIG, C, x, w, ws, SIZES)
    for e in range(E):
        xq = Q.quant_mx(x[e * C:(e + 1) * C], 32, "e4m3", global_scale=s)
        wq = quant_per_tensor(w[e], "e4m3", scale=ws[e])
        y_e = Q.mx_gemm(xq, wq, out_dtype=jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(y_grp[e * C:(e + 1) * C].astype(jnp.float32)),
            np.asarray(y_e))


def test_grouped_bf16_bitexact_vs_vmapped():
    """bf16 mode: grouped and vmapped are the same dots over the same
    rows — bitwise equal."""
    x, w = _buffer(), _weights()
    y_grp = qmm_grouped(BF16_CONFIG, C, x, w, jnp.zeros((E,), jnp.float32),
                        SIZES)
    y_vm = jax.vmap(lambda xe, we: qlinear(xe, QT(we, None), BF16_CONFIG))(
        x.reshape(E, C, D), w)
    np.testing.assert_array_equal(np.asarray(y_grp.reshape(E, C, F)),
                                  np.asarray(y_vm))


def test_qmm_grouped_grads_match_vmapped_qlinear(monkeypatch):
    """Grad-check against the vmapped path: with every expert's buffer
    carrying the same amax (so per-expert and buffer-global level-1
    scales coincide — see _buffer), moss grouped == vmapped down to
    quantization bit level; compare loss and both grads."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    x, w = _buffer(), _weights()
    ws = jnp.max(jnp.abs(w), axis=(1, 2)) / 448.0

    def loss_grouped(x, w):
        y = qmm_grouped(MOSS_CONFIG, C, x, w, ws, SIZES)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def loss_vmapped(x, w):
        y = jax.vmap(lambda xe, we, se: qlinear(xe, QT(we, se),
                                                MOSS_CONFIG))(
            x.reshape(E, C, D), w, ws)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    v_g, g_g = jax.value_and_grad(loss_grouped, argnums=(0, 1))(x, w)
    v_v, g_v = jax.value_and_grad(loss_vmapped, argnums=(0, 1))(x, w)
    assert abs(float(v_g) - float(v_v)) <= 1e-6 * abs(float(v_v))
    # backward quantizes the GRADIENT buffer with one level-1 scale
    # (grouped) vs E per-expert scales (vmapped) — the two-level scheme
    # bounds the difference to fp8 noise (effective micro-group scales
    # agree within one power-of-two bucket)
    rel_dx = float(jnp.linalg.norm(g_g[0] - g_v[0])
                   / (jnp.linalg.norm(g_v[0]) + 1e-9))
    rel_dw = float(jnp.linalg.norm(g_g[1] - g_v[1])
                   / (jnp.linalg.norm(g_v[1]) + 1e-9))
    assert rel_dx < 0.05, rel_dx
    assert rel_dw < 0.05, rel_dw


def _moe_block_ab(monkeypatch, quant):
    """Run the same MoE block through the grouped path and the vmapped
    fallback — identical sort-based dispatch, capacity truncation and
    combine; only the expert-GEMM execution differs."""
    from repro.configs.registry import get_config
    from repro.models import moe
    from repro.models.layers import (init_tree, quant_mask_tree,
                                     wrap_qt_nojit)

    monkeypatch.setenv("REPRO_KERNELS", "ref")
    # moe_decode_dense=False so the small-T train path really runs the
    # sort-based dispatch + expert GEMMs (not the dense decode combine)
    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True).replace(
        moe_decode_dense=False)
    cfg = cfg.replace(quant=quant)
    defs = moe.moe_defs(cfg)
    params = init_tree(defs, jax.random.PRNGKey(0))
    qp = wrap_qt_nojit(params, quant_mask_tree(defs))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model),
                          jnp.bfloat16)

    def block(path):
        monkeypatch.setenv("REPRO_MOE_EXPERTS", path)
        return moe.moe_block(cfg, qp, x, cfg.quant, mode="train")

    (y_g, aux_g), (y_v, aux_v) = block("grouped"), block("vmapped")
    assert float(aux_g["balance"]) == float(aux_v["balance"])
    return y_g.astype(jnp.float32), y_v.astype(jnp.float32)


def test_moe_block_grouped_bitexact_vs_vmapped_bf16(monkeypatch):
    """In bf16 mode the grouped path runs the same dots over the same
    rows as the vmapped experts — the block outputs must be BITWISE
    identical (pins dispatch, truncation and combine equivalence)."""
    y_g, y_v = _moe_block_ab(monkeypatch, BF16_CONFIG)
    np.testing.assert_array_equal(np.asarray(y_g), np.asarray(y_v))


def test_moe_block_grouped_matches_vmapped_moss(monkeypatch):
    """moss mode: grouped quantizes each buffer with ONE level-1 scale
    where vmapped uses E per-expert scales; the two-level scheme keeps
    every effective micro-group scale within the same power-of-two
    bucket of its fine scale, so the block outputs agree to fp8 noise
    (a routing/truncation bug would show up as O(1) error)."""
    y_g, y_v = _moe_block_ab(monkeypatch, MOSS_CONFIG)
    rel = float(jnp.linalg.norm(y_g - y_v) / (jnp.linalg.norm(y_v) + 1e-9))
    # ~4% observed: two independent e4m3 quantizations of the same
    # values through three chained GEMMs; routing errors would be O(1)
    assert rel < 0.08, rel


@pytest.mark.parametrize("arch", ["phi3.5-moe-42b-a6.6b",
                                  "deepseek-v2-lite-16b"])
def test_moe_train_step_under_interpret(arch, monkeypatch):
    """One real MoE train step with the grouped Pallas kernels active
    (interpreted) end-to-end."""
    from repro.configs.registry import get_config
    from repro.train.steps import (TrainHParams, init_train_state,
                                   make_train_step)

    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.setenv("REPRO_MOE_EXPERTS", "grouped")
    cfg = get_config(arch, smoke=True).replace(moe_decode_dense=False)
    hp = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=4)
    state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, hp))
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32),
             "labels": jnp.zeros((2, 64), jnp.int32)}
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))


def test_moe_expert_path_env(monkeypatch):
    from repro.core.runtime_flags import moe_expert_path

    monkeypatch.delenv("REPRO_MOE_EXPERTS", raising=False)
    assert moe_expert_path() == "grouped"
    monkeypatch.setenv("REPRO_MOE_EXPERTS", "vmapped")
    assert moe_expert_path() == "vmapped"
    monkeypatch.setenv("REPRO_MOE_EXPERTS", "dense")
    with pytest.raises(ValueError):
        moe_expert_path()


# ---------------------------------------------------------------------------
# Routing: held experts, dropless training dispatch, top-k weights
# ---------------------------------------------------------------------------


def _moe_layer(arch="phi3.5-moe-42b-a6.6b", **kw):
    from repro.configs.registry import get_config
    from repro.models import moe
    from repro.models.layers import init_tree, quant_mask_tree, wrap_qt_nojit

    cfg = get_config(arch, smoke=True).replace(quant=BF16_CONFIG, **kw)
    defs = moe.moe_defs(cfg)
    params = init_tree(defs, jax.random.PRNGKey(0))
    return cfg, params, wrap_qt_nojit(params, quant_mask_tree(defs))


def _routed_by_hand(cfg, params, x, renorm: bool):
    """Σ over each token's top-k held experts of w_k · FFN_k(x), in f32
    from the layer's own weights: w_k the softmax probability, divided
    by the top-k sum where ``renorm``."""
    xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    probs = jax.nn.softmax(xf @ params["router"], axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)
    if renorm:
        w = w / w.sum(-1, keepdims=True)
    ys = jnp.stack([
        (jax.nn.silu(xf @ params["w_gate"][e]) * (xf @ params["w_up"][e]))
        @ params["w_down"][e] for e in range(cfg.n_held)])     # (H, T, d)
    held = ids < cfg.n_held
    picked = ys[jnp.minimum(ids, cfg.n_held - 1), jnp.arange(xf.shape[0])[:, None]]
    return jnp.sum(jnp.where(held[..., None], w[..., None] * picked, 0.0),
                   axis=1).reshape(x.shape)


@pytest.mark.parametrize("norm_topk", [True, False])
def test_top_k_weights_renormalised_only_where_configured(norm_topk,
                                                          monkeypatch):
    """``norm_topk=False`` (DeepSeek-V2's ``norm_topk_prob``) combines
    the experts with their raw softmax probabilities; True divides by
    their sum.  The two differ by O(1), so a wrong choice cannot pass
    bf16's tolerance."""
    from repro.models import moe

    monkeypatch.setenv("REPRO_KERNELS", "ref")
    cfg, params, qp = _moe_layer(top_k=4, norm_topk=norm_topk)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.bfloat16)
    y, _ = moe.moe_block(cfg, qp, x, cfg.quant, mode="train")
    want = _routed_by_hand(cfg, params, x, renorm=norm_topk)
    other = _routed_by_hand(cfg, params, x, renorm=not norm_topk)
    err = float(jnp.linalg.norm(y.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    gap = float(jnp.linalg.norm(other - want) / jnp.linalg.norm(want))
    assert err < 0.02 < gap, (err, gap)


def test_held_experts_compute_their_part_of_the_routed_sum(monkeypatch):
    """A layer holding experts [0, 3) of 8 routes over all 8 and returns
    only its own experts' part; the router keeps its published width."""
    from repro.models import moe

    monkeypatch.setenv("REPRO_KERNELS", "ref")
    cfg, params, qp = _moe_layer(experts_held=3)
    assert params["router"].shape[-1] == 8
    assert params["w_up"].shape[0] == 3
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model),
                          jnp.bfloat16)
    y, aux = moe.moe_block(cfg, qp, x, cfg.quant, mode="train")
    want = _routed_by_hand(cfg, params, x, renorm=True)
    err = float(jnp.linalg.norm(y.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert err < 0.02, err
    xf = x.reshape(-1, cfg.d_model).astype(jnp.float32)
    _, ids = jax.lax.top_k(jax.nn.softmax(xf @ params["router"]), cfg.top_k)
    assert int(aux["rows_routed"]) == int(jnp.sum(ids < 3))


def test_train_dispatch_drops_nothing_when_one_expert_takes_all(
        monkeypatch):
    """A router that sends every token to the same experts overflows any
    capacity factor: prefill's dispatch drops slots, training's drops
    none and equals the combine computed by hand."""
    from repro.models import moe

    monkeypatch.setenv("REPRO_KERNELS", "ref")
    cfg, params, _ = _moe_layer(moe_decode_dense=False)
    params = dict(params, router=params["router"].at[:, :2].set(0.0)
                  .at[0, :2].set(50.0))
    from repro.models.layers import quant_mask_tree, wrap_qt_nojit

    qp = wrap_qt_nojit(params, quant_mask_tree(moe.moe_defs(cfg)))
    # every token's first feature large: experts 0 and 1 win everywhere
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model),
                          jnp.float32).at[..., 0].set(4.0).astype(jnp.bfloat16)
    t = x.shape[0] * x.shape[1]
    _, aux_p = moe.moe_block(cfg, qp, x, cfg.quant, mode="prefill")
    y, aux = moe.moe_block(cfg, qp, x, cfg.quant, mode="train")
    assert int(aux_p["slots_dropped"]) > 0
    assert int(aux["slots_dropped"]) == 0
    assert int(aux["rows_routed"]) == t * cfg.top_k
    want = _routed_by_hand(cfg, params, x, renorm=True)
    err = float(jnp.linalg.norm(y.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert err < 0.02, err


@pytest.mark.parametrize("capacity", [32, 4])
def test_dispatch_and_combine_gradients_match_autodiff_by_hand(capacity):
    """The gather-only dispatch and combine (custom transposes) against
    autodiff of the same routing written by hand: each kept (token, slot)
    pair adds w · s_e · x, with s_e an expert's own scale; at capacity 4
    the pairs past an expert's first 4 (in token order) are dropped."""
    import types

    from repro.models import moe

    t, d, k, held = 24, 8, 3, 4
    cfg = types.SimpleNamespace(n_held=held)
    kx, kw, ki = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(kx, (t, d), jnp.float32)
    w = jax.random.uniform(kw, (t, k), jnp.float32)
    ids = jax.random.permutation(ki, jnp.tile(jnp.arange(6), (t, 1)),
                                 axis=1, independent=True)[:, :k]
    scale = jnp.arange(1.0, held + 1.0)

    def program(x, w):
        y, dropped = moe._dispatch_combine_local(
            cfg, x, ids, w, lambda xs, sizes: xs * scale[:, None, None],
            capacity, None)
        return y, dropped

    flat = np.asarray(ids).reshape(-1)
    rank = np.array([np.sum(flat[:p] == flat[p]) for p in range(flat.size)])
    kept = jnp.asarray(((flat < held) & (rank < capacity)).reshape(t, k))

    def by_hand(x, w):
        s_pair = scale[jnp.minimum(ids, held - 1)]
        return jnp.sum(jnp.where(kept, w * s_pair, 0.0)[..., None]
                       * x[:, None, :], axis=1)

    (y, dropped), (y_h) = program(x, w), by_hand(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_h), rtol=1e-6,
                               atol=1e-6)
    assert int(dropped) == int(np.sum((flat < held) & (rank >= capacity)))
    assert (int(dropped) > 0) == (capacity < t)
    probe = jax.random.normal(jax.random.PRNGKey(7), (t, d), jnp.float32)
    g_p = jax.grad(lambda x, w: jnp.sum(program(x, w)[0] * probe),
                   argnums=(0, 1))(x, w)
    g_h = jax.grad(lambda x, w: jnp.sum(by_hand(x, w) * probe),
                   argnums=(0, 1))(x, w)
    for a, b in zip(g_p, g_h):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_training_never_takes_the_dense_combine(monkeypatch):
    """Training (and its eval forward) dispatches whatever the size; the
    masked dense combine is left to decode, verify, calibration and
    small prefills.  The step reports its routing counts."""
    from repro.configs.registry import get_config
    from repro.models import moe
    from repro.train.steps import (TrainHParams, init_train_state,
                                   make_train_step)

    monkeypatch.setenv("REPRO_KERNELS", "ref")
    calls = []
    real = moe._dense_moe
    monkeypatch.setattr(moe, "_dense_moe",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    cfg = get_config("deepseek-v2-lite-16b", smoke=True)
    assert cfg.moe_decode_dense
    hp = TrainHParams(peak_lr=1e-3, warmup_steps=0, total_steps=4)
    state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    _, m = jax.jit(make_train_step(cfg, hp))(
        state, {"tokens": tokens, "labels": tokens})
    assert calls == []
    assert int(m["moe_slots_dropped"]) == 0
    moe_layers = cfg.n_layers - cfg.first_dense
    assert int(m["moe_rows_routed"]) == moe_layers * 64 * cfg.top_k
