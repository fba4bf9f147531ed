"""Speculative multi-token decode exactness harness
(docs/speculative-decoding.md).

THE contract: greedy speculative output is token-for-token identical
to plain decode — for every draft length k, every draft source
(oracle, adversarial, n-gram), fp8 AND bf16 caches, ref AND interpret
kernel backends, float AND identity page placements, through
mid-stream rejections, EOS inside a draft window and mixed-depth
batches.  The draft source only changes how many tokens each cache
read commits, never which tokens.

Layers under test, innermost out:

- kernel: the batched-query (q_len > 1) in-step causal mask — each
  draft row of one 5-D launch is BITWISE the 4-D single-query launch
  at that draft's own validity window (contiguous and paged);
- step: ``make_verify_step``'s k logit rows are BITWISE the k
  sequential ``make_decode_step`` calls they replace;
- engine: end-to-end token parity vs the plain-decode engine, plus
  accept-rate bookkeeping;
- jaxpr: the (B, k) verify graph keeps the fused-kernel serving
  contract — ZERO cache-sized fp8 dequant upcasts, ZERO cache-sized
  dot_generals, and zero quantization amax reductions beyond the two
  unavoidable K/V storage-write amaxes on an fp8 cache (zero outright
  on bf16).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.formats import BF16_CONFIG
from repro.kernels import dispatch
from repro.models import attention as A
from repro.models.layers import init_tree
from repro.models.transformer import model_defs, spec_verify_supported
from repro.serving import Engine, ModelDraft, NgramDraft, Request
from repro.serving.spec import DraftSource
from repro.train.steps import (
    make_decode_step,
    make_prefill_step,
    make_verify_step,
    prequantize_params,
)

MAX_LEN = 64


def _cfg(kv_dtype="fp8"):
    return get_config("phi3-mini-3.8b", smoke=True).replace(
        quant=BF16_CONFIG, kv_cache_dtype=kv_dtype)


def _params(cfg):
    return init_tree(model_defs(cfg), jax.random.PRNGKey(0))


def _requests(cfg, lens, max_new=10, seed=0, eos=None):
    rng = np.random.default_rng(seed)
    reqs = []
    for i, spec in enumerate(lens):
        n, mn = spec if isinstance(spec, tuple) else (spec, max_new)
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, size=n,
                                       dtype=np.int32),
            max_new=mn, eos_id=eos))
    return reqs


def _serve(cfg, params, lens, *, spec, max_new=10, eos=None, **kw):
    eng = Engine(cfg, params, num_slots=3, max_len=MAX_LEN,
                 spec_decode=spec, **kw)
    reqs = _requests(cfg, lens, max_new=max_new, eos=eos)
    eng.run(reqs, log=None)
    return {r.rid: list(r.out) for r in reqs}, eng


class Oracle:
    """Proposes the exact continuation recorded from a baseline run —
    maximal acceptance, the accepted-tokens/step upper bound."""

    def __init__(self, truth):
        self.truth = truth

    def propose(self, req, k):
        t = self.truth[req.rid]
        return t[len(req.out):len(req.out) + k]


class Adversarial:
    """Always-wrong proposals — every draft must be rejected and the
    engine must still emit exactly the plain-decode stream (one
    correction token per verify step)."""

    def __init__(self, truth):
        self.truth = truth

    def propose(self, req, k):
        t = self.truth[req.rid]
        nxt = t[len(req.out):len(req.out) + k]
        return [(x + 1) % 500 for x in nxt] or [0]


class HalfOracle:
    """Right for the first ``good`` drafts of every window, wrong
    after — forces a MID-STREAM rejection inside every verify step
    (partial accept + truncation + correction)."""

    def __init__(self, truth, good=1):
        self.truth = truth
        self.good = good

    def propose(self, req, k):
        t = self.truth[req.rid]
        nxt = list(t[len(req.out):len(req.out) + k])
        for j in range(self.good, len(nxt)):
            nxt[j] = (nxt[j] + 1) % 500
        return nxt


def _truth(cfg, params, lens, max_new=10, eos=None):
    out, _ = _serve(cfg, params, lens, spec=False, max_new=max_new,
                    eos=eos)
    return out


# ---------------------------------------------------------------------------
# Engine-level token parity — the acceptance contract
# ---------------------------------------------------------------------------


MIXED_LENS = [5, 9, 17]          # straddle chunk/page boundaries


@pytest.mark.parametrize("kv_dtype", ["fp8", "bf16"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_token_parity_all_draft_sources(kv_dtype, k, monkeypatch):
    """Every draft source — full-accept oracle, always-rejected
    adversarial, per-window partial accept, and the real n-gram
    lookup — produces token-for-token the plain-decode stream, for
    k in {1, 2, 4} (k=1 exercises the fall-back-to-plain clamp)."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    cfg = _cfg(kv_dtype)
    params = _params(cfg)
    truth = _truth(cfg, params, MIXED_LENS)
    sources = [Oracle(truth), Adversarial(truth),
               HalfOracle(truth, good=1), NgramDraft()]
    for draft in sources:
        got, eng = _serve(cfg, params, MIXED_LENS, spec=True,
                          draft=draft, spec_k=k)
        assert got == truth, (kv_dtype, k, type(draft).__name__)
        st = eng.stats()
        if k > 1 and isinstance(draft, Oracle):
            # the oracle accepts everything: strictly fewer verify
            # steps than tokens, accept rate pinned at 1
            assert st["spec_verify_steps"] > 0
            assert st["spec_accept_rate"] == pytest.approx(1.0)
        if isinstance(draft, Adversarial) and k > 1:
            assert st["spec_accepted"] == 0


@pytest.mark.parametrize("placement", ["float", "identity"])
def test_token_parity_page_placements(placement, monkeypatch):
    """Rejection truncation under BOTH page placements: float restamps
    idx/block tables from host lengths every step (truncation is
    free); identity must walk the live device idx back after a
    rejected window (``PagedKVCache.commit``)."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    monkeypatch.setenv("REPRO_PAGED_PLACEMENT", placement)
    cfg = _cfg("fp8")
    params = _params(cfg)
    truth = _truth(cfg, params, MIXED_LENS)
    for draft in (Oracle(truth), HalfOracle(truth, good=1)):
        got, eng = _serve(cfg, params, MIXED_LENS, spec=True,
                          draft=draft, spec_k=4)
        assert got == truth, (placement, type(draft).__name__)
        assert eng.stats()["spec_verify_steps"] > 0


def test_eos_inside_draft_window(monkeypatch):
    """EOS arriving as an ACCEPTED DRAFT mid-window stops the request
    at exactly the plain-decode length — later drafts in the same
    window must not commit."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    cfg = _cfg("fp8")
    params = _params(cfg)
    free = _truth(cfg, params, [5], max_new=10)
    eos = free[0][4]                       # stop at output position 5
    truth = _truth(cfg, params, [5], max_new=10, eos=eos)
    assert len(truth[0]) == 5
    got, eng = _serve(cfg, params, [5], spec=True, max_new=10, eos=eos,
                      draft=Oracle(free), spec_k=4)
    assert got == truth
    assert eng.stats()["spec_verify_steps"] > 0


def test_mixed_depth_batches_and_budgets(monkeypatch):
    """Rows at different prompt depths AND different max_new budgets
    share one verify launch; k clamps to the tightest remaining
    budget, so no row ever overruns max_new."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    cfg = _cfg("fp8")
    params = _params(cfg)
    lens = [(5, 3), (9, 10), (17, 7)]      # (prompt_len, max_new)
    truth = _truth(cfg, params, lens)
    got, _ = _serve(cfg, params, lens, spec=True, draft=Oracle(truth),
                    spec_k=4)
    assert got == truth
    for rid, (_, mn) in enumerate(lens):
        assert len(got[rid]) == mn


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", ["fp8", "bf16"])
@pytest.mark.parametrize("placement", ["float", "identity"])
def test_token_parity_interpret_backend(kv_dtype, placement,
                                        monkeypatch):
    """The full matrix leg on the Pallas-interpret backend: the
    verify step runs through the REAL batched-query kernel (in-step
    causal mask, draft-major rows) and still reproduces plain decode
    token-for-token."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.setenv("REPRO_PAGED_PLACEMENT", placement)
    cfg = _cfg(kv_dtype)
    params = _params(cfg)
    lens = [5, 9]
    truth = _truth(cfg, params, lens, max_new=6)
    for draft in (Oracle(truth), HalfOracle(truth, good=1)):
        got, eng = _serve(cfg, params, lens, spec=True, max_new=6,
                          draft=draft, spec_k=3)
        assert got == truth, (kv_dtype, placement,
                              type(draft).__name__)
        assert eng.stats()["spec_verify_steps"] > 0


# ---------------------------------------------------------------------------
# Step-level: one (B, k) verify == k sequential decode steps, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["fp8", "bf16"])
def test_verify_step_bitwise_vs_sequential_decode(kv_dtype):
    """``make_verify_step``'s k logit rows are BITWISE the k
    sequential ``make_decode_step`` calls they replace: per-position
    K/V quantization (amax over Dh only), batch-independent DELAYED
    activation scales (the serving default — a just-in-time per-tensor
    amax would see k tokens instead of 1 and shift every scale) and
    the per-draft validity mask together make the verify graph a pure
    re-bracketing of the sequential computation."""
    from repro.core.actscale import calibrate_act_scales

    # full serving stack: fp8 weight quant + prequantized params
    cfg = get_config("phi3-mini-3.8b", smoke=True).replace(
        kv_cache_dtype=kv_dtype)
    params = _params(cfg)
    pq = prequantize_params(cfg, params)
    act = calibrate_act_scales(cfg, pq.qweights, pq.scales)
    pre = jax.jit(make_prefill_step(cfg, 16, scales=pq.scales,
                                    act_scales=act))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab)
    _, caches0 = pre(pq.qweights, {"tokens": toks})

    dec = jax.jit(make_decode_step(cfg, scales=pq.scales,
                                   act_scales=act))
    feed0 = toks[:, :1]
    seq_logits, caches = [], caches0
    cur = feed0
    for _ in range(4):
        lo, caches = dec(pq.qweights, caches, cur)
        seq_logits.append(np.asarray(lo[:, 0]))
        cur = jnp.argmax(lo[:, -1], axis=-1)[:, None].astype(jnp.int32)

    # verify feed = [t0, d1, d2, d3] with d_j the greedy continuation
    _, caches = pre(pq.qweights, {"tokens": toks})   # fresh prefill
    ver = jax.jit(make_verify_step(cfg, scales=pq.scales,
                                   act_scales=act))
    drafts = np.stack([np.argmax(s, axis=-1) for s in seq_logits[:3]],
                      axis=1)
    feed = np.concatenate([np.asarray(feed0), drafts], axis=1)
    vlo, _ = ver(pq.qweights, caches, jnp.asarray(feed, jnp.int32))
    for j in range(4):
        assert np.array_equal(np.asarray(vlo[:, j]), seq_logits[j]), \
            (kv_dtype, j)


# ---------------------------------------------------------------------------
# Kernel-level: the batched-query in-step causal mask
# ---------------------------------------------------------------------------


def _kernel_fixture(kv_dtype, b=2, kvh=2, g=4, c=48, dh=32, seed=0):
    rng = np.random.default_rng(seed)
    s_len = 3
    q = jnp.asarray(rng.standard_normal((b, kvh, s_len, g, dh)),
                    jnp.bfloat16)
    kf = jnp.asarray(rng.standard_normal((b, kvh, c, dh)))
    vf = jnp.asarray(rng.standard_normal((b, kvh, c, dh)))
    if kv_dtype == "fp8":
        k, ks = A._quant_kv(kf)
        v, vs = A._quant_kv(vf)
    else:
        k, v = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
        ks = vs = None
    nv = jnp.asarray([17, 41], jnp.int32)   # POST-write depths, >= s_len
    return q, k, v, ks, vs, nv


@pytest.mark.parametrize("kv_dtype", ["fp8", "bf16"])
@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_batched_query_rows_bitwise_vs_single_query(kv_dtype, backend):
    """Draft row j of ONE 5-D launch == the 4-D single-query launch
    at that draft's own validity window (n_valid - (S-1-j)), bitwise:
    the in-step causal mask reproduces each sequential step's window
    exactly, so sharing one cache read loses nothing."""
    q, k, v, ks, vs, nv = _kernel_fixture(kv_dtype)
    s_len = q.shape[2]
    out = dispatch.decode_attention(q, k, v, ks, vs, nv,
                                    backend=backend)
    assert out.shape == q.shape
    for j in range(s_len):
        solo = dispatch.decode_attention(q[:, :, j], k, v, ks, vs,
                                         nv - (s_len - 1 - j),
                                         backend=backend)
        assert jnp.array_equal(out[:, :, j], solo), (kv_dtype,
                                                     backend, j)


@pytest.mark.parametrize("kv_dtype", ["fp8", "bf16"])
def test_batched_query_ref_vs_interpret_bitwise(kv_dtype):
    """5-D ref (einsum oracle) vs interpret (Pallas kernel) — single
    C block replays the exact softmax in the reference operation
    order, so across backends the verify step is bitwise too."""
    q, k, v, ks, vs, nv = _kernel_fixture(kv_dtype, seed=3)
    outs = {b: dispatch.decode_attention(q, k, v, ks, vs, nv,
                                         backend=b)
            for b in ("ref", "interpret")}
    assert jnp.array_equal(outs["ref"], outs["interpret"])


@pytest.mark.parametrize("kv_dtype", ["fp8", "bf16"])
def test_batched_query_paged_shares_one_page_read(kv_dtype):
    """The paged variant: k draft queries share ONE gather of the fp8
    KV pages — row parity against the paged single-query launch at
    shifted windows, both backends."""
    q, k, v, ks, vs, nv = _kernel_fixture(kv_dtype, c=64, seed=5)
    t, n_p = 16, 4
    pool = lambda a: (None if a is None else jnp.concatenate(
        [a[i].reshape(a.shape[1], n_p, t, *a.shape[3:]).swapaxes(0, 1)
         for i in range(a.shape[0])], axis=0))
    pk, pv, pks, pvs = pool(k), pool(v), pool(ks), pool(vs)
    bt = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    s_len = q.shape[2]
    for backend in ("ref", "interpret"):
        out = dispatch.decode_attention_paged(q, pk, pv, pks, pvs, nv,
                                              bt, backend=backend)
        for j in range(s_len):
            solo = dispatch.decode_attention_paged(
                q[:, :, j], pk, pv, pks, pvs,
                nv - (s_len - 1 - j), bt, backend=backend)
            assert jnp.array_equal(out[:, :, j], solo), (kv_dtype,
                                                         backend, j)


# ---------------------------------------------------------------------------
# jaxpr: the verify step keeps the reduction-free serving contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["fp8", "bf16"])
def test_verify_jaxpr_zero_dequant_and_quant_reductions(kv_dtype,
                                                        monkeypatch):
    """The batched-query verify graph inherits every serving-graph
    contract the single-token decode earned: ZERO cache-sized fp8
    dequant upcasts, ZERO cache-sized dot_generals (the k draft
    queries ride the fused kernel's one page read), and zero
    quantization amax reductions — outright on a bf16 cache; on fp8
    exactly the TWO per-position K/V storage-write amaxes remain
    (they quantize the k incoming tokens, not the cache)."""
    from repro.core.actscale import calibrate_act_scales
    from repro.core.introspect import (
        count_dot_general_over,
        count_fp8_dequant_upcasts,
        count_quant_reductions,
        kv_cache_slice_sizes,
    )

    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.delenv("REPRO_DECODE_ATTN", raising=False)
    cfg = get_config("phi3-mini-3.8b", smoke=True).replace(
        kv_cache_dtype=kv_dtype)
    params = _params(cfg)
    pq = prequantize_params(cfg, params)
    act = calibrate_act_scales(cfg, pq.qweights, pq.scales)
    pre = jax.jit(make_prefill_step(cfg, 16, scales=pq.scales,
                                    act_scales=act))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab)
    _, caches = pre(pq.qweights, {"tokens": toks})
    feed = toks[:, :4]                     # k = 4 verify window
    jx = jax.make_jaxpr(make_verify_step(cfg, scales=pq.scales,
                                         act_scales=act))(
        pq.qweights, caches, feed)
    sizes = kv_cache_slice_sizes(cfg, 2, 16)
    assert count_fp8_dequant_upcasts(jx, sizes) == 0
    assert count_dot_general_over(jx, sizes) == 0
    storage_amaxes = 2 if kv_dtype == "fp8" else 0
    assert count_quant_reductions(jx) == storage_amaxes


# ---------------------------------------------------------------------------
# Draft sources + gating
# ---------------------------------------------------------------------------


def test_ngram_draft_prompt_lookup():
    """Suffix lookup basics: longest n-gram wins, the most recent
    earlier occurrence wins, empty when nothing matches."""
    d = NgramDraft(max_ngram=3)
    req = Request(rid=0, prompt=np.asarray([7, 8, 9, 1, 2, 3, 4, 5],
                                           np.int32), max_new=8)
    req.out = [1, 2, 3]
    # suffix (1,2,3) recurs at position 3 -> propose its continuation
    assert d.propose(req, 4) == [4, 5, 1, 2]
    req.out = [99]
    assert d.propose(req, 4) == []         # 99 never seen before
    # recency: the LAST earlier occurrence's continuation wins
    req2 = Request(rid=1, prompt=np.asarray([1, 2, 5, 1, 2, 6, 1, 2],
                                            np.int32), max_new=8)
    assert d.propose(req2, 1) == [6]


def test_model_draft_hook():
    calls = []

    def propose_fn(ctx, k):
        calls.append((tuple(ctx), k))
        return [41, 42, 43][:k]

    d = ModelDraft(propose_fn)
    assert isinstance(d, DraftSource)
    req = Request(rid=0, prompt=np.asarray([1, 2], np.int32),
                  max_new=4)
    req.out = [3]
    assert d.propose(req, 2) == [41, 42]
    assert calls == [((1, 2, 3), 2)]


def test_spec_gate_requires_chunked_v2(monkeypatch):
    """The verify step rides the v2 mixed-step support surface: with
    chunked prefill off the spec flag is inert (plain decode), and
    the env flag mirrors the constructor arg."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    cfg = _cfg("fp8")
    params = _params(cfg)
    assert spec_verify_supported(cfg, MAX_LEN)
    monkeypatch.setenv("REPRO_CHUNKED_PREFILL", "0")
    eng = Engine(cfg, params, num_slots=2, max_len=MAX_LEN,
                 spec_decode=True)
    assert not eng.spec
    monkeypatch.delenv("REPRO_CHUNKED_PREFILL")
    monkeypatch.setenv("REPRO_SPEC_DECODE", "1")
    eng = Engine(cfg, params, num_slots=2, max_len=MAX_LEN)
    assert eng.spec
    monkeypatch.setenv("REPRO_SPEC_DECODE", "0")
    eng = Engine(cfg, params, num_slots=2, max_len=MAX_LEN)
    assert not eng.spec
    # fp8 activation quant WITHOUT delayed scales: a (B, k) window
    # would measure different per-tensor act amaxes than the (B, 1)
    # steps it replaces — inexact, so the gate stays off
    monkeypatch.setenv("REPRO_SERVE_DELAYED_ACT", "0")
    fcfg = get_config("phi3-mini-3.8b", smoke=True)
    eng = Engine(fcfg, _params(fcfg), num_slots=2, max_len=MAX_LEN,
                 spec_decode=True)
    assert not eng.spec
    monkeypatch.delenv("REPRO_SERVE_DELAYED_ACT")
    eng = Engine(fcfg, _params(fcfg), num_slots=2, max_len=MAX_LEN,
                 spec_decode=True)
    assert eng.spec


def test_accept_rate_ema_steers_draft_len():
    """Scheduler policy units: the EMA starts optimistic, decays
    toward the observed accept rate, and ``draft_len`` scales the
    configured maximum (floored at 2 so the EMA can recover)."""
    from repro.serving import Scheduler

    s = Scheduler()
    assert s.draft_len(4) == 4             # optimistic start
    for _ in range(20):
        s.on_verify(proposed=6, accepted=0)
    assert s.accept_rate < 0.05
    assert s.draft_len(8) == 2             # floored, never 1
    assert s.draft_len(2) == 2             # k_max <= 2 passes through
    assert s.draft_len(1) == 1
    for _ in range(30):
        s.on_verify(proposed=6, accepted=6)
    assert s.accept_rate > 0.95
    assert s.draft_len(8) == 8
    st = s.summary()
    assert st["spec_verify_steps"] == 50
    assert st["spec_drafted"] == 300


# ---------------------------------------------------------------------------
# Satellite: small-T single-device MoE routing — prefill short-circuits
# to the dense decode combine unless moe_decode_dense is disabled;
# training never does (it dispatches dropless)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dense_flag,expect_dense", [(True, True),
                                                     (False, False)])
def test_moe_small_t_train_routes_dense_combine(dense_flag,
                                                expect_dense,
                                                monkeypatch):
    """Pin the routing decision: on a single device with small T the
    PREFILL path short-circuits to the dense decode combine (one
    gather-free einsum) unless ``moe_decode_dense=False``, and the TRAIN
    path takes the sort-based dispatch whatever the flag — future
    engine/scheduler changes must not silently flip either."""
    from repro.models import moe as M
    from repro.models.layers import quant_mask_tree, wrap_qt_nojit

    monkeypatch.setenv("REPRO_KERNELS", "ref")
    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True).replace(
        quant=BF16_CONFIG, moe_decode_dense=dense_flag)
    defs = M.moe_defs(cfg)
    params = init_tree(defs, jax.random.PRNGKey(0))
    qp = wrap_qt_nojit(params, quant_mask_tree(defs))
    seen = []
    real_dense, real_dc = M._dense_moe, M._dispatch_combine_local

    def spy_dense(*a, **kw):
        seen.append("dense")
        return real_dense(*a, **kw)

    def spy_dc(*a, **kw):
        seen.append("dispatch")
        return real_dc(*a, **kw)

    monkeypatch.setattr(M, "_dense_moe", spy_dense)
    monkeypatch.setattr(M, "_dispatch_combine_local", spy_dc)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.bfloat16)                 # T = 16 << 4096
    M.moe_block(cfg, qp, x, cfg.quant, mode="prefill")
    M.moe_block(cfg, qp, x, cfg.quant, mode="train")
    assert seen == (["dense"] if expect_dense
                    else ["dispatch"]) + ["dispatch"], (dense_flag, seen)
