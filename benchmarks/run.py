"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  CPU-runtime caveat
(EXPERIMENTS.md): FP8 is emulated on this container, so wall-clock rows
measure the *emulation*; the paper's speedup evidence is carried by the
structural rows (bytes, scaling-op counts, SNR, roofline terms), which
are runtime-independent.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6     # us


# rows accumulate here so --json can emit the whole run as machine-
# readable records (the perf-trajectory artifact uploaded by CI)
_ROWS: list[dict] = []


def row(name, us, derived=""):
    _ROWS.append({"name": name, "us_per_call": round(us, 1),
                  "derived": derived})
    print(f"{name},{us:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------
# Paper Table 1: time to produce per-tensor weight scales —
# just-in-time (max-reduction over the tensor) vs automatic (Eq. 10).
# ---------------------------------------------------------------------------


def bench_table1_autoscale():
    from repro.core.autoscale import ScaleState, predicted_scale
    from repro.core.formats import MOSS_CONFIG

    sizes = [(11008, 16384), (11008, 8192), (4096, 12288), (4096, 4096)]
    for shape in sizes:
        w = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)

        jit_scale = jax.jit(lambda w: jnp.max(jnp.abs(w)) / 448.0)
        us_jit = _timeit(jit_scale, w)

        st = ScaleState(s0=jnp.float32(0.01),
                        steps_since=jnp.asarray(17, jnp.int32))
        auto = jax.jit(lambda st, lr: predicted_scale(st, lr,
                                                      MOSS_CONFIG))
        us_auto = _timeit(auto, st, jnp.float32(3e-4))
        # derived: bytes the JIT path must read from HBM that the
        # automatic path does not (the paper's Table 1 mechanism)
        saved = int(np.prod(shape)) * 4
        row(f"table1_jit_scaling_{shape[0]}x{shape[1]}", us_jit,
            f"reads_{saved}B")
        row(f"table1_auto_scaling_{shape[0]}x{shape[1]}", us_auto,
            "reads_0B_constant_time")


# ---------------------------------------------------------------------------
# Paper Table 2/3: training throughput, MOSS vs BF16 vs COAT-style —
# smoke-scale wall clock + structural accounting.
# ---------------------------------------------------------------------------


def bench_table2_throughput(B: int = 8, S: int = 128, iters: int = 5):
    from repro.configs.registry import get_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.train import quant_from_name
    from repro.train.steps import (TrainHParams, init_train_state,
                                   make_train_step)

    for quant in ["bf16", "per_tensor", "per_group", "moss"]:
        cfg = get_config("olmo-7b", smoke=True).replace(
            quant=quant_from_name(quant))
        hp = TrainHParams(peak_lr=1e-3, warmup_steps=5, total_steps=100)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=S,
                                      global_batch=B))
        state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(cfg, hp), donate_argnums=(0,))
        state, _ = step(state, data.batch_for_step(0))   # compile
        t0 = time.perf_counter()
        for i in range(iters):
            state, m = step(state, data.batch_for_step(i + 1))
        jax.block_until_ready(m["loss"])
        dt = (time.perf_counter() - t0) / iters
        row(f"table2_train_step_{quant}", dt * 1e6,
            f"tokens_per_s_{B*S/dt:.0f}_cpu_emulation")


# ---------------------------------------------------------------------------
# Paper Table 5: activation-memory accounting — bytes saved for the
# backward pass under bf16 vs MOSS fp8 residuals.
# ---------------------------------------------------------------------------


def bench_table5_memory_comm():
    from repro.configs.registry import get_config
    from repro.launch.train import quant_from_name
    from repro.models.layers import (abstract_tree, quant_mask_tree,
                                     wrap_qt_nojit)
    from repro.models.transformer import ce_loss, forward, model_defs

    cfg0 = get_config("llama2-7b", smoke=True)
    B, S = 4, 256
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}

    results = {}
    for quant in ["bf16", "moss"]:
        cfg = cfg0.replace(quant=quant_from_name(quant), remat=False)
        defs = model_defs(cfg)
        params = abstract_tree(defs)

        def loss_fn(params, cfg=cfg, defs=defs):
            qp = wrap_qt_nojit(params, quant_mask_tree(defs))
            logits, _, _ = forward(cfg, cfg.quant, qp, batch,
                                   mode="train")
            return ce_loss(cfg, logits, batch["labels"])

        res_shapes = jax.eval_shape(
            lambda p: jax.vjp(loss_fn, p)[1], params)
        leaves = [l for l in jax.tree.leaves(res_shapes)
                  if hasattr(l, "shape")]
        total = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                    for l in leaves)
        results[quant] = total
        row(f"table5_residual_bytes_{quant}", 0.0, f"{total}B")
    ratio = results["bf16"] / max(results["moss"], 1)
    row("table5_residual_saving", 0.0, f"{ratio:.2f}x")


# ---------------------------------------------------------------------------
# Paper Table 6: quantized GEMM comparison at the paper's shapes.
# ---------------------------------------------------------------------------


def bench_table6_gemm():
    from repro.core.quant import (MxQ, PerGroupQ, PerTensorQ, group_gemm,
                                  mx_gemm, pt_gemm, quant_mx,
                                  quant_per_group, quant_per_tensor)

    shapes = [(2048, 7168, 4096), (4096, 2048, 7168), (4096, 4096, 8192)]
    for m, n, k in shapes:
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n),
                              jnp.float32) * 0.02
        xq_mx = quant_mx(x)
        xq_pg = quant_per_group(x, 128)
        xq_pt = quant_per_tensor(x)
        wq = quant_per_tensor(w)

        f_mx = jax.jit(lambda q, e, s: mx_gemm(MxQ(q, e, s), wq,
                                               jnp.bfloat16))
        us_mx = _timeit(f_mx, xq_mx.q, xq_mx.sexp, xq_mx.s, iters=3,
                        warmup=1)
        f_pg = jax.jit(lambda q, s: group_gemm(PerGroupQ(q, s), wq,
                                               jnp.bfloat16))
        us_pg = _timeit(f_pg, xq_pg.q, xq_pg.s, iters=3, warmup=1)
        f_pt = jax.jit(lambda q, s: pt_gemm(PerTensorQ(q, s), wq,
                                            jnp.bfloat16))
        us_pt = _timeit(f_pt, xq_pt.q, xq_pt.s, iters=3, warmup=1)

        # structural: in-loop VPU dequant multiplies of the (bm,bn)
        # accumulator per output element (the cost MOSS removes)
        row(f"table6_gemm_moss_{m}x{n}x{k}", us_mx,
            "acc_rescales_per_output_1(epilogue)")
        row(f"table6_gemm_coat_{m}x{n}x{k}", us_pg,
            f"acc_rescales_per_output_{k//128}(inloop)")
        row(f"table6_gemm_te_{m}x{n}x{k}", us_pt,
            "acc_rescales_per_output_1(epilogue)")


# ---------------------------------------------------------------------------
# Paper Table 7: SNR of quantization schemes on LLM-like activations.
# ---------------------------------------------------------------------------


def bench_table7_snr():
    from repro.core.formats import (MOSS_CONFIG, PER_GROUP_CONFIG,
                                    PER_TENSOR_CONFIG)
    from repro.core.quant import (model_snr_moss, model_snr_per_group,
                                  model_snr_per_tensor, scheme_snr)

    layers = {
        "attention_output": (300.0, 0.002),
        "ffn_intermediate": (800.0, 0.001),
        "layernorm_input": (100.0, 0.005),
    }
    for name, (scale, dens) in layers.items():
        k1, k2 = jax.random.split(jax.random.PRNGKey(hash(name) % 2**31))
        x = jax.random.normal(k1, (256, 2048), jnp.float32) \
            * (1 + scale * jax.random.bernoulli(k2, dens, (256, 2048)))
        t = float(model_snr_per_tensor(x))
        g = float(model_snr_per_group(x))
        mm_ = float(model_snr_moss(x))
        row(f"table7_modelSNR_{name}", 0.0,
            f"pt_{t:.1f}dB_pg_{g:.1f}dB_moss_{mm_:.1f}dB")
        tm = float(scheme_snr(x, PER_TENSOR_CONFIG))
        gm = float(scheme_snr(x, PER_GROUP_CONFIG))
        mq = float(scheme_snr(x, MOSS_CONFIG))
        row(f"table7_measuredSNR_{name}", 0.0,
            f"pt_{tm:.1f}dB_pg_{gm:.1f}dB_moss_{mq:.1f}dB")


# ---------------------------------------------------------------------------
# Paper Table 9/10: rescale-interval ablation + scaling strategies.
# ---------------------------------------------------------------------------


def bench_table9_interval():
    from repro.configs.registry import get_config
    from repro.core.formats import QuantConfig
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.train.steps import (TrainHParams, init_train_state,
                                   make_train_step)

    for name, scaling, interval in [("jit", "jit", 1),
                                    ("auto100", "auto", 100),
                                    ("auto500", "auto", 500),
                                    ("delayed", "delayed", 1)]:
        cfg = get_config("llama2-7b", smoke=True).replace(
            quant=QuantConfig(mode="moss", weight_scaling=scaling,
                              rescale_interval=interval))
        hp = TrainHParams(peak_lr=1e-3, warmup_steps=5, total_steps=60)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64,
                                      global_batch=8))
        state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(cfg, hp), donate_argnums=(0,))
        losses = []
        t0 = time.perf_counter()
        for t in range(30):
            state, m = step(state, data.batch_for_step(t))
            losses.append(float(m["loss"]))
        dt = (time.perf_counter() - t0) / 30
        row(f"table9_interval_{name}", dt * 1e6,
            f"final_loss_{np.mean(losses[-5:]):.4f}")


# ---------------------------------------------------------------------------
# Kernel-dispatch timing: the same MOSS GEMM through each backend of
# repro.kernels.dispatch (ref = jnp reference; interpret = Pallas
# kernels under the interpreter — kernel-path validation, not a speed
# claim; pallas-native requires a TPU).
# ---------------------------------------------------------------------------


def bench_dispatch_backends(m=256, n=256, k=1024):
    from repro.core.quant import quant_mx, quant_per_tensor
    from repro.core.runtime_flags import kernel_backend
    from repro.kernels import dispatch

    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n),
                          jnp.float32) * 0.02
    xq, wq = quant_mx(x), quant_per_tensor(w)
    backends = ["ref", "interpret"]
    if kernel_backend() == "pallas":
        backends.append("pallas")
    for backend in backends:
        fn = jax.jit(lambda q, e, s: dispatch.mx_matmul(
            type(xq)(q, e, s), wq, jnp.bfloat16, backend=backend))
        us = _timeit(fn, xq.q, xq.sexp, xq.s, iters=3, warmup=1)
        row(f"dispatch_mx_matmul_{backend}_{m}x{n}x{k}", us)
        ffn = jax.jit(lambda xx: dispatch.fused_quant_matmul(
            xx, wq, out_dtype=jnp.bfloat16, backend=backend)[0])
        us = _timeit(ffn, x, iters=3, warmup=1)
        row(f"dispatch_fused_quant_matmul_{backend}_{m}x{n}x{k}", us)


# ---------------------------------------------------------------------------
# Grouped-expert MoE GEMM: one ragged kernel for every expert vs the
# legacy per-expert vmapped path.  Wall clock is CPU emulation; the
# structural columns (kernel launches and level-1 amax reductions per
# MoE block) carry the speedup mechanism.
# ---------------------------------------------------------------------------


def bench_moe_grouped(B: int = 2, S: int = 128, iters: int = 5):
    from repro.configs.registry import get_config
    from repro.models import moe
    from repro.models.layers import (init_tree, quant_mask_tree,
                                     wrap_qt_nojit)

    # the train path always dispatches, so both arms run the experts'
    # GEMMs on the dispatched rows
    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
    qcfg = cfg.quant
    defs = moe.moe_defs(cfg)
    params = init_tree(defs, jax.random.PRNGKey(0))
    qp = wrap_qt_nojit(params, quant_mask_tree(defs))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.bfloat16)
    e = cfg.n_experts
    us = {}
    prior = os.environ.get("REPRO_MOE_EXPERTS")
    try:
        for path in ("grouped", "vmapped"):
            os.environ["REPRO_MOE_EXPERTS"] = path

            def block(x, path=path):
                return moe.moe_block(cfg, qp, x, qcfg, mode="train")[0]

            us[path] = _timeit(jax.jit(block), x, iters=iters, warmup=2)
    finally:
        if prior is None:
            os.environ.pop("REPRO_MOE_EXPERTS", None)
        else:
            os.environ["REPRO_MOE_EXPERTS"] = prior
    row("moe_grouped_vs_vmapped", us["grouped"],
        f"vmapped_us_{us['vmapped']:.1f}"
        f"_launches_3_vs_{3 * e}_amax_reductions_1_vs_{e}")


# ---------------------------------------------------------------------------
# Pre-quantized serving: decode step time + structural op counts with
# in-graph weight quantization vs build-time fp8 weights
# (PrequantParams).  The op counts (weight fp8 casts, max-reductions in
# the decode jaxpr) are the mechanism; CPU wall clock is emulation.
# ---------------------------------------------------------------------------


def bench_serve_prequant(arch: str = "phi3-mini-3.8b"):
    from repro.configs.registry import get_config
    from repro.core.formats import PER_GROUP_CONFIG
    from repro.core.introspect import (count_fp8_casts, count_primitive,
                                       weight_slice_sizes)
    from repro.models.layers import init_tree
    from repro.models.transformer import model_defs
    from repro.train.steps import (make_decode_step, make_prefill_step,
                                   prequantize_params, serve_weight_scales)

    for mode, quant in (("per_group", PER_GROUP_CONFIG), ("moss", None)):
        cfg = get_config(arch, smoke=True)
        if quant is not None:
            cfg = cfg.replace(quant=quant)
        params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab)
        tok1 = toks[:, :1]
        wsizes = weight_slice_sizes(cfg)

        stats = {}
        for tag in ("ingraph", "prequant"):
            if tag == "prequant":
                pq = prequantize_params(cfg, params)
                p, scales = pq.qweights, pq.scales
            else:
                p, scales = params, serve_weight_scales(cfg, params)
            pre = jax.jit(make_prefill_step(cfg, 32, scales=scales))
            _, caches = pre(p, {"tokens": toks})
            step = make_decode_step(cfg, scales=scales)
            jx = jax.make_jaxpr(step)(p, caches, tok1)
            dec = jax.jit(step)
            us = _timeit(lambda c: dec(p, c, tok1)[0], caches,
                         iters=10, warmup=2)
            stats[tag] = (us, count_primitive(jx, "reduce_max"),
                          count_fp8_casts(jx, wsizes))
        us_pq, amax_pq, wc_pq = stats["prequant"]
        us_no, amax_no, wc_no = stats["ingraph"]
        row(f"serve_prequant_decode_{mode}", us_pq,
            f"ingraph_us_{us_no:.1f}_amax_{amax_pq}_vs_{amax_no}"
            f"_weight_fp8_casts_{wc_pq}_vs_{wc_no}")


# ---------------------------------------------------------------------------
# Reduction-free decode: delayed (calibrated) activation scales vs the
# just-in-time path, per recipe — decode-step wall clock plus the
# structural mechanism: quantization reductions (reduce_max feeding an
# fp8 cast, core.introspect.count_quant_reductions) removed from the
# decode jaxpr.  bf16 KV cache so the counts isolate the activation
# quantizers (the fp8 cache keeps its 2 storage-format reductions —
# docs/serving.md).
# ---------------------------------------------------------------------------


def bench_decode_reduction_free(arch: str = "phi3-mini-3.8b"):
    from repro.configs.registry import get_config
    from repro.core.actscale import calibrate_act_scales
    from repro.core.formats import (MOSS_CONFIG, PER_GROUP_CONFIG,
                                    PER_TENSOR_CONFIG)
    from repro.core.introspect import count_quant_reductions
    from repro.models.layers import init_tree
    from repro.models.transformer import model_defs
    from repro.train.steps import (make_decode_step, make_prefill_step,
                                   prequantize_params)

    for mode, quant in (("per_tensor", PER_TENSOR_CONFIG),
                        ("per_group", PER_GROUP_CONFIG),
                        ("moss", MOSS_CONFIG)):
        cfg = get_config(arch, smoke=True).replace(quant=quant,
                                                   kv_cache_dtype="bf16")
        params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
        pq = prequantize_params(cfg, params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab)
        tok1 = toks[:, :1]
        act = calibrate_act_scales(cfg, pq.qweights, pq.scales)
        pre = jax.jit(make_prefill_step(cfg, 32, scales=pq.scales))
        _, caches = pre(pq.qweights, {"tokens": toks})

        stats = {}
        for tag, a in (("jit", None), ("delayed", act)):
            step = make_decode_step(cfg, scales=pq.scales, act_scales=a)
            jx = jax.make_jaxpr(step)(pq.qweights, caches, tok1)
            dec = jax.jit(step)
            us = _timeit(lambda c: dec(pq.qweights, c, tok1)[0], caches,
                         iters=10, warmup=2)
            stats[tag] = (us, count_quant_reductions(jx))
        us_d, nred_d = stats["delayed"]
        us_j, nred_j = stats["jit"]
        row(f"serve_delayed_decode_{mode}", us_d,
            f"jit_us_{us_j:.1f}_quant_reductions_{nred_d}_vs_{nred_j}")


# ---------------------------------------------------------------------------
# Fused decode attention over the fp8 KV cache: decode step wall clock
# for the kernel path (CPU default resolves to the ref oracle — same
# math as the einsum path, so "no slower" holds structurally and in
# wall clock) vs the REPRO_DECODE_ATTN=einsum fallback, plus the
# jaxpr-level mechanism: cache-sized fp8 dequant upcasts and cache
# dots removed from the decode graph (counted on the interpret-backend
# trace, where the fused pallas_call is actually in the graph).
# ---------------------------------------------------------------------------


def bench_decode_attn(arch: str = "phi3-mini-3.8b"):
    from repro.configs.registry import get_config
    from repro.core.introspect import (count_dot_general_over,
                                       count_fp8_dequant_upcasts,
                                       count_primitive,
                                       kv_cache_slice_sizes)
    from repro.models.layers import init_tree
    from repro.models.transformer import model_defs
    from repro.train.steps import (make_decode_step, make_prefill_step,
                                   prequantize_params)

    # B=2 keeps the cache slice size (B·KV·C·Dh = 8192) disjoint from
    # every weight-slice size, so the counters see only the cache ops
    cfg = get_config(arch, smoke=True)           # fp8 cache default
    params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab)
    pq = prequantize_params(cfg, params)
    pre = jax.jit(make_prefill_step(cfg, 32, scales=pq.scales))
    _, caches = pre(pq.qweights, {"tokens": toks})
    tok1 = toks[:, :1]
    sizes = kv_cache_slice_sizes(cfg, 2, 32)

    knobs = ("REPRO_DECODE_ATTN", "REPRO_KERNELS")
    prior = {k: os.environ.get(k) for k in knobs}
    us, counts = {}, {}
    try:
        for tag, env in (("kernel", {}),
                         ("einsum", {"REPRO_DECODE_ATTN": "einsum"})):
            for k in knobs:
                os.environ.pop(k, None)
            os.environ.update(env)
            dec = jax.jit(make_decode_step(cfg, scales=pq.scales))
            # min-of-3: on the CPU default both paths resolve to the
            # same ref math, so wall-clock differences are pure noise
            us[tag] = min(_timeit(lambda c: dec(pq.qweights, c,
                                                tok1)[0],
                                  caches, iters=10, warmup=2)
                          for _ in range(3))
            # structural counts from the interpret-backend trace —
            # the linear GEMMs become pallas_calls on BOTH paths, so
            # the deltas isolate the decode-attention mechanism
            os.environ["REPRO_KERNELS"] = "interpret"
            step = make_decode_step(cfg, scales=pq.scales)
            jx = jax.make_jaxpr(step)(pq.qweights, caches, tok1)
            counts[tag] = (count_fp8_dequant_upcasts(jx, sizes),
                           count_dot_general_over(jx, sizes),
                           count_primitive(jx, "pallas_call"))
            if tag == "kernel":
                dec_i = jax.jit(step)
                us["interpret"] = _timeit(
                    lambda c: dec_i(pq.qweights, c, tok1)[0], caches,
                    iters=3, warmup=1)
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    up_k, dot_k, pc_k = counts["kernel"]
    up_e, dot_e, pc_e = counts["einsum"]
    row("decode_attn_fused_vs_einsum", us["kernel"],
        f"einsum_us_{us['einsum']:.1f}"
        f"_interpret_us_{us['interpret']:.1f}"
        f"_cache_dequant_upcasts_{up_k}_vs_{up_e}"
        f"_cache_dots_{dot_k}_vs_{dot_e}"
        f"_fused_launches_{pc_k - pc_e}")


# ---------------------------------------------------------------------------
# Paged continuous batching: tok/s + mean TTFT on a mixed-length
# request trace, paged engine vs legacy contiguous-ring Server.  CPU
# wall clock is emulation; the structural columns (decode batch sizes,
# page-pool accounting, engine steps) carry the mechanism — the paged
# engine retires finished slots from the decode batch and admits
# mixed-depth requests without re-prefill (docs/continuous-batching.md).
# ---------------------------------------------------------------------------


def bench_serve_continuous(arch: str = "phi3-mini-3.8b"):
    from repro.configs.registry import get_config
    from repro.launch.serve import Server
    from repro.models.layers import init_tree
    from repro.models.transformer import model_defs
    from repro.serving import Engine, Request

    cfg = get_config(arch, smoke=True)
    params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens = [8, 24, 12, 30, 16, 20, 10, 28]       # mixed-length trace
    max_new, slots, max_len = 8, 4, 48

    def trace(rid0):
        return [Request(rid=rid0 + i,
                        prompt=rng.integers(0, cfg.vocab, size=n,
                                            dtype=np.int32),
                        max_new=max_new)
                for i, n in enumerate(lens)]

    stats = {}
    for tag in ("paged", "legacy"):
        # the warmup pass compiles prefill buckets + per-row-count
        # decode steps ON THE SAME INSTANCE (jit caches live on the
        # built step callables), so the timed pass measures steady
        # state
        if tag == "paged":
            drv = Engine(cfg, params, slots, max_len=max_len)
            serve = lambda rr: drv.run(rr, log=None)
        else:
            drv = Server(cfg, params, slots, max_len=max_len)
            serve = lambda rr: drv.run(rr, log=lambda *a: None)
        for run in ("warmup", "timed"):
            reqs = trace(0 if run == "warmup" else 100)
            t0 = time.perf_counter()
            serve(reqs)
            dt = time.perf_counter() - t0
        if tag == "paged":
            # metrics over the timed trace only (warmup paid compiles)
            ttft = float(np.mean([r.ttft for r in reqs]))
            extra = (f"_mean_ttft_ms_{1e3 * ttft:.0f}"
                     f"_pages_{drv.kv.allocator.num_pages}")
        else:
            extra = ""
        toks = sum(len(r.out) for r in reqs)
        stats[tag] = (dt / toks * 1e6, toks / dt, extra)
    us_p, tps_p, extra_p = stats["paged"]
    us_l, tps_l, _ = stats["legacy"]
    row("serve_continuous_paged_vs_legacy", us_p,
        f"tok_s_{tps_p:.1f}_legacy_tok_s_{tps_l:.1f}"
        f"_legacy_us_per_tok_{us_l:.1f}{extra_p}"
        f"_trace_{len(lens)}reqs_mixed_{min(lens)}to{max(lens)}")


# ---------------------------------------------------------------------------
# Prefix caching: a shared-system-prompt trace (every request repeats
# the same page-aligned prefix) served with the copy-on-write prefix
# cache vs cold (REPRO_PREFIX_CACHE-off equivalent).  CPU wall clock
# is emulation; the structural columns — prefill tokens skipped,
# physical pages shared, peak pool pages, CoW copies — carry the
# mechanism (docs/paged-attention.md).
# ---------------------------------------------------------------------------


def bench_serve_prefix(arch: str = "phi3-mini-3.8b"):
    from repro.configs.registry import get_config
    from repro.models.layers import init_tree
    from repro.models.transformer import model_defs
    from repro.serving import Engine, Request

    cfg = get_config(arch, smoke=True)
    params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # smoke scale of the 16-request/512-token-system-prompt scenario:
    # 8 requests sharing a 64-token (4-page) prefix + short distinct
    # tails, 4 slots
    n_reqs, prefix_tokens, max_new, slots, max_len = 8, 64, 6, 4, 96
    tails = [5, 8, 3, 7, 4, 6, 2, 8]

    def trace(rid0, prefix):
        return [Request(rid=rid0 + i,
                        prompt=np.concatenate(
                            [prefix, rng.integers(0, cfg.vocab, size=n,
                                                  dtype=np.int32)]),
                        max_new=max_new)
                for i, n in enumerate(tails[:n_reqs])]

    stats = {}
    for tag in ("shared", "cold"):
        eng = Engine(cfg, params, slots, max_len=max_len,
                     prefix_cache=(tag == "shared"))
        for run in ("warmup", "timed"):
            # warmup pays the jit compiles on a DIFFERENT prefix (no
            # cross-run hits); timed serves the shared-prompt trace
            prefix = rng.integers(0, cfg.vocab, size=prefix_tokens,
                                  dtype=np.int32)
            reqs = trace(0 if run == "warmup" else 100, prefix)
            skipped0 = eng.prefill_tokens_skipped
            shared0 = eng.pages_shared
            hits0 = eng.prefix_hits
            t0 = time.perf_counter()
            eng.run(reqs, log=None)
            dt = time.perf_counter() - t0
            eng.prune_finished()
        toks = sum(len(r.out) for r in reqs)
        stats[tag] = (dt / toks * 1e6, toks / dt, eng,
                      eng.prefill_tokens_skipped - skipped0,
                      eng.pages_shared - shared0,
                      eng.prefix_hits - hits0)
    us_s, tps_s, eng_s, skipped, shared, hits = stats["shared"]
    us_c, tps_c = stats["cold"][:2]
    row("serve_prefix_shared_vs_cold", us_s,
        f"tok_s_{tps_s:.1f}_cold_tok_s_{tps_c:.1f}"
        f"_cold_us_per_tok_{us_c:.1f}"
        f"_prefill_tokens_skipped_{skipped}"
        f"_pages_shared_{shared}"
        f"_prefix_hits_{hits}"
        f"_cow_copies_{eng_s.kv.cow_copies}"
        f"_peak_pool_pages_{eng_s.kv.allocator.peak_used}"
        f"_trace_{n_reqs}reqs_prefix_{prefix_tokens}tok")


# ---------------------------------------------------------------------------
# Scheduler v2 under heavy traffic: seeded Poisson arrivals with long-
# tail (Pareto) prompt lengths and a mid-trace burst, served open-
# loop.  A/B on the SAME seeded eval trace: v2 (chunked prefill +
# preemption + usage admission, the default) vs v1 (whole-prompt B=1
# bucketed prefill, worst-case reservation admission —
# REPRO_CHUNKED_PREFILL=0 REPRO_PREEMPTION=0).  The warmup trace uses
# a DIFFERENT seed on purpose: real traffic shifts, and v1 compiles a
# fresh prefill step for every 16-token prompt bucket it meets, so the
# eval trace's tail lengths hit v1 with multi-second jit stalls mid-
# serving and park every resident decode behind a B=1 long-prompt
# prefill.  v2's one mixed-step chunk shape is warm after any traffic
# — THE structural claim of chunked prefill.  CPU wall clock is
# emulation; the compile-stall asymmetry it surfaces is not (the
# prefill_shapes column counts v1's per-bucket compiles; v2 has 0).
# ---------------------------------------------------------------------------


def bench_serve_slo(arch: str = "phi3-mini-3.8b"):
    from repro.configs.registry import get_config
    from repro.models.layers import init_tree
    from repro.models.transformer import model_defs
    from repro.serving import Engine, Request

    cfg = get_config(arch, smoke=True)
    params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
    n_reqs, max_new, slots, max_len, pages = 14, 8, 4, 128, 18

    def trace(rid0, seed):
        rng = np.random.default_rng(seed)
        reqs, t = [], 0.0
        for i in range(n_reqs):
            # Poisson arrivals (exponential gaps) + a 3-request burst
            # landing together mid-trace; prompt lengths are long-
            # tailed (Pareto): mostly short, occasionally near-max
            if i not in (6, 7, 8):                # the burst
                t += float(rng.exponential(0.02))
            n = int(np.clip(6 + rng.pareto(1.5) * 10, 6,
                            max_len - max_new - 1))
            reqs.append(Request(
                rid=rid0 + i,
                prompt=rng.integers(0, cfg.vocab, size=n,
                                    dtype=np.int32),
                max_new=max_new, arrival_time=t))
        return reqs

    def pct(vals, q):
        return float(np.percentile([v for v in vals if v is not None],
                                   q))

    stats = {}
    for tag in ("v2", "v1"):
        env = {} if tag == "v2" else {"REPRO_CHUNKED_PREFILL": "0",
                                      "REPRO_PREEMPTION": "0"}
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            eng = Engine(cfg, params, slots, max_len=max_len,
                         num_pages=pages, prefix_cache=False)
            assert eng.chunked == (tag == "v2")
            for run, seed in (("warmup", 3), ("timed", 7)):
                # warmup serves a same-distribution, different-seed
                # trace on the same engine instance (steady state for
                # every shape that trace happens to cover); timed
                # serves the shared eval trace
                reqs = trace(0 if run == "warmup" else 100, seed)
                t0 = time.perf_counter()
                eng.run(reqs, log=None)
                dt = time.perf_counter() - t0
                eng.prune_finished()
            toks = sum(len(r.out) for r in reqs)
            try:
                prefill_shapes = eng.prefill._cache_size()
            except Exception:       # jit cache introspection moved
                prefill_shapes = -1
            stats[tag] = {
                "us": dt / toks * 1e6, "tok_s": toks / dt,
                "p50_ttft": pct([r.ttft for r in reqs], 50),
                "p99_ttft": pct([r.ttft for r in reqs], 99),
                "p50_tpot": pct([r.tpot for r in reqs], 50),
                "p99_tpot": pct([r.tpot for r in reqs], 99),
                "preempt": eng.preemptions,
                "chunks": eng.chunk_prefill_steps,
                "prefill_shapes": prefill_shapes,
            }
        finally:
            for k, v in saved.items():
                (os.environ.pop(k, None) if v is None
                 else os.environ.__setitem__(k, v))
    s2, s1 = stats["v2"], stats["v1"]
    row("serve_slo_v2_vs_v1", s2["us"],
        f"tok_s_{s2['tok_s']:.1f}_v1_tok_s_{s1['tok_s']:.1f}"
        f"_p99_ttft_ms_{1e3 * s2['p99_ttft']:.0f}"
        f"_v1_p99_ttft_ms_{1e3 * s1['p99_ttft']:.0f}"
        f"_p50_ttft_ms_{1e3 * s2['p50_ttft']:.0f}"
        f"_v1_p50_ttft_ms_{1e3 * s1['p50_ttft']:.0f}"
        f"_p99_tpot_ms_{1e3 * s2['p99_tpot']:.0f}"
        f"_v1_p99_tpot_ms_{1e3 * s1['p99_tpot']:.0f}"
        f"_prefill_shapes_{s2['prefill_shapes']}"
        f"_vs_{s1['prefill_shapes']}"
        f"_chunk_steps_{s2['chunks']}"
        f"_preemptions_{s2['preempt']}"
        f"_trace_{n_reqs}reqs_poisson_burst_pool_{pages}pages")


# ---------------------------------------------------------------------------
# Speculative multi-token decode: a repeated-suffix trace (each prompt
# tiles its own motif — the regime prompt-lookup drafting targets)
# served A/B: plain decode vs speculative verify at k in {2, 4}.  The
# speculative engines use a replay draft through the ``ModelDraft``
# hook (the baseline's own outputs, i.e. a perfectly-aligned small
# model — upper-bound acceptance), then the same engine re-serves the
# trace with the host-side ``NgramDraft`` for a model-free acceptance
# column.  Greedy verification guarantees token-for-token identical
# output for EVERY draft source, asserted here.  CPU wall clock is
# emulation; the structural columns carry the mechanism: accepted
# tokens per verify step (> 1 means each fp8-cache page read now
# produces multiple committed tokens) and the verify-step jaxpr's
# quantization-reduction count (the batched-query graph keeps the
# serving contract: only the 2 per-position K/V storage-write amaxes;
# docs/speculative-decoding.md).
# ---------------------------------------------------------------------------


def bench_spec_decode(arch: str = "phi3-mini-3.8b"):
    from repro.configs.registry import get_config
    from repro.core.introspect import count_quant_reductions
    from repro.models.layers import init_tree
    from repro.models.transformer import model_defs
    from repro.serving import Engine, ModelDraft, NgramDraft, Request
    from repro.train.steps import make_prefill_step, make_verify_step

    cfg = get_config(arch, smoke=True)
    params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    n_reqs, max_new, slots, max_len = 4, 12, 4, 64
    # 16-token prompts x 4 rows = one 64-token prefill chunk: all rows
    # admit together and stay lockstep, so per-step columns divide by
    # a constant batch (prefix cache off for the same reason — a
    # timed-trace prefix hit would change the admission timeline vs
    # warmup)
    prompts = [np.tile(rng.integers(0, cfg.vocab, size=4,
                                    dtype=np.int32), 4)
               for _ in range(n_reqs)]

    def trace(rid0):
        return [Request(rid=rid0 + i, prompt=p, max_new=max_new)
                for i, p in enumerate(prompts)]

    def serve(eng, rid0):
        reqs = trace(rid0)
        t0 = time.perf_counter()
        eng.run(reqs, log=None)
        dt = time.perf_counter() - t0
        eng.prune_finished()
        return reqs, dt

    # plain-decode baseline; its outputs double as the replay draft
    base = Engine(cfg, params, slots, max_len=max_len,
                  chunk_tokens=64, prefix_cache=False,
                  spec_decode=False)
    serve(base, 0)                               # warmup (compiles)
    breqs, bdt = serve(base, 100)
    btoks = sum(len(r.out) for r in breqs)
    truth = {tuple(int(x) for x in p): list(r.out)
             for p, r in zip(prompts, breqs)}

    def replay(ctx, k):
        for p, out in truth.items():
            if tuple(ctx[:len(p)]) == p:
                done = len(ctx) - len(p)
                return out[done:done + k]
        return []

    for k in (2, 4):
        eng = Engine(cfg, params, slots, max_len=max_len,
                     chunk_tokens=64, prefix_cache=False,
                     spec_decode=True, draft=ModelDraft(replay),
                     spec_k=k)
        assert eng.spec, "spec gate off on the smoke serving config"
        serve(eng, 0)                            # warmup
        s0 = eng.sched.summary()
        reqs, dt = serve(eng, 100)
        s1 = eng.sched.summary()
        toks = sum(len(r.out) for r in reqs)
        for b, r in zip(breqs, reqs):
            assert b.out == r.out, "speculative output diverged"
        vsteps = s1["spec_verify_steps"] - s0["spec_verify_steps"]
        acc = s1["spec_accepted"] - s0["spec_accepted"]
        drafted = s1["spec_drafted"] - s0["spec_drafted"]
        # committed tokens per verify step per resident row: 1
        # (correction) + accepted drafts
        tok_step = toks / max(1, vsteps) / slots
        # same trace through the host-side n-gram draft (no model) on
        # the warm engine; output identity must survive any proposal
        # stream
        eng.draft = NgramDraft()
        nreqs, _ = serve(eng, 200)
        for b, r in zip(breqs, nreqs):
            assert b.out == r.out, "n-gram output diverged"
        s2 = eng.sched.summary()
        ndraft = s2["spec_drafted"] - s1["spec_drafted"]
        nacc = s2["spec_accepted"] - s1["spec_accepted"]
        # structural: the (B, k) verify graph keeps the serving-graph
        # quantization contract (2 = K/V storage-write amaxes on the
        # fp8 cache; the cache itself is never re-reduced).  Traced
        # abstractly — the pool caches drain to None once the trace
        # retires, so shape the operands from a prefill eval_shape.
        cshape = jax.eval_shape(
            make_prefill_step(cfg, 16, scales=eng.scales,
                              act_scales=eng.act_scales),
            eng.params,
            {"tokens": jax.ShapeDtypeStruct((slots, 12),
                                            jnp.int32)})[1]
        jx = jax.make_jaxpr(make_verify_step(
            cfg, scales=eng.scales, act_scales=eng.act_scales))(
            eng.params, cshape,
            jax.ShapeDtypeStruct((slots, k), jnp.int32))
        row(f"serve_spec_decode_k{k}", dt / toks * 1e6,
            f"tok_s_{toks / dt:.1f}_base_tok_s_{btoks / bdt:.1f}"
            f"_tok_per_step_{tok_step:.2f}"
            f"_accept_rate_{acc / max(1, drafted):.2f}"
            f"_verify_steps_{vsteps}"
            f"_ngram_accept_rate_{nacc / max(1, ndraft):.2f}"
            f"_verify_quant_reductions_{count_quant_reductions(jx)}"
            f"_trace_{n_reqs}reqs_repeated_suffix_max_new_{max_new}")


# ---------------------------------------------------------------------------
# Observability overhead: the SAME trace served all-telemetry-on
# (REPRO_QUANT_HEALTH=1 + span tracing) vs all-off.  The contract
# (docs/observability.md) is that off is FREE — the off-path jaxpr is
# byte-identical, asserted in tests/test_obs.py — and that on costs
# under a few percent of tok/s: the health stats are tiny per-site
# reductions riding existing steps, the spans are host-side
# perf_counter pairs.  CPU wall clock is emulation; overhead_pct is
# the structural column.
# ---------------------------------------------------------------------------


def bench_obs_overhead(arch: str = "phi3-mini-3.8b"):
    from repro.configs.registry import get_config
    from repro.models.layers import init_tree
    from repro.models.transformer import model_defs
    from repro.obs.trace import get_tracer
    from repro.serving import Engine, Request

    cfg = get_config(arch, smoke=True)
    params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    n_reqs, max_new, slots, max_len = 8, 10, 4, 64
    prompts = [rng.integers(0, cfg.vocab,
                            size=int(rng.integers(8, 24)),
                            dtype=np.int32) for _ in range(n_reqs)]

    def serve(eng, rid0):
        reqs = [Request(rid=rid0 + i, prompt=p, max_new=max_new)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        eng.run(reqs, log=None)
        dt = time.perf_counter() - t0
        eng.prune_finished()
        return sum(len(r.out) for r in reqs), dt, reqs

    stats = {}
    outs = {}
    saved = os.environ.get("REPRO_QUANT_HEALTH")
    tracer = get_tracer()
    for tag in ("off", "on"):
        if tag == "on":
            os.environ["REPRO_QUANT_HEALTH"] = "1"
            tracer.clear()
            tracer.enable()       # ring buffer only, no output path
        else:
            os.environ.pop("REPRO_QUANT_HEALTH", None)
        try:
            eng = Engine(cfg, params, slots, max_len=max_len,
                         prefix_cache=False)
            assert eng.health == (tag == "on")
            serve(eng, 0)                         # warmup (compiles)
            toks, dt, reqs = serve(eng, 100)
            stats[tag] = {"us": dt / toks * 1e6, "tok_s": toks / dt}
            outs[tag] = [r.out for r in reqs]
            if tag == "on":
                s = eng.stats()
                stats[tag]["sites"] = len(s["quant_health"]["sites"])
                stats[tag]["events"] = len(tracer)
        finally:
            if tag == "on":
                tracer.disable()
            (os.environ.pop("REPRO_QUANT_HEALTH", None) if saved is None
             else os.environ.__setitem__("REPRO_QUANT_HEALTH", saved))
    assert outs["on"] == outs["off"], \
        "telemetry changed the greedy output stream"
    on, off = stats["on"], stats["off"]
    overhead = (on["us"] - off["us"]) / off["us"] * 100
    row("serve_obs_overhead", on["us"],
        f"tok_s_on_{on['tok_s']:.1f}_tok_s_off_{off['tok_s']:.1f}"
        f"_overhead_pct_{overhead:.1f}"
        f"_health_sites_{on['sites']}"
        f"_trace_events_{on['events']}"
        f"_trace_{n_reqs}reqs_max_new_{max_new}")


def _write_json(path: str, rows=None) -> None:
    import json

    rows = _ROWS if rows is None else rows
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {len(rows)} rows to {path}", flush=True)


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced set: dispatch backends + MoE grouped "
                         "A/B + per-mode train-step timings (CI smoke)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as JSON records (machine-"
                         "readable perf trajectory; --smoke defaults "
                         "to BENCH_moe.json)")
    args = ap.parse_args(argv)
    if args.smoke and args.json is None:
        args.json = "BENCH_moe.json"

    print("name,us_per_call,derived")
    if args.smoke:
        bench_dispatch_backends(m=128, n=128, k=512)
        bench_moe_grouped()
        bench_table2_throughput(B=4, S=64, iters=2)
        bench_serve_prequant()
        bench_decode_reduction_free()
        bench_decode_attn()
        bench_serve_continuous()
        bench_serve_prefix()
        bench_serve_slo()
        bench_spec_decode()
        bench_obs_overhead()
        _write_json(args.json)
        # serving / decode-attention rows also land in their own
        # artifacts (consumed by benchmarks/report.py --trajectory
        # alongside BENCH_moe.json)
        _write_json("BENCH_serve.json",
                    [r for r in _ROWS if r["name"].startswith("serve_")])
        _write_json("BENCH_decode.json",
                    [r for r in _ROWS if r["name"].startswith("decode_")])
        return
    bench_table1_autoscale()
    bench_table7_snr()
    bench_dispatch_backends()
    bench_moe_grouped()
    bench_table6_gemm()
    bench_table5_memory_comm()
    bench_table2_throughput()
    bench_table9_interval()
    bench_serve_prequant()
    bench_decode_reduction_free()
    bench_decode_attn()
    bench_serve_continuous()
    bench_serve_prefix()
    bench_serve_slo()
    bench_spec_decode()
    bench_obs_overhead()
    if args.json:
        _write_json(args.json)


if __name__ == "__main__":
    main()
