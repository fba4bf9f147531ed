#!/usr/bin/env python3
"""Bring-up check of the MOSS system on a TPU, through the entry points
a user calls.  Not a benchmark: the times it prints are from one short
run, compilation excluded where it says so.

  python chip_smoke.py             # one chip (a TPU v5e)
  python chip_smoke.py --chips 4   # four chips: data-parallel training

One chip, three phases in this one process:

1. Training (``repro.launch.train.train``): ``olmo-7b`` with the MOSS
   recipe at published widths (d 4096, d_ff 11008, vocab 50304), depth
   cut to 2 layers, batch 2 x seq 2048, 3 steps on the Pallas kernels.
   The losses must be finite and fall by step 3, and the compiled step
   must hold Pallas kernel calls (``tpu_custom_call``).
2. Forward check: one forward of the trained params on one batch, each
   MOSS GEMM run through the Pallas kernel and through XLA's jnp
   reference on the same operands; every kernel output within
   KERNEL_RTOL relative L2 of the reference's.
3. Serving (``repro.serving.Engine``, its defaults: fp8 weights, fp8 KV
   cache in floating pages, chunked prefill, delayed activation
   scales): ``phi3-mini-3.8b`` at published widths (d 3072, Dh 96,
   vocab 32064) cut to 8 layers serves 8 seeded requests, each to
   exactly MAX_NEW tokens; the decode step must hold Pallas calls.

``--chips 4``: only the data-parallel path — the same training job on
a (4, 1) data x model mesh, once with the fp8-compressed gradient
all-reduce (paper §4.4) and once with the uncompressed reduction; the
losses must agree within COMM_RTOL, the fp8 run must have rounded
(FP8_RESIDUAL_MIN), and parameters and batch must be spread over all
four devices.

The script exits non-zero and prints no result unless JAX sees a TPU
and the kernel backend is ``pallas``.  Its last stdout line is the JSON
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_cache/`` at the repository root.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import re
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"

# The jobs, fixed: published widths, depth cut to fit one 16 GB chip.
TRAIN_ARCH, TRAIN_LAYERS, TRAIN_BATCH, SEQ, STEPS = "olmo-7b", 2, 2, 2048, 3
DP_BATCH = 8                        # --chips 4: 2 rows per chip
SERVE_ARCH, SERVE_LAYERS = "phi3-mini-3.8b", 8
N_REQUESTS, MAX_NEW, PROMPT_LEN = 8, 64, (64, 128)

# Forward check, per GEMM: relative L2 distance of a Pallas kernel's
# output from the jnp reference's on the same operands.  The two make
# the same fp8 payload and exponents (compared bitwise, too) and differ
# only in the f32 accumulation order of the dot: ~1e-7.  The smallest
# quantization fault — one 32-wide micro-group of one row off by one
# exponent — reads ~sqrt(32/K)/sqrt(M): 8e-4 to 1.4e-3 at the olmo-7b
# GEMMs (M 4096, K 4096 or 11008); a whole tensor mis-scaled reads ~1.
# The limit sits between: 42x the worst sound reading on a v5e
# (2.4e-7), 84x below the smallest such fault.
# Free-running logits are no such gate: a one-ulp difference upstream
# flips fp8 roundings downstream, and the flips compound over the
# layers — on a v5e the logits of one Pallas program compiled with and
# without XLA's excess precision differ by 33% (PERF.md, Findings).
KERNEL_RTOL = 1e-5
# fp8 (E5M2 + error feedback) vs uncompressed gradient reduction: per
# step loss gap relative to the loss.  Three AdamW steps on the same
# batches move the two runs apart only where E5M2 rounds or flushes a
# gradient element; the loss itself falls by far more than this.
COMM_RTOL = 2e-2
# --chips 4, fp8 reduction: norm of the error-feedback residual the last
# step leaves, relative to that step's gradient norm.  E5M2 keeps 2
# mantissa bits, so ~5e-2 (5.3e-2 for a Gaussian tensor on a v5e); a
# program that skipped the fp8 rounding reads ~1e-7 (7e-8 measured).
FP8_RESIDUAL_MIN = 1e-3


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _require(ok: bool, what) -> None:
    """A failed check raises (``assert`` would vanish under ``-O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _log(lines: list[str]):
    def log(msg: str) -> None:
        print(msg, flush=True)
        lines.append(msg)
    return log


def _logged(lines: list[str], pattern: str, what: str) -> list[float]:
    vals = [float(m.group(1)) for line in lines
            if (m := re.search(pattern, line))]
    _require(vals, f"train() logged no {what}")
    return vals


def _check_losses(hist, what: str) -> list[float]:
    losses = [loss for _, loss in hist]
    _require(all(math.isfinite(v) for v in losses),
             f"{what}: non-finite loss {losses}")
    _require(losses[-1] < losses[0], f"{what}: loss did not fall {losses}")
    return losses


def train_phase():
    """STEPS MOSS steps on one device; returns the trained params."""
    import jax
    from repro.launch.train import train

    lines: list[str] = []
    state, hist = train(TRAIN_ARCH, smoke=False, layers=TRAIN_LAYERS,
                        steps=STEPS, batch=TRAIN_BATCH, seq=SEQ,
                        quant="moss", lr=1e-3, warmup=0, log_every=1,
                        log=_log(lines))
    losses = _check_losses(hist, "train")
    n_kernels = int(_logged(lines, r"(\d+) Pallas kernel calls",
                            "compiled-step kernel count")[0])
    stats = jax.devices()[0].memory_stats() or {}
    print(f"train: losses {losses}, Pallas calls in the step {n_kernels}, "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}",
          flush=True)
    _require(n_kernels > 0, "train step holds no Pallas kernel call")
    return state.params


def forward_check(params) -> float:
    """One forward of ``params`` on one batch in which every MOSS GEMM
    runs twice on the same operands, through the Pallas kernel and
    through the jnp reference.  The forward goes on with the
    reference's output, so a difference at one GEMM never reaches the
    next: each kernel call is compared at the operands the model gives
    it.  Returns the largest relative L2 distance."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_config
    from repro.core.introspect import count_pallas_calls
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.kernels import dispatch
    from repro.launch.train import quant_from_name
    from repro.models.layers import quant_mask_tree, wrap_qt_nojit
    from repro.models.transformer import forward, model_defs

    cfg = get_config(TRAIN_ARCH, layers=TRAIN_LAYERS).replace(
        quant=quant_from_name("moss"))
    mask = quant_mask_tree(model_defs(cfg))
    b = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=SEQ,
                               global_batch=TRAIN_BATCH, seed=1)
                    ).batch_for_step(0)
    fused = dispatch.fused_quant_matmul
    readings: list[tuple[str, np.ndarray]] = []

    def both(x2d, wq, **kw):
        x2d = jax.lax.optimization_barrier(x2d)     # one operand for both
        y, xq = fused(x2d, wq, backend="ref", **kw)
        yk, xqk = fused(x2d, wq, backend="pallas", **kw)
        bits = lambda a: jax.lax.bitcast_convert_type(a, jnp.uint8)
        stats = jnp.stack([
            jnp.sum(jnp.square(yk - y)), jnp.sum(jnp.square(y)),
            jnp.sum(bits(xqk.q) != bits(xq.q)).astype(jnp.float32),
            jnp.sum(xqk.sexp != xq.sexp).astype(jnp.float32)])
        site = f"{x2d.shape[0]}x{x2d.shape[1]}x{wq.q.shape[1]}"
        jax.debug.callback(lambda v: readings.append((site, np.asarray(v))),
                           stats)
        return y, xq

    def logits(p, b):
        out, _, _ = forward(cfg, cfg.quant, wrap_qt_nojit(p, mask), b,
                            mode="train")
        return out

    with mock.patch.object(dispatch, "fused_quant_matmul", both):
        # jitted pieces traced by the train step are reused otherwise
        jax.clear_caches()
        compiled = jax.jit(logits).lower(params, b).compile()
    jax.block_until_ready(compiled(params, b))
    jax.effects_barrier()
    n_kernels = count_pallas_calls(compiled)
    rels = [float(np.sqrt(d / r)) for _, (d, r, _, _) in readings]
    worst = max(rels, default=float("nan"))
    for site, (d, r, nq, ne) in readings:
        print(f"forward check {site}: rel L2 {np.sqrt(d / r):.3e}, "
              f"payload mismatches {int(nq)}, exponent mismatches "
              f"{int(ne)}", flush=True)
    print(f"forward check: {len(readings)} GEMMs, worst rel L2 "
          f"{worst:.3e} (tolerance {KERNEL_RTOL:g}), Pallas calls "
          f"{n_kernels}", flush=True)
    _require(n_kernels > 0 and readings, "no GEMM ran through a kernel")
    _require(all(math.isfinite(r) and r <= KERNEL_RTOL for r in rels),
             f"rel L2 {rels}")
    return worst


def serve_phase():
    """N_REQUESTS seeded requests through the Engine, twice (the first
    run compiles); returns steady tok/s of the second."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_config
    from repro.core.introspect import count_pallas_calls
    from repro.models.layers import init_tree
    from repro.models.transformer import model_defs
    from repro.serving import Engine, Request

    cfg = get_config(SERVE_ARCH, layers=SERVE_LAYERS)
    engine = Engine(cfg, init_tree(model_defs(cfg), jax.random.PRNGKey(0)),
                    N_REQUESTS, max_len=PROMPT_LEN[1] + MAX_NEW + 1)
    rng = np.random.default_rng(0)
    rate, n_kernels = None, 0
    for run in ("compile", "steady"):
        reqs = [Request(rid=i + (0 if run == "compile" else N_REQUESTS),
                        prompt=rng.integers(0, cfg.vocab,
                                            int(rng.integers(*PROMPT_LEN)),
                                            dtype=np.int32),
                        max_new=MAX_NEW)
                for i in range(N_REQUESTS)]
        t0 = time.time()
        engine.submit(reqs)
        if run == "compile":
            # step until every request decodes in one batch, then count
            # the kernels in that decode step (compiled by now)
            for _ in range(8 * N_REQUESTS):
                if len(engine.kv.rows) == N_REQUESTS:
                    break
                engine.step()
            _require(len(engine.kv.rows) == N_REQUESTS,
                     f"decode batch never held all {N_REQUESTS} requests")
            feed = jnp.zeros((N_REQUESTS, 1), jnp.int32)
            n_kernels = count_pallas_calls(engine.decode.lower(
                engine.params, engine.kv.caches, feed).compile())
        done = engine.run()
        dt = time.time() - t0
        _require(len(done) == N_REQUESTS,
                 f"{run}: {len(done)} of {N_REQUESTS} requests finished")
        _require(all(len(r.out) == MAX_NEW for r in reqs),
                 f"{run}: tokens per request {[len(r.out) for r in reqs]}")
        rate = N_REQUESTS * MAX_NEW / dt
        print(f"serve ({run} run): {N_REQUESTS} requests x {MAX_NEW} "
              f"tokens in {dt:.2f} s, {rate:.1f} tok/s", flush=True)
    print(f"serve: Pallas calls in the decode step {n_kernels}", flush=True)
    _require(n_kernels > 0, "decode step holds no Pallas kernel call")
    return rate


def four_chip_phase():
    """Data-parallel MOSS training on a (n, 1) mesh over every device,
    fp8-compressed vs uncompressed gradient reduction."""
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train

    mesh = make_host_mesh(model=1)
    devices = set(jax.devices())
    losses = {}
    for fp8 in (True, False):
        lines: list[str] = []
        state, hist = train(TRAIN_ARCH, smoke=False, layers=TRAIN_LAYERS,
                            steps=STEPS, batch=DP_BATCH, seq=SEQ,
                            quant="moss", lr=1e-3, warmup=0, log_every=1,
                            grad_comm_fp8=fp8, mesh=mesh, log=_log(lines))
        losses[fp8] = _check_losses(hist, f"grad_comm_fp8={fp8}")
        if fp8:
            gnorm = _logged(lines, r"gnorm ([\d.]+)", "gradient norm")[-1]
            res = math.sqrt(sum(float(jnp.sum(jnp.square(r))) for r in
                                jax.tree.leaves(state.comm_residual)))
            print(f"fp8 reduction: error-feedback residual {res / gnorm:.3e}"
                  f" of the gradient norm (at least {FP8_RESIDUAL_MIN:g})",
                  flush=True)
            _require(res / gnorm >= FP8_RESIDUAL_MIN,
                     f"fp8 reduction did not round: residual {res / gnorm}")
        leaves = jax.tree.leaves(state.params)
        on = set().union(*(leaf.sharding.device_set for leaf in leaves))
        split = sum(leaf.addressable_shards[0].data.shape != leaf.shape
                    for leaf in leaves)
        print(f"grad_comm_fp8={fp8}: losses {losses[fp8]}, params on "
              f"{len(on)} devices, {split}/{len(leaves)} leaves split",
              flush=True)
        _require(on == devices, f"params on {on}")
        del state
    vocab = get_config(TRAIN_ARCH).vocab
    b = SyntheticLM(DataConfig(vocab=vocab, seq_len=SEQ,
                               global_batch=DP_BATCH)
                    ).batch_for_step(0, mesh)["tokens"]
    rows = {s.device: s.data.shape[0] for s in b.addressable_shards}
    print(f"batch rows per device {sorted(rows.values())}", flush=True)
    _require(set(rows) == devices and max(rows.values()) < DP_BATCH,
             f"batch rows per device {rows}")
    gaps = [abs(f - u) / u for f, u in zip(losses[True], losses[False])]
    print(f"fp8 vs uncompressed reduction: relative loss gaps {gaps} "
          f"(tolerance {COMM_RTOL:g})", flush=True)
    _require(max(gaps) <= COMM_RTOL, f"loss gaps {gaps}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        _fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, str(SRC))

    import jax
    from repro.core.runtime_flags import kernel_backend
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if kernel_backend() != "pallas":
        _fail(f"kernel backend is {kernel_backend()!r}, not 'pallas' "
              f"(REPRO_KERNELS={os.environ.get('REPRO_KERNELS')!r})")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices, "
              f"JAX finds {len(devices)}")
    print(f"devices: {len(devices)} x {devices[0].device_kind}; "
          f"compile cache {enable_compile_cache()}", flush=True)

    if args.chips == 4:
        four_chip_phase()
    else:
        params = train_phase()
        forward_check(params)
        del params
        serve_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
