"""Serving CLI: a thin driver over the paged continuous-batching
engine (``repro.serving.Engine``, the default) with the legacy
contiguous-ring ``Server`` as the ``REPRO_SERVE_PAGED=0`` fallback.

The engine layer (docs/continuous-batching.md) owns admission,
page-exhaustion backpressure, per-slot depths and retirement; both
paths share the fp8-at-rest serving stack: weights pre-quantized once
at build (``PrequantParams``; ``REPRO_SERVE_PREQUANT=0`` falls back to
cached-scale in-graph quantization), the fp8 KV cache default
(``REPRO_KV_CACHE=bf16`` restores bf16) and the fused Pallas decode-
attention kernel (``REPRO_DECODE_ATTN=einsum`` pins the scale-folding
einsum path) — see docs/serving.md.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
      --smoke --requests 16 --max-new 32
  # published widths, depth cut to fit one chip:
  PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
      --full --layers 8 --requests 8 --slots 8 --max-new 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.runtime_flags import serve_paged
from repro.launch.compile_cache import enable_compile_cache
from repro.models.layers import init_tree
from repro.models.transformer import init_caches, model_defs
from repro.serving import Engine, Request, greedy_sample, prepare_weights
from repro.serving.engine import calibrate_serving
from repro.serving.paged_cache import write_row
from repro.serving.scheduler import RequestState, hit_stop
from repro.train.steps import make_decode_step, make_prefill_step

__all__ = ["Engine", "Request", "Server", "greedy_sample", "main"]


class Server:
    """Legacy continuous batching: a FIXED batch of B decode slots over
    one slot-shaped KV cache, FIFO refill — no page accounting, no
    scheduler, no retirement of finished rows from the decode batch
    (the paged ``Engine`` adds all three; this class is the
    ``REPRO_SERVE_PAGED=0`` fallback).

    Correctness note: the cache is allocated ONCE at build with
    per-slot lengths (``init_caches(..., per_slot=True)`` — ``idx`` is
    a (B,) vector), so a refilled request whose prefill length differs
    from the incumbents keeps every slot's depth, ring position and
    validity mask intact.  The historical single shared scalar ``idx``
    was silently clobbered with the newest request's offset on every
    refill, corrupting incumbent slots at different depths."""

    def __init__(self, cfg, params, batch_slots: int, max_len: int):
        self.cfg = cfg
        self.B = batch_slots
        self.max_len = max_len
        self.params, self.scales, self.prequant = \
            prepare_weights(cfg, params)
        self.act_scales = calibrate_serving(cfg, self.params,
                                            self.scales)
        self._build_steps()
        # slot-shaped caches at build: B rows, per-slot idx vector
        self.caches = init_caches(cfg, batch_slots, max_len,
                                  per_slot=True)
        self.slots: list[Request | None] = [None] * batch_slots

    def _build_steps(self):
        self.prefill = jax.jit(
            make_prefill_step(self.cfg, self.max_len,
                              scales=self.scales,
                              act_scales=self.act_scales))
        self.decode = jax.jit(
            make_decode_step(self.cfg, scales=self.scales,
                             act_scales=self.act_scales),
            donate_argnums=(1,))

    def refresh_act_scales(self, tokens=None, margin=None):
        """Re-calibrate delayed activation scales and rebuild the
        jitted steps (see ``Engine.refresh_act_scales``)."""
        if self.act_scales is None:
            return None
        from repro.core.actscale import calibrate_act_scales

        kw = {} if margin is None else {"margin": margin}
        self.act_scales = calibrate_act_scales(
            self.cfg, self.params, self.scales, tokens=tokens, **kw)
        self._build_steps()
        return self.act_scales

    def _prefill_request(self, req: Request, slot: int):
        req.state = RequestState.RUNNING
        toks = jnp.asarray(req.prompt, jnp.int32)[None]
        logits, one = self.prefill(self.params, {"tokens": toks})
        self._on_token(req, int(greedy_sample(logits)[0]))
        # merge this request's single-row cache into slot `slot`,
        # stamping ITS prompt length into idx[slot] only — incumbent
        # slots at other depths are untouched
        self.caches = write_row(self.caches, one, jnp.int32(slot),
                                jnp.int32(len(req.prompt)))

    def _on_token(self, req: Request, token: int):
        req.out.append(token)
        if hit_stop(req, token):
            req.state = RequestState.FINISHED

    def step(self, queue: list[Request]):
        # refill free slots
        for i in range(self.B):
            if self.slots[i] is None or self.slots[i].done:
                if queue:
                    req = queue.pop(0)
                    self._prefill_request(req, i)
                    self.slots[i] = req
        # batched decode for active slots (finished slots still ride
        # along at fixed B — the paged engine retires them instead)
        active = [i for i in range(self.B)
                  if self.slots[i] is not None and not self.slots[i].done]
        if not active:
            return
        last = np.zeros((self.B, 1), np.int32)
        for i in active:
            last[i, 0] = self.slots[i].out[-1]
        logits, self.caches = self.decode(self.params, self.caches,
                                          jnp.asarray(last))
        nxt = np.asarray(greedy_sample(logits))
        for i in active:
            self._on_token(self.slots[i], int(nxt[i]))

    def run(self, requests: list[Request], log=print):
        queue = list(requests)
        t0 = time.time()
        steps = 0
        while queue or any(s is not None and not s.done
                           for s in self.slots):
            self.step(queue)
            steps += 1
            if steps > 10_000:
                raise RuntimeError("serving loop did not converge")
        dt = time.time() - t0
        toks = sum(len(r.out) for r in requests)
        log(f"served {len(requests)} requests, {toks} tokens in "
            f"{dt:.2f}s ({toks/dt:,.1f} tok/s, {steps} engine steps)")
        return requests


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="published widths (default: the smoke config)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page pool budget (default: fully backed "
                         "slots); smaller values exercise admission "
                         "backpressure")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--legacy", action="store_true",
                    help="force the legacy contiguous-ring Server "
                         "(same as REPRO_SERVE_PAGED=0)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the metrics-registry snapshot as JSON "
                         "at exit (docs/observability.md)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record engine step spans and write the "
                         "Chrome-trace JSON at exit (same as "
                         "REPRO_TRACE=PATH)")
    args = ap.parse_args()
    enable_compile_cache()

    tracer = None
    if args.trace_out:
        from repro.obs.trace import get_tracer

        tracer = get_tracer().enable(path=args.trace_out)

    cfg = get_config(args.arch, smoke=args.smoke, layers=args.layers)
    defs = model_defs(cfg)
    params = init_tree(defs, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    # mixed prompt lengths: the paged engine serves them concurrently
    # at their true depths (the legacy ring also stays correct now —
    # per-slot lengths — it just never retires finished rows)
    lens = rng.integers(max(4, args.prompt_len // 2),
                        args.prompt_len + 1, size=args.requests)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=int(n),
                                        dtype=np.int32),
                    max_new=args.max_new)
            for i, n in enumerate(lens)]
    max_len = args.prompt_len + args.max_new + 1
    if args.legacy or not serve_paged():
        print("path: legacy contiguous-ring Server "
              "(REPRO_SERVE_PAGED=0)")
        server = Server(cfg, params, args.slots, max_len=max_len)
        server.run(reqs)
    else:
        print("path: paged continuous-batching engine "
              "(docs/continuous-batching.md)")
        engine = Engine(cfg, params, args.slots, max_len=max_len,
                        page_size=args.page_size,
                        num_pages=args.num_pages)
        engine.run(reqs)
        s = engine.stats()       # publishes engine/sched registry rows
        qh = s.get("quant_health")
        if qh is not None:
            print(f"quant health: {len(qh['sites'])} sites, "
                  f"refresh_recommended={qh['refresh_recommended']}")
    if tracer is not None:
        print(f"trace: {tracer.save()} ({len(tracer)} events)")
    if args.metrics_out:
        from repro.obs.metrics import get_registry

        with open(args.metrics_out, "w") as f:
            f.write(get_registry().to_json(indent=2))
        print(f"metrics: {args.metrics_out}")


if __name__ == "__main__":
    main()
