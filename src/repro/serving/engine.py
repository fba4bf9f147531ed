"""Continuous-batching serving engine over the paged KV cache — the
layer between the model's step functions and the ``launch/serve.py``
CLI (docs/continuous-batching.md).

Scheduler v2 (the default, ``REPRO_CHUNKED_PREFILL``): one engine
``step()`` is

  1. retire finished requests (release pages, shrink them out of the
     decode batch);
  2. swap preempted requests back in when their pages fit again
     (FIFO over the preempted deque — they hold finished work);
  3. up to ``Scheduler.chunk_budget()`` CHUNKED-PREFILL steps: the
     staging request's next ``chunk_tokens`` prompt tokens run as one
     (1, chunk) decode-mode step writing at the request's own depth
     into its own pages (block-table scatter; padded tail garbage
     lands in the trash page), attending over its already-resident
     history.  The final chunk's last real logit is the request's
     first output token (stamps TTFT) and the request joins the
     decode batch at its true prompt length;
  4. one batched (B, 1) decode over the resident rows, every row at
     its own depth — or, with speculative decode on
     (``REPRO_SPEC_DECODE=1`` / ``Engine(spec_decode=True)``), one
     (B, k) VERIFY step: each row gambles up to ``k-1`` host-proposed
     draft tokens (greedy n-gram prompt lookup by default, or an
     injected draft model), all k positions run through ONE forward
     over the fp8 KV cache, and the longest draft prefix matching the
     model's own argmaxes commits together with the model's
     correction token.  Greedy output is token-for-token identical to
     plain decode; rejected drafts truncate for free (the per-slot
     length vector is the truth, docs/speculative-decoding.md).  The
     draft length adapts to the measured accept rate
     (``Scheduler.draft_len``).

One compiled mixed-step graph serves both shapes (3) and (4) — there
is no per-prompt-bucket prefill compile and no B=1 whole-prompt
stall; a long prompt's chunks interleave with other requests' decode
steps.  A prefix-cache hit (float placement, ``REPRO_PREFIX_CACHE``)
maps its page-aligned shared prefix copy-on-write and chunk-prefills
only the UNSHARED SUFFIX at an offset — the replay-through-decode
path this replaces is gone.

Admission is usage-based when preemption is on
(``REPRO_PREEMPTION``): a request reserves its prompt plus one page
of headroom instead of the worst case, and outgrowing the
reservation extends it page by page.  When an extension finds the
pool dry, the engine PREEMPTS: ``Scheduler.pick_victim`` chooses the
resident request with the most TPOT headroom, its pages are copied
to host (payloads and scales, bitwise) and freed, and the victim
parks in a deque until retirement frees enough pages to swap back in
and resume at its recorded depth.  A request whose worst case
exceeds the whole pool is still rejected at submit — so a lone
resident request always fits and the preempt-retry loop terminates.

``Request.arrival_time`` turns ``run()`` into an open-loop driver:
requests are submitted (and their TTFT clocks started) at their
trace offsets instead of all at once.

The v1 path (whole-prompt bucketed B=1 prefill, reservation-based
admission, no preemption) is kept verbatim behind
``REPRO_CHUNKED_PREFILL=0`` as the A/B baseline, and is the
automatic fallback for families the mixed step cannot serve
(recurrent states, MLA latent caches, windowed rings).

Weights are pre-quantized at build exactly like the legacy Server
(``PrequantParams``; ``REPRO_SERVE_PREQUANT=0`` falls back to cached
scales).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.actscale import calibrate_act_scales
from repro.core.runtime_flags import (
    chunked_prefill,
    paged_placement,
    quant_health,
    quant_health_every,
    serve_delayed_act,
    serve_preemption,
    serve_prefix_cache,
    serve_prequant,
)
from repro.core.runtime_flags import spec_decode as spec_decode_flag
from repro.models.transformer import (
    chunk_prefill_supported,
    init_caches,
    map_cache_nodes,
    paged_decode_supported,
    spec_verify_supported,
)
from repro.train.steps import (
    make_decode_step,
    make_prefill_step,
    make_verify_step,
    prequantize_params,
    serve_weight_scales,
)

from repro.obs.metrics import count_compiles, get_registry
from repro.obs.trace import instant, span

from .paged_cache import (
    PAGE_SIZE,
    FloatingPageCache,
    PagedKVCache,
    PageExhausted,
    SlotCapacityExceeded,
    page_keys,
)
from .scheduler import Request, RequestState, Scheduler, SLOTargets
from .spec import DraftSource, NgramDraft

PROMPT_BUCKET = 16
CHUNK_TOKENS = 32


def prepare_weights(cfg, params):
    """Build-time weight preparation shared by the engine and the
    legacy Server: pre-quantized fp8 payloads + scales by default,
    cached per-tensor scales under ``REPRO_SERVE_PREQUANT=0``.
    Returns (params_tree, scales, prequant_or_None)."""
    prequant = (prequantize_params(cfg, params)
                if serve_prequant() else None)
    if prequant is not None:
        return prequant.qweights, prequant.scales, prequant
    return params, serve_weight_scales(cfg, params), None


def calibrate_serving(cfg, params, scales):
    """Build-time delayed-activation-scale calibration shared by the
    engine and the legacy Server: one eager forward over the
    calibration prompt (``repro.core.actscale``) when
    ``REPRO_SERVE_DELAYED_ACT`` is on, else None (just-in-time
    activation scaling, the pre-delayed graphs bitwise)."""
    if not serve_delayed_act():
        return None
    return calibrate_act_scales(cfg, params, scales)


def greedy_sample(logits):
    """(B, 1, V) last-position logits -> (B,) next token ids."""
    return jnp.argmax(logits[:, -1], axis=-1)


@dataclasses.dataclass
class PrefixPlan:
    """Admission-time prefix-cache decision for one request.

    ``keys``        chained page hashes of every FULL prompt page
    ``pages``       physical pages hit (longest registered prefix run,
                    clamped to the prompt's full pages) — empty = cold
    ``suffix_from`` first prompt position that chunk-prefills:
                    ``min(n_shared*T, prompt_len - 1)`` (a FULL hit
                    still runs the last prompt token through a chunk,
                    whose sample is the first output)
    ``cow_slack``   1 when the suffix's first write lands inside a
                    shared page (full page-aligned hit), else 0 —
                    reserved so the copy-on-write can always
                    allocate"""
    keys: list
    pages: list
    suffix_from: int
    cow_slack: int


@dataclasses.dataclass
class _Staging:
    """The one request currently chunk-prefilling: its pages are
    admitted but it has no decode-batch row until the last chunk."""
    req: Request
    pos: int                  # next prompt position to chunk-prefill
    keys: list | None         # page hashes to publish at attach (float)
    row_cache: dict | None    # detached one-row caches (identity only)


class Engine:
    """Paged-KV continuous-batching engine (see module docstring)."""

    def __init__(self, cfg, params, num_slots: int, max_len: int, *,
                 page_size: int = PAGE_SIZE,
                 num_pages: int | None = None,
                 prompt_bucket: int = PROMPT_BUCKET,
                 chunk_tokens: int = CHUNK_TOKENS,
                 eos_id: int | None = None,
                 prefix_cache: bool | None = None,
                 slo: SLOTargets | None = None,
                 spec_decode: bool | None = None,
                 draft: DraftSource | None = None,
                 spec_k: int = 4):
        count_compiles()
        if cfg.input_mode != "tokens":
            raise ValueError(
                f"serving engine drives token models; {cfg.name} has "
                f"input_mode={cfg.input_mode!r}")
        self.cfg = cfg
        self.max_len = max_len
        self.num_slots = num_slots
        # recurrent state (RWKV / RG-LRU) integrates every prefill
        # token — padded garbage would corrupt it (attention caches
        # just mask it), so those families prefill at exact length
        # (one compile per distinct prompt length)
        self.prompt_bucket = (1 if cfg.family in ("ssm", "hybrid")
                              else prompt_bucket)
        self.eos_id = eos_id
        self.params, self.scales, self.prequant = \
            prepare_weights(cfg, params)
        self.act_scales = calibrate_serving(cfg, self.params,
                                            self.scales)
        self._build_steps()
        self.float_pages = (paged_placement() == "float"
                            and paged_decode_supported(cfg, max_len,
                                                       page_size))
        self.chunked = (chunked_prefill()
                        and chunk_prefill_supported(cfg, max_len))
        # preemption = usage-based admission + swap-to-host; both
        # live on the floating pool's block-table indirection
        self.preemption = (serve_preemption() and self.chunked
                           and self.float_pages)
        if self.float_pages:
            self.kv = FloatingPageCache(cfg, max_len, num_slots,
                                        page_size=page_size,
                                        num_pages=num_pages,
                                        usage_mode=self.preemption)
        else:
            self.kv = PagedKVCache(cfg, max_len, num_slots,
                                   page_size=page_size,
                                   num_pages=num_pages)
        # prefix hits are served by chunk-prefilling the unshared
        # suffix — no chunked prefill, no prefix cache
        self.prefix_cache = (self.float_pages and self.chunked
                             and (serve_prefix_cache()
                                  if prefix_cache is None
                                  else prefix_cache))
        self.chunk_tokens = max(1, min(chunk_tokens,
                                       self.kv.slot_tokens))
        # speculative multi-token decode (docs/speculative-decoding.md)
        # rides on the v2 mixed step: the verify graph needs per-slot
        # depths and an unwrapped cache, exactly the chunked-prefill
        # support surface.  Opt-in (constructor arg wins over the env
        # flag); greedy output stays token-for-token identical either
        # way, so the toggle is pure performance.
        # ... and batch-shape-independent activation scaling: with
        # just-in-time act amaxes a (B, k) verify window measures a
        # different per-tensor scale than the (B, 1) steps it
        # replaces, breaking token-for-token parity.  Delayed
        # (calibrated) scales — the serving default — or the bf16
        # pipeline are exact.
        self.spec = ((spec_decode if spec_decode is not None
                      else spec_decode_flag())
                     and self.chunked
                     and spec_verify_supported(cfg, max_len)
                     and (self.act_scales is not None
                          or cfg.quant.mode == "bf16"))
        self.draft: DraftSource = (draft if draft is not None
                                   else NgramDraft())
        self.spec_k = max(1, int(spec_k))
        self._staging: _Staging | None = None
        self._preempted: deque[tuple[Request, dict]] = deque()
        self.prefill_calls = 0
        self.prefill_tokens_skipped = 0
        self.prefix_hits = 0
        self.pages_shared = 0
        self.chunk_prefill_steps = 0
        self.chunked_requests = 0
        self.preemptions = 0
        self.swap_ins = 0
        self.sched = Scheduler(slo=slo)
        self.requests: dict[int, Request] = {}

    def _build_steps(self):
        # quant-health telemetry (docs/observability.md): resolved at
        # BUILD time so the off path's step graphs carry zero telemetry
        # code — the decode/verify jaxprs stay byte-identical to a
        # health-free build (tests/test_obs.py).  Needs the delayed
        # activation scales: the health stats measure drift AGAINST
        # them.  With health on the engine carries BOTH step variants
        # and runs the instrumented one every Nth call
        # (REPRO_QUANT_HEALTH_EVERY, default 16) — drift moves over
        # thousands of steps, so sparse sampling keeps the signal
        # while the hot loop runs the plain (telemetry-free) graphs.
        self.health = quant_health() and self.act_scales is not None
        self.health_every = quant_health_every() if self.health else 0
        # per-(step kind, token shape) countdowns: the FIRST call of
        # every distinct signature samples health, so a warmup pass
        # compiles every instrumented variant it will ever need and
        # steady state never jit-stalls mid-serving; short runs and
        # post-refresh rebuilds still report.
        self._health_countdown: dict = {}
        if self.health and getattr(self, "qh", None) is None:
            from repro.obs.quant_health import HealthAggregator

            self.qh = HealthAggregator()
        elif not self.health:
            self.qh = None
        self.prefill = jax.jit(
            make_prefill_step(self.cfg, self.max_len,
                              scales=self.scales,
                              act_scales=self.act_scales))
        self.decode = jax.jit(
            make_decode_step(self.cfg, scales=self.scales,
                             act_scales=self.act_scales),
            donate_argnums=(1,))
        # the speculative verify step ((B, k) tokens -> (B, k, V)
        # logits); jit is lazy, so non-speculative engines never
        # compile it
        self.verify = jax.jit(
            make_verify_step(self.cfg, scales=self.scales,
                             act_scales=self.act_scales),
            donate_argnums=(1,))
        if self.health:
            self.prefill_h = jax.jit(
                make_prefill_step(self.cfg, self.max_len,
                                  scales=self.scales,
                                  act_scales=self.act_scales,
                                  quant_health=True))
            self.decode_h = jax.jit(
                make_decode_step(self.cfg, scales=self.scales,
                                 act_scales=self.act_scales,
                                 quant_health=True),
                donate_argnums=(1,))
            self.verify_h = jax.jit(
                make_verify_step(self.cfg, scales=self.scales,
                                 act_scales=self.act_scales,
                                 quant_health=True),
                donate_argnums=(1,))

    # -- quant-health step-call shims ----------------------------------
    # Health OFF: the plain steps, exactly the historical 2-tuples.
    # Health ON: every Nth call runs the instrumented variant, whose
    # third output (the per-site stats tree) feeds the host-side
    # aggregator.
    def _health_due(self, kind: str, shape) -> bool:
        if not self.health:
            return False
        key = (kind, tuple(shape))
        cd = self._health_countdown.get(key)
        if cd is None or cd <= 0:
            self._health_countdown[key] = self.health_every
            return True
        self._health_countdown[key] = cd - 1
        return False

    def _run_prefill(self, *a):
        if self._health_due("prefill", a[1]["tokens"].shape):
            logits, caches, qh = self.prefill_h(*a)
            self.qh.ingest(qh)
            return logits, caches
        return self.prefill(*a)

    def _run_decode(self, *a):
        if self._health_due("decode", a[2].shape):
            logits, caches, qh = self.decode_h(*a)
            self.qh.ingest(qh)
            return logits, caches
        return self.decode(*a)

    def _run_verify(self, *a):
        if self._health_due("verify", a[2].shape):
            logits, caches, qh = self.verify_h(*a)
            self.qh.ingest(qh)
            return logits, caches
        return self.verify(*a)

    def refresh_act_scales(self, tokens=None, margin=None):
        """Re-calibrate the delayed activation scales (optionally on
        caller-supplied ``tokens``) and rebuild the jitted steps —
        runs entirely OUTSIDE the hot decode jaxpr.  No-op when
        delayed scaling is off."""
        if self.act_scales is None:
            return None
        kw = {} if margin is None else {"margin": margin}
        self.act_scales = calibrate_act_scales(
            self.cfg, self.params, self.scales, tokens=tokens, **kw)
        self._build_steps()
        return self.act_scales

    # -- admission -----------------------------------------------------
    def _total_tokens(self, req: Request) -> int:
        # worst-case resident K/V: prompt + every decode-step write
        # (the last generated token is sampled but never written)
        return req.prompt_len + req.max_new - 1

    def _admit_tokens(self, req: Request) -> int:
        """The token count admission reserves pages for: actual usage
        (prompt) plus one page of headroom under preemption — growth
        past it extends page by page, preempting on a dry pool — or
        the worst case when preemption is off (reservation-based
        admission is then the no-corruption guarantee)."""
        total = self._total_tokens(req)
        if self.preemption:
            return min(total, req.prompt_len + self.kv.page_size)
        return total

    def submit(self, requests: list[Request]) -> None:
        for req in requests:
            if req.eos_id is None:
                req.eos_id = self.eos_id
            total = self._total_tokens(req)
            if not self.kv.ring and total > self.kv.slot_tokens:
                raise SlotCapacityExceeded(
                    f"request {req.rid}: prompt {req.prompt_len} + "
                    f"max_new {req.max_new} needs {total} cache "
                    f"positions > slot capacity {self.kv.slot_tokens}")
            al = self.kv.allocator
            need = al.pages_needed(self.kv._resident(total))
            if need > al.num_pages:
                # can never be admitted — even alone in an empty pool
                # (this reject is also what makes the preempt-retry
                # loop terminate: a lone resident request always
                # fits): reject at submit instead of letting
                # head-of-line FIFO livelock the queue
                raise PageExhausted(
                    f"request {req.rid}: worst-case reservation of "
                    f"{need} pages exceeds the whole pool "
                    f"({al.num_pages} pages)")
            self.requests[req.rid] = req
        self.sched.submit(requests)

    def _prefix_plan(self, req: Request) -> PrefixPlan | None:
        """Look the request's page-aligned prompt prefix up in the
        hash map (None when prefix caching is off)."""
        if not self.prefix_cache:
            return None
        t = self.kv.page_size
        keys = page_keys(req.prompt, t)
        pages = self.kv.allocator.lookup(keys)
        n_shared = len(pages)
        if n_shared == 0:
            return PrefixPlan(keys=keys, pages=[], suffix_from=0,
                              cow_slack=0)
        suffix_from = min(n_shared * t, req.prompt_len - 1)
        cow_slack = 1 if n_shared * t >= req.prompt_len else 0
        return PrefixPlan(keys=keys, pages=pages,
                          suffix_from=suffix_from, cow_slack=cow_slack)

    # -- the engine step -----------------------------------------------
    def step(self) -> None:
        # spans wrap the HOST-side phases (repro.obs.trace) — never
        # anything inside a jitted graph, so REPRO_TRACE can never
        # change a jaxpr
        with span("engine.step", rows=len(self.kv.rows)):
            if not self.chunked:
                with span("retire_refill"):
                    self._retire_and_refill()
                    self._admit_new_rows()
                with span("decode", rows=len(self.kv.rows)):
                    self._decode_once()
                return
            with span("retire"):
                self._retire()
            with span("swap_in", preempted=len(self._preempted)):
                self._swap_in_preempted()
            with span("chunk_phase"):
                self._chunk_phase()
            with span("retire"):
                self._retire()  # an attached request may finish
            if self.spec:       # instantly (max_new == 1 / EOS)
                with span("verify", rows=len(self.kv.rows)):
                    self._verify_once()
            else:
                with span("decode", rows=len(self.kv.rows)):
                    self._decode_once()

    # -- v2: retirement ------------------------------------------------
    def _retire(self):
        row = 0
        while row < len(self.kv.rows):
            if self.requests[self.kv.rows[row]].done:
                self.kv.release(row)
                self.kv.shrink(row)   # swapped-in last row re-checked
            else:
                row += 1

    # -- v2: preemption ------------------------------------------------
    def _swap_in_preempted(self):
        """Resume preempted requests FIFO while their pages fit.  One
        slot stays reserved for the in-flight staging request — its
        attach must never find the batch full."""
        while self._preempted:
            limit = self.num_slots - (self._staging is not None)
            if len(self.kv.rows) >= limit:
                return
            req, bundle = self._preempted[0]
            admit = (min(self._total_tokens(req),
                         bundle["depth"] + self.kv.page_size)
                     if self.preemption else self._total_tokens(req))
            try:
                self.kv.swap_in(bundle, admit)
            except PageExhausted:
                return            # stays parked; retirement frees pages
            self._preempted.popleft()
            req.state = RequestState.RUNNING
            self.swap_ins += 1

    def _preempt_one(self) -> bool:
        """Swap the SLO-chosen victim out to host; False when the
        decode batch has nobody left to preempt."""
        cands = [self.requests[rid] for rid in self.kv.rows
                 if rid is not None]
        victim = self.sched.pick_victim(cands)
        if victim is None:
            return False
        bundle = self.kv.swap_out(self.kv.rows.index(victim.rid))
        victim.state = RequestState.PREEMPTED
        self._preempted.append((victim, bundle))
        self.preemptions += 1
        instant("preempt", rid=victim.rid, depth=bundle["depth"])
        return True

    def _grow_or_preempt(self, grow) -> None:
        """Run a page-growing cache operation, preempting one victim
        per ``PageExhausted`` until it fits.  Terminates: every
        preemption frees pages, and a lone resident request always
        fits (submit-time whole-pool reject)."""
        while True:
            try:
                grow()
                return
            except PageExhausted:
                if not (self.preemption and self._preempt_one()):
                    raise

    # -- v2: chunked prefill -------------------------------------------
    def _begin_staging(self) -> bool:
        """Pop the queue head into the staging slot when it fits under
        ACTUAL free-page accounting (usage-based under preemption).
        Preempted requests drain first — they hold finished work, and
        refusing new admissions while any are parked guarantees their
        re-admission is never starved."""
        if self._preempted:
            return False
        head = self.sched.peek()
        if head is None or len(self.kv.rows) >= self.num_slots:
            return False
        plan = self._prefix_plan(head)
        admit = self._admit_tokens(head)
        if plan is not None and plan.pages:
            ok = self.kv.can_admit(admit, shared=plan.pages,
                                   cow_slack=plan.cow_slack)
            if not ok and self.kv.can_admit(admit):
                # the hit needs MORE headroom than a cold admit (page
                # revival + CoW slack, e.g. a minimal pool): serve it
                # cold rather than livelock the FIFO head forever
                plan = PrefixPlan(keys=plan.keys, pages=[],
                                  suffix_from=0, cow_slack=0)
                ok = True
        else:
            ok = self.kv.can_admit(admit)
        if not ok:
            return False          # stays queued (backpressure)
        req = self.sched.pop()
        pos, keys, row_cache = 0, None, None
        if self.float_pages:
            shared = plan.pages if plan is not None else []
            self.kv.stage_admit(req.rid, admit, shared=shared,
                                cow_slack=plan.cow_slack
                                if plan is not None else 0)
            keys = plan.keys if plan is not None else None
            if shared:
                pos = plan.suffix_from
                self.prefix_hits += 1
                self.prefill_tokens_skipped += pos
                self.pages_shared += len(shared)
                req.prefix_pages = len(shared)
                req.prefill_skipped = pos
        else:
            self.kv.stage_admit(req.rid, admit)
            row_cache = init_caches(self.cfg, 1, self.max_len,
                                    per_slot=True)
        self._staging = _Staging(req=req, pos=pos, keys=keys,
                                 row_cache=row_cache)
        self.chunked_requests += 1
        return True

    def _chunk_step(self) -> None:
        """One (1, chunk_tokens) prefill chunk of the staging request:
        write its next prompt tokens at its own depth, attend over its
        resident history.  The final chunk emits the first output
        token and attaches the request to the decode batch."""
        st = self._staging
        req, plen = st.req, st.req.prompt_len
        chunk = self.chunk_tokens
        n_real = min(chunk, plen - st.pos)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n_real] = req.prompt[st.pos:st.pos + n_real]
        if self.float_pages:
            self._grow_or_preempt(
                lambda: self.kv.stage_ensure(req.rid, st.pos,
                                             st.pos + n_real))
            self.kv.stage_stamp(req.rid, st.pos)
            logits, self.kv.caches = self._run_decode(
                self.params, self.kv.caches, jnp.asarray(toks))
        else:
            # identity placement: the chunk runs on a detached one-row
            # cache; only the depth stamp moves between chunks
            st.row_cache = {
                name: map_cache_nodes(
                    seg, lambda n: n._replace(
                        idx=jnp.full_like(n.idx, st.pos)))
                if seg is not None else None
                for name, seg in st.row_cache.items()}
            logits, st.row_cache = self._run_decode(
                self.params, st.row_cache, jnp.asarray(toks))
        self.chunk_prefill_steps += 1
        st.pos += n_real
        if st.pos < plen:
            return
        # last chunk: its final real logit is the first output token
        first = int(jnp.argmax(logits[0, n_real - 1]))
        if self.float_pages:
            self.kv.stage_attach(req.rid, plen)
            if st.keys:
                self.kv.register_prompt(req.rid, st.keys)
        else:
            self.kv.stage_attach(req.rid, st.row_cache, plen)
        self._staging = None
        self.sched.on_token(req, first)

    def _chunk_phase(self):
        budget = self.sched.chunk_budget()
        while budget > 0:
            if self._staging is None and not self._begin_staging():
                return
            self._chunk_step()
            budget -= 1

    # -- v1: whole-prompt prefill admission (A/B fallback) -------------
    def _bucket_len(self, n: int) -> int:
        c = self.kv.slot_tokens
        if n >= c:
            return n          # ring keep-last-C prefill path, exact
        return min(c, -(-n // self.prompt_bucket) * self.prompt_bucket)

    def _prefill_request(self, req: Request):
        """B=1 prefill of a (bucket-padded) prompt; returns the one-row
        caches.  Emits the request's first generated token (TTFT)."""
        n = req.prompt_len
        toks = np.zeros((1, self._bucket_len(n)), np.int32)
        toks[0, :n] = req.prompt
        logits, one = self._run_prefill(self.params, {"tokens":
                                                 jnp.asarray(toks)},
                                   jnp.int32(min(n, toks.shape[1]) - 1))
        self.prefill_calls += 1
        self.sched.on_token(req, int(greedy_sample(logits)[0]))
        return one

    def _admissible_head(self):
        """The head request when it fits under the pool's actual
        free-page accounting, else None."""
        head = self.sched.peek()
        if head is None or not self.kv.can_admit(
                self._total_tokens(head)):
            return None
        return head

    def _admit(self, req: Request, row: int | None = None) -> None:
        """Admit one popped request: B=1 whole-prompt prefill, then
        merge/scatter the row."""
        one = self._prefill_request(req)
        total = self._total_tokens(req)
        if row is None:
            self.kv.append(req.rid, one, req.prompt_len, total)
        else:
            self.kv.refill(row, req.rid, one, req.prompt_len, total)

    def _retire_and_refill(self):
        row = 0
        while row < len(self.kv.rows):
            owner = self.kv.rows[row]
            if owner is not None and not self.requests[owner].done:
                row += 1
                continue
            if owner is not None:
                self.kv.release(row)
            head = self._admissible_head()
            if head is not None:
                self._admit(self.sched.pop(), row=row)
                # a refill may itself already be done (max_new == 1
                # or instant EOS): the loop re-checks this row
            else:
                self.kv.shrink(row)
                # the swapped-in last row is re-checked at this index

    def _admit_new_rows(self):
        while len(self.kv.rows) < self.num_slots:
            head = self._admissible_head()
            if head is None:
                break
            self._admit(self.sched.pop())
            if head.done:                         # instant finish
                self._retire_and_refill()

    # -- decode --------------------------------------------------------
    def _decode_once(self):
        if self.float_pages:
            # copy-on-write barrier + idx/block-table restamp: every
            # row's write-target page must be private BEFORE the
            # in-graph append.  Growth past a usage reservation can
            # exhaust the pool — preempt a victim and retry (the
            # ensure pass is idempotent across retries)
            self._grow_or_preempt(
                lambda: self.kv.prepare_decode()
                if self.kv.rows else None)
        rows = self.kv.rows
        if not rows:
            return
        feed = np.zeros((len(rows), 1), np.int32)
        for i, rid in enumerate(rows):
            feed[i, 0] = self.requests[rid].out[-1]
        logits, self.kv.caches = self._run_decode(
            self.params, self.kv.caches, jnp.asarray(feed))
        self.kv.advance()
        nxt = np.asarray(greedy_sample(logits))
        for i, rid in enumerate(list(rows)):
            self.sched.on_token(self.requests[rid], int(nxt[i]))

    # -- speculative verify (docs/speculative-decoding.md) -------------
    def _verify_once(self):
        """One speculative verify step over the resident rows: propose
        up to ``k-1`` draft tokens per row, run ALL ``k`` positions
        ([last output, drafts...]) through ONE (B, k) forward over the
        paged fp8 cache, and commit per row the longest draft prefix
        matching the model's own argmaxes plus the model's correction
        token.  Greedy output is token-for-token identical to plain
        decode — position j's logits equal the sequential step's
        because the per-draft kernel mask reproduces each step's
        validity window exactly (docs/speculative-decoding.md).

        ``k`` is clamped so NO row can overrun its ``max_new`` budget
        or its slot's write window, and collapses to a plain
        ``_decode_once`` (same compiled (B, 1) graph) when the clamp
        or an empty proposal round leaves nothing to gamble on."""
        rows = self.kv.rows
        if not rows:
            return
        reqs = [self.requests[rid] for rid in rows]
        k = self.sched.draft_len(self.spec_k)
        for i, r in enumerate(reqs):
            # a k-step commits up to k tokens and writes k positions:
            # stay inside every row's generation budget and its slot
            k = min(k, r.max_new - len(r.out),
                    self.kv.slot_tokens - self.kv.lengths[i])
        props = ([list(self.draft.propose(r, k - 1))[:k - 1]
                  for r in reqs] if k > 1 else [])
        if k > 1:
            k = min(k, 1 + max(len(p) for p in props))
        if k <= 1:
            self._decode_once()
            return
        feed = np.zeros((len(rows), k), np.int32)
        n_prop = []
        for i, r in enumerate(reqs):
            feed[i, 0] = r.out[-1]
            p = props[i][:k - 1]
            n_prop.append(len(p))
            # unproposed tail slots stay zero-padded: a pad token only
            # commits on a coincidental argmax match, which is by
            # definition the token plain decode would have produced
            feed[i, 1:1 + len(p)] = p
        if self.float_pages:
            # CoW barrier + restamp over the FULL k-token write window
            self._grow_or_preempt(
                lambda: self.kv.prepare_decode(write_tokens=k))
        logits, self.kv.caches = self._run_verify(
            self.params, self.kv.caches, jnp.asarray(feed))
        nxt = np.asarray(jnp.argmax(logits, axis=-1))      # (B, k)
        advs, accepted = [], 0
        for i, rid in enumerate(list(rows)):
            req = self.requests[rid]
            drafts_in, done, j = 0, False, 0
            # accept drafts while they match the model's own argmax:
            # logits[i, j] is the model's prediction AFTER consuming
            # feed[i, :j+1], i.e. exactly the sequential step's logits
            while j < k - 1 and int(feed[i, j + 1]) == int(nxt[i, j]):
                done = self.sched.on_token(req, int(feed[i, j + 1]))
                drafts_in += 1
                j += 1
                if done:
                    break         # EOS / budget inside the window
            if not done:
                # first mismatch (or window exhausted): the model's
                # correction token — always committable, so a verify
                # step never stalls
                self.sched.on_token(req, int(nxt[i, j]))
            # cache depth advances one position per committed token
            # whose KV the step wrote: out[-1] + accepted drafts (the
            # correction token's KV, like plain decode's sample, waits
            # for the next step's write)
            advs.append(drafts_in + (0 if done else 1))
            accepted += min(drafts_in, n_prop[i])
        self.kv.commit(advs)
        # denominator = the whole (k-1)·B draft window, so padded
        # slots count as misses and the EMA shortens k when the draft
        # source cannot fill the window
        self.sched.on_verify((k - 1) * len(rows), accepted)

    # -- driver --------------------------------------------------------
    def _idle(self) -> bool:
        return not (self.sched.queue or self.kv.rows
                    or self._staging is not None or self._preempted)

    def run(self, requests: list[Request] | None = None, log=print):
        """Drain the queue; returns the requests that finished during
        THIS call (an engine instance can serve several runs — the jit
        caches on its step functions carry over).  Requests with an
        ``arrival_time`` are submitted open-loop at that offset from
        the call's start; the rest are submitted up front."""
        requests = requests or []
        pending = deque(sorted(
            (r for r in requests if r.arrival_time is not None),
            key=lambda r: r.arrival_time))
        now_batch = [r for r in requests if r.arrival_time is None]
        if now_batch:
            self.submit(now_batch)
        done_before = {rid for rid, r in self.requests.items() if r.done}
        toks_before = sum(len(r.out) for r in self.requests.values())
        t0 = time.monotonic()
        steps = 0
        while pending or not self._idle():
            now = time.monotonic() - t0
            while pending and pending[0].arrival_time <= now:
                self.submit([pending.popleft()])
            if self._idle():
                # nothing resident and the next arrival is in the
                # future: sleep toward it instead of spinning
                time.sleep(min(pending[0].arrival_time - now, 0.05))
                continue
            self.step()
            steps += 1
            if steps > 100_000:
                raise RuntimeError("serving loop did not converge")
        dt = time.monotonic() - t0
        done = [r for rid, r in self.requests.items()
                if r.done and rid not in done_before]
        toks = sum(len(r.out) for r in self.requests.values()) \
            - toks_before
        if log is not None:
            ttfts = [r.ttft for r in done if r.ttft is not None]
            tpots = [r.tpot for r in done if r.tpot is not None]
            mean = lambda v: float(np.mean(v)) if v else float("nan")
            log(f"served {len(done)} requests, {toks} tokens in "
                f"{dt:.2f}s ({toks / max(dt, 1e-9):,.1f} tok/s, "
                f"{steps} engine steps, mean TTFT "
                f"{1e3 * mean(ttfts):.1f} ms, mean TPOT "
                f"{1e3 * mean(tpots):.1f} ms)")
        return done

    def stats(self) -> dict:
        s = self.sched.summary()
        al = self.kv.allocator
        s.update({
            "prefill_calls": self.prefill_calls,
            "chunk_prefill_steps": self.chunk_prefill_steps,
            "chunked_requests": self.chunked_requests,
            "preemptions": self.preemptions,
            "swap_ins": self.swap_ins,
            "prefix_hits": self.prefix_hits,
            "prefill_tokens_skipped": self.prefill_tokens_skipped,
            "pages_shared": self.pages_shared,
            "cow_copies": getattr(self.kv, "cow_copies", 0),
            "page_evictions": al.evictions,
            "peak_pool_pages": al.peak_used,
        })
        if self.qh is not None:
            s["quant_health"] = {
                "refresh_recommended": self.qh.refresh_recommended,
                "sites": self.qh.report(),
            }
        self._publish_metrics(s, al)
        return s

    def _publish_metrics(self, s: dict, al) -> None:
        """Mirror the engine/allocator stats into the process-wide
        metrics registry (repro.obs.metrics) — ``set_total`` adopts
        the running python counters without double counting, so
        ``stats()`` can be called any number of times."""
        reg = get_registry()
        for name in ("prefill_calls", "chunk_prefill_steps",
                     "chunked_requests", "preemptions", "swap_ins",
                     "prefix_hits", "prefill_tokens_skipped",
                     "pages_shared", "cow_copies", "page_evictions"):
            reg.counter(f"engine_{name}_total").set_total(float(s[name]))
        reg.gauge("pages_total").set(float(al.num_pages))
        reg.gauge("pages_in_use").set(float(al.num_pages - al.free_pages))
        reg.gauge("pages_cached").set(float(al.cached_pages))
        reg.gauge("pages_peak_used").set(float(al.peak_used))
        reg.gauge("engine_resident_rows").set(float(len(self.kv.rows)))

    def prune_finished(self) -> int:
        """Drop finished requests from the engine's history.  A
        long-lived engine keeps every request for ``stats()``; call
        this between runs to bound memory (returns the count pruned —
        their metrics leave ``stats()`` with them)."""
        done = [rid for rid, r in self.requests.items() if r.done]
        for rid in done:
            del self.requests[rid]
        self.sched.all = [r for r in self.sched.all if not r.done]
        return len(done)
