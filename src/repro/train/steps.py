"""Jitted step functions: train_step (fwd+bwd+AdamW+automatic scaling),
prefill_step, decode_step — plus TrainState plumbing.

The MOSS integration points:
  1. before the forward, predicted per-tensor weight scales are computed
     from ``ScaleState`` (no max-reductions — paper Eq. 10);
  2. all linear GEMMs run the two-level-MX custom-vjp path;
  3. after the optimizer update, scale states advance one step, with a
     real max-reduction only on the lax.cond refresh branch;
  4. optional FP8-compressed gradient all-reduce (paper Table 5).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.formats import QuantConfig, fp8_max, TINY
from repro.core.linear import QT
from repro.distributed import compression
from repro.models.layers import quant_mask_tree, wrap_qt, wrap_qt_nojit
from repro.models.moe import no_aux
from repro.models.transformer import ce_loss, forward, init_caches, model_defs
from repro.obs.metrics import count_compiles
from repro.optim.adamw import (
    AdamWConfig,
    adamw_update,
    clip_by_global_norm,
    init_opt_state,
)
from repro.optim.schedule import cosine_with_warmup


class TrainState(NamedTuple):
    params: Any               # f32 master weights
    opt: Any                  # OptState tree
    scale_s0: Any             # per-leaf predicted-scale base (f32)
    scale_t: Any              # per-leaf steps-since-refresh (i32)
    comm_residual: Any        # fp8-allreduce error feedback (or None)
    step: jax.Array           # i32


class TrainHParams(NamedTuple):
    peak_lr: float = 3e-4
    warmup_steps: int = 2000
    total_steps: int = 100_000
    grad_clip: float = 1.0
    aux_coef: float = 0.01
    microbatches: int = 1     # gradient accumulation (activation memory)
    adamw: AdamWConfig = AdamWConfig()


def _scale_dims(defs):
    """Leading dims that get independent fp8 scales: stacked layer dim
    (+ expert dim).  Derived from PDef logical names."""
    from repro.models.layers import PDef

    def dims(d: PDef):
        n = 0
        for name in d.logical:
            if name in ("layers", "experts"):
                n += 1
            else:
                break
        return n

    return jax.tree.map(dims, defs, is_leaf=lambda x: isinstance(x, PDef))


def init_scales(defs, params, qcfg: QuantConfig):
    """s0 per (layer, expert) slice: amax over the non-stacked dims."""
    sdims = _scale_dims(defs)

    def init(w, nd):
        axes = tuple(range(nd, w.ndim))
        amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axes)
        return jnp.maximum(amax, TINY) / fp8_max(qcfg.fwd_format)

    s0 = jax.tree.map(init, params, sdims)
    t = jax.tree.map(lambda w: jnp.zeros((), jnp.int32), params)
    return s0, t


@jax.named_scope("moss.scale")
def predicted_scales(s0, t, lr, qcfg: QuantConfig):
    def pred(s, ts):
        return s + lr * ts.astype(jnp.float32) / fp8_max(qcfg.fwd_format)
    return jax.tree.map(pred, s0, t)


@jax.named_scope("moss.scale")
def advance_scales(defs, s0, t, params, qcfg: QuantConfig):
    """One step forward; lax.cond refresh at the interval (the untaken
    branch reads no weight bytes — the paper's Table 1 saving)."""
    sdims = _scale_dims(defs)

    def adv(s, ts, w, nd):
        ts_next = ts + 1

        def refresh(_):
            axes = tuple(range(nd, w.ndim))
            amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axes)
            return (jnp.maximum(amax, TINY) / fp8_max(qcfg.fwd_format),
                    jnp.zeros((), jnp.int32))

        def keep(_):
            return (s, ts_next)

        if qcfg.weight_scaling in ("jit", "delayed"):
            return refresh(None)
        return jax.lax.cond(ts_next >= qcfg.rescale_interval,
                            refresh, keep, operand=None)

    out = jax.tree.map(adv, s0, t, params, sdims)
    new_s0 = jax.tree.map(lambda o: o[0], out,
                          is_leaf=lambda o: isinstance(o, tuple))
    new_t = jax.tree.map(lambda o: o[1], out,
                         is_leaf=lambda o: isinstance(o, tuple))
    return new_s0, new_t


def init_train_state(cfg, hp: TrainHParams, key, params=None):
    from repro.models.layers import init_tree

    defs = model_defs(cfg)
    if params is None:
        params = init_tree(defs, key)
    opt = init_opt_state(params)
    qcfg = cfg.quant
    s0, t = init_scales(defs, params, qcfg)
    res = (compression.init_residuals(params)
           if qcfg.grad_comm_fp8 else None)
    return TrainState(params=params, opt=opt, scale_s0=s0, scale_t=t,
                      comm_residual=res, step=jnp.zeros((), jnp.int32))


def make_train_step(cfg, hp: TrainHParams, mesh=None):
    """Builds the jittable train step for arch ``cfg``."""
    count_compiles()
    defs = model_defs(cfg)
    mask = quant_mask_tree(defs)
    qcfg = cfg.quant

    def train_step(state: TrainState, batch: dict):
        lr = cosine_with_warmup(state.step, peak_lr=hp.peak_lr,
                                warmup_steps=hp.warmup_steps,
                                total_steps=hp.total_steps)

        if qcfg.quantized and qcfg.weight_scaling == "auto":
            scales = predicted_scales(state.scale_s0, state.scale_t, lr,
                                      qcfg)
        else:
            scales = jax.tree.map(lambda w: None, state.params)

        def loss_fn(params, mb):
            if qcfg.quantized and qcfg.weight_scaling == "auto":
                qp = wrap_qt(params, scales, mask)
            else:
                qp = wrap_qt_nojit(params, mask)
            logits, _, aux = forward(cfg, qcfg, qp, mb, mode="train")
            loss = ce_loss(cfg, logits, mb["labels"], mb.get("mask"))
            return loss + hp.aux_coef * aux["balance"], (loss, aux)

        n_mb = hp.microbatches
        if n_mb <= 1:
            (_, (loss, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)
        else:
            # gradient accumulation: scan over microbatches (bounds the
            # per-layer activation carry at B/n_mb)
            mbs = jax.tree.map(
                lambda x: x.reshape(n_mb, x.shape[0] // n_mb,
                                    *x.shape[1:]), batch)

            def acc_step(carry, mb):
                g_acc, loss_acc, aux_acc = carry
                (_, (l, a)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                aux_acc = jax.tree.map(jnp.add, aux_acc, a)
                return (g_acc, loss_acc + l, aux_acc), None

            g0 = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32),
                              state.params)
            (grads, loss, aux), _ = jax.lax.scan(
                acc_step, (g0, jnp.zeros((), jnp.float32), no_aux(cfg)),
                mbs)
            grads = jax.tree.map(lambda g: g / n_mb, grads)
            # the counts stay summed over the microbatches
            loss = loss / n_mb
            aux = dict(aux, balance=aux["balance"] / n_mb)

        if mesh is not None:
            # constrain gradients to the parameter sharding so GSPMD
            # emits reduce-scatters instead of full all-reduces (§Perf)
            from repro.distributed.sharding import resolve_spec
            from repro.models.layers import PDef

            def _gspec(d):
                return jax.sharding.NamedSharding(
                    mesh, resolve_spec(d.logical, mesh, d.shape))

            gspecs = jax.tree.map(_gspec, defs,
                                  is_leaf=lambda x: isinstance(x, PDef))
            grads = jax.tree.map(
                jax.lax.with_sharding_constraint, grads, gspecs)

        if qcfg.grad_comm_fp8 and mesh is not None:
            grads, new_res = compression.fp8_allreduce_grads(
                grads, state.comm_residual, mesh)
            # keep the error-feedback residual sharded like the params
            # (as it was born — launch/specs.state_shardings)
            new_res = jax.tree.map(jax.lax.with_sharding_constraint,
                                   new_res, gspecs)
        else:
            new_res = state.comm_residual

        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, hp.grad_clip)
            new_params, new_opt = adamw_update(hp.adamw, state.params,
                                               grads, state.opt,
                                               state.step, lr)
        if qcfg.quantized:
            new_s0, new_t = advance_scales(defs, state.scale_s0,
                                           state.scale_t, new_params, qcfg)
        else:
            new_s0, new_t = state.scale_s0, state.scale_t

        metrics = {"loss": loss, "aux": aux["balance"], "lr": lr,
                   "grad_norm": gnorm}
        if cfg.n_experts:
            # summed over the step's MoE layers (and microbatches)
            metrics["moe_rows_routed"] = aux["rows_routed"]
            metrics["moe_slots_dropped"] = aux["slots_dropped"]
        return TrainState(params=new_params, opt=new_opt, scale_s0=new_s0,
                          scale_t=new_t, comm_residual=new_res,
                          step=state.step + 1), metrics

    return train_step


def make_eval_step(cfg):
    defs = model_defs(cfg)
    mask = quant_mask_tree(defs)
    qcfg = cfg.quant

    def eval_step(params, batch):
        qp = wrap_qt_nojit(params, mask)
        logits, _, _ = forward(cfg, qcfg, qp, batch, mode="train")
        return ce_loss(cfg, logits, batch["labels"], batch.get("mask"))

    return eval_step


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def serve_weight_scales(cfg, params):
    """Per-tensor fp8 scales for a frozen serving model, computed ONCE
    at build time.  Without these, every prefill/decode step re-reduces
    ``max|W|`` for every quantized weight inside the jitted graph (the
    Table-1 traffic automatic scaling removes from training) — for
    serving the weights never change, so the scales are build-time
    constants.  Returns None in bf16 mode and for jit/delayed scaling
    recipes (whose defined semantics are the in-step reduction —
    ``_quantize_w`` only consumes supplied scales in "auto" mode)."""
    if not (cfg.quant.quantized and cfg.quant.weight_scaling == "auto"):
        return None
    return init_scales(model_defs(cfg), params, cfg.quant)[0]


def prequantize_params(cfg, params):
    """Quantize the WHOLE weight stack to fp8 payloads + scales at
    server build time — the step beyond ``serve_weight_scales``: not
    only the max-reductions but the fp8 casts themselves leave the
    decode/prefill graphs, and weight HBM traffic drops to 1
    byte/element for every quantized GEMM.

    Works for every quantized recipe (``per_tensor``, ``per_group``,
    ``moss`` — weights are per-tensor-quantized in all three; the
    per-group/micro-group machinery applies to activations, which are
    dynamic and stay quantized in-graph).  Per-(layer, expert) slices
    get independent scales, matching what the scan-over-layers forward
    quantizes one slice at a time, so serving outputs are *bitwise*
    identical to the in-graph path (tests/test_serving.py).

    Returns a ``PrequantParams`` (qweights, scales), or None in bf16
    mode.  Never-quantized leaves (norms, routers, embeddings) keep
    their raw arrays and in-graph behavior.

    Tied-embedding models additionally get a build-time fp8
    **transposed head** (``embed/head_t``, COAT-style dual layout): the
    historical tied path re-quantized the vocab-sized ``embeddingᵀ``
    inside EVERY decode step — the one remaining vocab-sized fp8 cast
    in the decode graph.  The payload is quantized with the same
    in-graph (amax) scale the tied path computed — amax is
    transpose-invariant — so serving logits stay bitwise identical
    while the cast and its reduction leave the graph
    (tests/test_serving.py tied-head parity).
    """
    from repro.core.quant import PrequantParams, prequant_weight

    qcfg = cfg.quant
    if not qcfg.quantized:
        return None
    defs = model_defs(cfg)
    sdims = _scale_dims(defs)
    mask = quant_mask_tree(defs)
    auto = qcfg.weight_scaling == "auto"
    pred = init_scales(defs, params, qcfg)[0] if auto else None

    def leaf(w, nd, m, s):
        if not m:
            return w, jnp.ones((), jnp.float32)
        # "auto" recipes quantize against the predicted (build-time
        # amax) scale like serve_weight_scales; jit/delayed recipes
        # reduce amax over the (possibly bf16-cast) slice exactly as
        # the in-graph quantizer would
        return prequant_weight(w, nd, qcfg.fwd_format,
                               scale=s if auto else None,
                               cast_bf16=qcfg.weight_cast_bf16)

    out = jax.tree.map(leaf, params, sdims, mask,
                       pred if auto else sdims)
    is_pair = lambda o: isinstance(o, tuple) and len(o) == 2
    qweights = jax.tree.map(lambda o: o[0], out, is_leaf=is_pair)
    scales = jax.tree.map(lambda o: o[1], out, is_leaf=is_pair)
    if cfg.tie_embeddings:
        # scale=None ALWAYS (even for auto recipes): the in-graph tied
        # path is QT(embᵀ, None) → jit weight scaling, and amax is
        # transpose-invariant, so this reproduces it bitwise
        q, s = prequant_weight(
            jnp.asarray(params["embed"]["embedding"]).T, 0,
            qcfg.fwd_format, scale=None,
            cast_bf16=qcfg.weight_cast_bf16)
        qweights["embed"]["head_t"] = q
        scales["embed"]["head_t"] = s
    return PrequantParams(qweights=qweights, scales=scales)


def serve_quant_mask(cfg, tree=None):
    """The serving quantization mask: ``quant_mask_tree`` patched with
    the prequant transposed tied head (``embed/head_t``) when ``tree``
    (a serving params or scales tree) carries one — the head is not a
    PDef, it exists only in prequantized serving trees."""
    mask = quant_mask_tree(model_defs(cfg))
    if (isinstance(tree, dict) and isinstance(tree.get("embed"), dict)
            and "head_t" in tree["embed"]):
        mask = {**mask, "embed": {**mask["embed"], "head_t": True}}
    return mask


def _wrap_serve(params, mask, scales, act=None):
    """QT-wrap with cached build-time scales when available.  ``params``
    may be the raw tree or ``PrequantParams.qweights`` (fp8 payloads) —
    the linear layer keys off the leaf dtype.

    ``act`` is the flat ``{site tag: ActScale}`` dict from
    ``repro.core.actscale.calibrate_act_scales``: each quantized leaf
    additionally gets its site's calibrated activation scales in the
    third QT field, flipping ``qlinear`` onto the reduction-free
    delayed forward (docs/serving.md)."""
    if act:
        from repro.core.actscale import path_tag

        tmw = jax.tree_util.tree_map_with_path
        if scales is None:
            return tmw(lambda p, w, m: QT(w, None, act.get(path_tag(p)))
                       if m else w, params, mask)
        return tmw(lambda p, w, s, m: QT(w, s, act.get(path_tag(p)))
                   if m else w, params, scales, mask)
    if scales is None:
        return wrap_qt_nojit(params, mask)
    return wrap_qt(params, scales, mask)


def _health_act(act_scales, quant_health: bool):
    """Build-time resolution of the quant-health tap (repro.obs.
    quant_health): when the flag is on and delayed activation scales
    exist, each site's ``ActScale`` is wrapped in a ``TaggedScale`` so
    ``qlinear`` can report per-site stats.  Off (the default) returns
    ``act_scales`` untouched — the step graphs are byte-identical to a
    build without this feature."""
    if quant_health and act_scales:
        from repro.obs.quant_health import tag_act_scales

        return tag_act_scales(act_scales), True
    return act_scales, False


def _forward_health(health: bool, cfg, qcfg, qp, batch, caches, mode):
    """forward() plus, when health is on, the collected per-site stats
    tree (None otherwise — and then this is exactly ``forward``)."""
    if not health:
        logits, caches, _ = forward(cfg, qcfg, qp, batch, caches,
                                    mode=mode)
        return logits, caches, None
    from repro.obs.quant_health import QH

    with QH.capture() as cap:
        logits, caches, _ = forward(cfg, qcfg, qp, batch, caches,
                                    mode=mode)
    return logits, caches, cap.tree


def make_prefill_step(cfg, max_len: int, scales=None, act_scales=None,
                      quant_health: bool = False):
    """``scales`` (from ``serve_weight_scales``) threads pre-computed
    per-tensor weight scales through; None falls back to in-step (jit)
    scaling — the training-eval behavior.

    The built step takes an optional third argument ``last`` — the
    index of the logits position to return (int32 scalar).  The
    serving engine right-pads prompts to a length bucket so prefill
    compiles once per bucket instead of once per prompt length; the
    causally-correct last-token logits then sit at the true prompt
    length - 1, not at -1 (docs/continuous-batching.md).  ``None``
    (the default) keeps the historical behavior: logits[:, -1:].

    ``act_scales`` (from ``repro.core.actscale.calibrate_act_scales``)
    swaps in-graph activation amax reductions for the calibrated
    delayed scales; None keeps just-in-time scaling.

    ``quant_health=True`` (REPRO_QUANT_HEALTH=1, engine-resolved)
    additionally returns the per-site quantization-health stats tree
    as a THIRD output — docs/observability.md."""
    mask = serve_quant_mask(cfg, scales)
    qcfg = cfg.quant
    act, health = _health_act(act_scales, quant_health)

    def prefill_step(params, batch, last=None):
        qp = _wrap_serve(params, mask, scales, act)
        b = (batch["tokens"].shape[0] if "tokens" in batch
             else batch["embeds"].shape[0])
        caches = init_caches(cfg, b, max_len)
        logits, caches, qh = _forward_health(health, cfg, qcfg, qp,
                                             batch, caches, "prefill")
        if last is None:
            logits = logits[:, -1:]
        else:
            logits = jax.lax.dynamic_slice_in_dim(logits, last, 1,
                                                  axis=1)
        if health:
            return logits, caches, qh
        return logits, caches

    return prefill_step


def make_chunk_prefill_step(cfg, scales=None, act_scales=None,
                            quant_health: bool = False):
    """Chunked-prefill step — a documented alias of
    ``make_decode_step``.

    One mixed-step graph serves both shapes: the engine feeds (B, 1)
    decode tokens and (1, C) prompt chunks through the SAME jitted
    callable; jit shape-specializes each, and the (1, C) trace takes
    decode mode's S > 1 path (``attention._chunk_attention``) — the
    chunk is written at the slot's current depth (the start position
    and per-slot RoPE offsets ride in the caches' ``idx``), attending
    the already-resident pages via the block table plus an in-chunk
    causal mask.  ONE chunk shape replaces v1's per-16-token-bucket
    prefill compiles (docs/continuous-batching.md)."""
    return make_decode_step(cfg, scales=scales, act_scales=act_scales,
                            quant_health=quant_health)


def make_decode_step(cfg, scales=None, act_scales=None,
                     quant_health: bool = False):
    mask = serve_quant_mask(cfg, scales)
    qcfg = cfg.quant
    act, health = _health_act(act_scales, quant_health)

    def decode_step(params, caches, tokens):
        """tokens: (B, 1) int32 (or embeds (B,1,d)) -> next logits."""
        qp = _wrap_serve(params, mask, scales, act)
        batch = ({"embeds": tokens} if cfg.input_mode == "embeddings"
                 and tokens.ndim == 3 else {"tokens": tokens})
        logits, caches, qh = _forward_health(health, cfg, qcfg, qp,
                                             batch, caches, "decode")
        if health:
            return logits, caches, qh
        return logits, caches

    return decode_step


def make_verify_step(cfg, scales=None, act_scales=None,
                     quant_health: bool = False):
    """Speculative verify step (docs/speculative-decoding.md).

    The built step takes ``tokens (B, k)`` = [last committed token,
    draft_1 .. draft_{k-1}] per row, writes all k positions to the
    cache and returns logits for ALL k positions in one forward —
    position j's logits are what sequential decode would emit after
    feeding tokens[:, :j+1], so greedy accept/reject against them is
    token-for-token exact.  Unlike the chunked-prefill path the
    history is attended through the fused batched-query decode kernel
    (mode="verify"): no cache-sized dequant upcasts, no quant
    reductions beyond the k-position storage writes.  The caller
    truncates per-slot lengths on rejection (the written-but-rejected
    positions are simply never covered by ``n_valid`` again)."""
    mask = serve_quant_mask(cfg, scales)
    qcfg = cfg.quant
    act, health = _health_act(act_scales, quant_health)

    def verify_step(params, caches, tokens):
        """tokens: (B, k) int32 -> ((B, k, V) logits, caches)."""
        qp = _wrap_serve(params, mask, scales, act)
        logits, caches, qh = _forward_health(health, cfg, qcfg, qp,
                                             {"tokens": tokens}, caches,
                                             "verify")
        if health:
            return logits, caches, qh
        return logits, caches

    return verify_step
