"""Token-choice top-k Mixture of Experts with explicit expert parallelism.

Train/prefill path (mesh present): shard_map over (pod, data, model) —
tokens are split across *all* mesh axes for dispatch, experts live on
the ``model`` axis, and two ``all_to_all`` collectives move token
buffers to/from their experts (the torch-EP pattern, expressed
jax-natively; the collectives land in the HLO where the roofline
collective term can count them).

Held experts: the router scores all ``n_experts`` and picks the top-k
over all of them, but the layer holds the weights of experts [0, H)
only (``cfg.experts_held``, one chip's share under expert parallelism).
Only (token, slot) pairs routed to a held expert are dispatched, and
the layer returns its own experts' part of the routed sum; what the
absent experts would add is left out.

Dispatch is sort-based (argsort by expert id + capacity truncation) —
never materializes a (T, E, C) one-hot — and moves rows by gathers
alone, forward and backward: no scatter of rows, so no colliding
writes whatever the routing.  Per-device buffer is
(H, C_local, d).  Training is dropless: C_local = T_local (rounded up
to 32), so every pair fits whatever the routing, and the grouped
kernels skip each expert's empty row blocks; under EP the per-expert
counts ride the all_to_all too, and the kernels skip the empty blocks
of each source shard's segment, though the all_to_all itself moves all
H·C_local rows.  Outside training C_local = ceil(T_local·k·cf/E) and
overflow pairs are dropped.

Decode path (T small): masked dense-experts combine — every expert runs
on every token.  With batch≥experts·top_k the full expert weights are
read anyway, so the memory roofline is identical and decode stays
simple and shardable (DESIGN.md §3).  Training never takes it.

Profiler scopes: ``moe.router`` (router, top-k, balance loss),
``moe.dispatch`` (sort, index maps, gathers, combine, the all-to-alls) and
``moe.experts`` (the expert GEMMs).

Router stays f32 and unquantized (tiny, accuracy-critical).  Expert
GEMMs are MOSS-quantized with *per-expert* weight scales.

Expert-GEMM execution (``REPRO_MOE_EXPERTS``, see
``repro.core.runtime_flags.moe_expert_path``):

  grouped  (default, moss mode)  the flat ``(E·C, d)`` dispatch buffer
           plus the ragged per-expert row counts (already produced by
           the sort-based dispatch) go through ONE grouped Pallas
           kernel per GEMM (``qmm_grouped`` → ``kernels/moe_gmm.py``):
           3 launches + 1 amax reduction per MoE block.
  vmapped  legacy ``jax.vmap`` over per-expert ``qlinear``: 3·E
           launches + E reductions — the A/B benchmarking fallback,
           and the path for non-moss quant modes and decode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.formats import QuantConfig
from repro.core.linear import QT, qlinear, qlinear_grouped
from repro.core.runtime_flags import moe_expert_path
from repro.distributed.sharding import active_mesh
from repro.obs.metrics import get_registry
from .layers import PDef


def moe_defs(cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_held
    defs = {
        "router": PDef((d, cfg.n_experts), (None, None)),  # f32, unquantized
        "w_up": PDef((e, d, f), ("experts", "fsdp", "mlp"), quantized=True),
        "w_gate": PDef((e, d, f), ("experts", "fsdp", "mlp"), quantized=True),
        "w_down": PDef((e, f, d), ("experts", "mlp", "fsdp"), quantized=True),
    }
    return defs


def _expert_ffn(cfg, w_up: QT, w_gate: QT, w_down: QT, x, qcfg):
    """One expert's gated FFN on its (C, d) token buffer."""
    up = qlinear(x, w_up, qcfg)
    gate = qlinear(x, w_gate, qcfg)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return qlinear(h, w_down, qcfg)


def _experts_vmapped(cfg, p, xs, qcfg):
    """xs: (E_local, C, d) -> (E_local, C, d); per-expert quant scales."""
    from repro.core.actscale import REC

    def one(w_up, w_gate, w_down, x):
        return _expert_ffn(cfg, w_up, w_gate, w_down, x, qcfg)

    if REC.recording:
        # calibration: python-loop the experts so each records its own
        # concrete activation amax under its (layer, expert) index.
        # QT fields are sliced by hand — the tag string in ``a`` is not
        # indexable, and vmap can't batch over a str leaf.
        def sl(wt, i):
            return QT(wt.w[i], None if wt.s is None else wt.s[i], wt.a)

        ys = []
        for i in range(xs.shape[0]):
            with REC.sub_index(i):
                ys.append(one(sl(p["w_up"], i), sl(p["w_gate"], i),
                              sl(p["w_down"], i), xs[i]))
        return jnp.stack(ys)
    return jax.vmap(one)(p["w_up"], p["w_gate"], p["w_down"], xs)


def _experts_grouped(cfg, p, xs, sizes, qcfg):
    """All expert FFNs through the grouped ragged kernel: xs (E, C, d)
    flattened to the sorted token buffer, 3 grouped GEMM launches + 1
    level-1 amax per GEMM instead of 3·E launches + E reductions.

    ``sizes`` is the ragged valid-row count from dispatch: one per
    expert, or after the EP all_to_all one per (expert, source shard)
    segment of the expert's rows, so the kernels skip the empty row
    blocks of every segment."""
    e, c, d = xs.shape
    flat = xs.reshape(e * c, d)
    up = qlinear_grouped(flat, p["w_up"], sizes, c, qcfg)
    gate = qlinear_grouped(flat, p["w_gate"], sizes, c, qcfg)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(flat.dtype) * up
    y = qlinear_grouped(h, p["w_down"], sizes, c, qcfg)
    return y.reshape(e, c, d)


def _expert_runner(cfg, p, qcfg):
    """Selects the expert-GEMM path; returns fn(xs, sizes) -> ys.

    moss and bf16 route through the grouped kernel (bf16 grouped is
    bitwise identical to vmapped — same dots over the same rows); the
    per-tensor/per-group baselines keep the vmapped experts."""
    if qcfg.mode in ("moss", "bf16") and moe_expert_path() == "grouped":
        return lambda xs, sizes: _experts_grouped(cfg, p, xs, sizes, qcfg)
    return lambda xs, sizes: _experts_vmapped(cfg, p, xs, qcfg)


def router_probs(cfg, p, x_flat):
    """f32 router; returns (probs, aux metrics)."""
    w = p["router"]
    w = w.w if isinstance(w, QT) else w
    logits = x_flat.astype(jnp.float32) @ w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    return logits, probs


def load_balance_loss(probs, ids, n_experts: int, top_k: int):
    """Switch-style aux loss: E · Σ_e f_e · P_e."""
    one_hot = jax.nn.one_hot(ids, n_experts, dtype=jnp.float32)  # (T,k,E)
    f = one_hot.sum(axis=(0, 1)) / (ids.shape[0] * top_k)
    pmean = probs.mean(axis=0)
    return n_experts * jnp.sum(f * pmean)


def balance_loss(cfg, probs, ids, batch: int):
    """``load_balance_loss`` over the whole batch, or with
    ``cfg.seq_aux`` over each of its ``batch`` sequences and averaged
    (DeepSeek-V2's expert-level balance loss)."""
    if not cfg.seq_aux:
        return load_balance_loss(probs, ids, cfg.n_experts, cfg.top_k)
    per_seq = jax.vmap(lambda p_, i_: load_balance_loss(
        p_, i_, cfg.n_experts, cfg.top_k))
    return jnp.mean(per_seq(probs.reshape(batch, -1, probs.shape[-1]),
                            ids.reshape(batch, -1, ids.shape[-1])))


@jax.custom_vjp
def _to_slots(x, tok_of_slot, slot_of_pair):
    """The experts' buffer rows: slot s holds token ``tok_of_slot[s]``,
    or zeros where that index is past the last token.  Its transpose
    sums each token's k slots (``slot_of_pair``, (T, k)) back, a gather
    too, so neither direction scatters rows."""
    return jnp.take(x, tok_of_slot, axis=0, mode="fill", fill_value=0)


def _to_slots_fwd(x, tok_of_slot, slot_of_pair):
    return (_to_slots(x, tok_of_slot, slot_of_pair),
            (tok_of_slot, slot_of_pair))


def _to_slots_bwd(res, dxs):
    tok_of_slot, slot_of_pair = res
    dx = sum(jnp.take(dxs, slot_of_pair[:, j], axis=0, mode="fill",
                      fill_value=0).astype(jnp.float32)
             for j in range(slot_of_pair.shape[1]))
    return (dx.astype(dxs.dtype), _no_grad(tok_of_slot),
            _no_grad(slot_of_pair))


_to_slots.defvjp(_to_slots_fwd, _to_slots_bwd)


@jax.custom_vjp
def _from_slots(ys, w, slot_of_pair, tok_of_slot, pair_of_slot):
    """y[t] = Σ_j w[t, j] · ys[slot_of_pair[t, j]] in f32, a slot index
    past the last slot reading zeros.  Its transpose takes each slot's
    cotangent from its token (``tok_of_slot``) and weight
    (``pair_of_slot`` into the flat (T·k) weights) by gathers too."""
    return sum(w[:, j:j + 1].astype(jnp.float32)
               * jnp.take(ys, slot_of_pair[:, j], axis=0, mode="fill",
                          fill_value=0).astype(jnp.float32)
               for j in range(w.shape[1]))


def _from_slots_fwd(ys, w, slot_of_pair, tok_of_slot, pair_of_slot):
    return (_from_slots(ys, w, slot_of_pair, tok_of_slot, pair_of_slot),
            (ys, w, slot_of_pair, tok_of_slot, pair_of_slot))


def _from_slots_bwd(res, dy):
    ys, w, slot_of_pair, tok_of_slot, pair_of_slot = res
    w_slot = jnp.take(w.reshape(-1).astype(jnp.float32), pair_of_slot,
                      mode="fill", fill_value=0)
    # gathered in the rows' dtype: the layer casts y to it, so dy holds
    # no more, and an f32 (H·C, d) copy would be twice the bytes
    dys = (jnp.take(dy.astype(ys.dtype), tok_of_slot, axis=0, mode="fill",
                    fill_value=0) * w_slot[:, None]).astype(ys.dtype)
    dw = jnp.stack([
        jnp.sum(jnp.take(ys, slot_of_pair[:, j], axis=0, mode="fill",
                         fill_value=0).astype(jnp.float32) * dy, axis=-1)
        for j in range(w.shape[1])], axis=1)
    return (dys, dw.astype(w.dtype), _no_grad(slot_of_pair),
            _no_grad(tok_of_slot), _no_grad(pair_of_slot))


_from_slots.defvjp(_from_slots_fwd, _from_slots_bwd)


def _no_grad(ints):
    return np.zeros(ints.shape, jax.dtypes.float0)


def _dispatch_combine_local(cfg, x_loc, ids_loc, w_loc, expert_fn,
                            capacity: int, model_axis: str | None):
    """Per-device dispatch -> (all_to_all) -> experts -> (all_to_all) ->
    combine.  Runs inside shard_map (or standalone without a mesh).
    Returns (y, pairs routed to a held expert that did not fit).

    The (token, slot) pairs are sorted by expert; from that order come
    three index maps — the slot of each pair, the pair and the token of
    each slot, an index past the end meaning none — and rows move
    between tokens and slots by gathers alone, forward and backward."""
    t_loc, d = x_loc.shape
    k = ids_loc.shape[-1]
    e = cfg.n_held

    with jax.named_scope("moe.dispatch"):
        flat_ids = ids_loc.reshape(-1)                   # (T·k,)
        order = jnp.argsort(flat_ids, stable=True)
        sorted_ids = flat_ids[order]
        # position within expert group
        group_start = jnp.searchsorted(sorted_ids, jnp.arange(e))
        group_end = jnp.searchsorted(sorted_ids, jnp.arange(e),
                                     side="right")
        # ragged per-expert valid-row counts — the grouped kernel's
        # group sizes (capacity truncation applied, zero-size allowed)
        sizes = jnp.minimum(group_end - group_start,
                            capacity).astype(jnp.int32)
        held = sorted_ids < e
        pos = jnp.arange(t_loc * k) - group_start[jnp.minimum(sorted_ids,
                                                              e - 1)]
        keep = held & (pos < capacity)
        dropped = jnp.sum(held & ~keep, dtype=jnp.int32)
        dest = jnp.where(keep, sorted_ids * capacity + pos, e * capacity)
        slot_of_pair = jnp.zeros((t_loc * k,), jnp.int32).at[order].set(
            dest.astype(jnp.int32), unique_indices=True).reshape(t_loc, k)
        c = jnp.arange(capacity)
        at = jnp.minimum(group_start[:, None] + c, t_loc * k - 1)
        pair_of_slot = jnp.where(c < sizes[:, None], order[at],
                                 t_loc * k).reshape(-1)
        tok_of_slot = pair_of_slot // k                  # T where none
        xs = _to_slots(x_loc, tok_of_slot, slot_of_pair).reshape(
            e, capacity, d)
    get_registry().counter(
        "moe_rows_allocated_total",
        "rows of expert buffers dispatch allocates, per traced call"
    ).inc(e * capacity)

    if model_axis is not None:
        with jax.named_scope("moe.dispatch"):
            xs = jax.lax.all_to_all(xs, model_axis, split_axis=0,
                                    concat_axis=1, tiled=True)
            # this shard's experts' counts from every source shard,
            # (source, expert) -> (expert, source): the segments of each
            # expert's received rows
            got = jax.lax.all_to_all(sizes, model_axis, split_axis=0,
                                     concat_axis=0, tiled=True)
            m = e // xs.shape[0]
            seg_sizes = got.reshape(m, e // m).T.reshape(-1)
        with jax.named_scope("moe.experts"):
            ys = expert_fn(xs, seg_sizes)
        with jax.named_scope("moe.dispatch"):
            ys = jax.lax.all_to_all(ys, model_axis, split_axis=1,
                                    concat_axis=0, tiled=True)
    else:
        with jax.named_scope("moe.experts"):
            ys = expert_fn(xs, sizes)                    # (E, C, d)

    with jax.named_scope("moe.dispatch"):
        y = _from_slots(ys.reshape(e * capacity, d), w_loc, slot_of_pair,
                        tok_of_slot, pair_of_slot)
    return y.astype(x_loc.dtype), dropped


def _capacity(cfg, t_local: int, mode: str) -> int:
    """Rows per held expert: every local token in training (dropless),
    rounded up to 32 so that each source shard's segment of an expert's
    rows under EP holds whole micro-groups for the grouped dW kernel;
    else the capacity factor's share, rounded up to 8."""
    if mode == "train":
        return -(-t_local // 32) * 32
    c = int(t_local * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return -(-c // 8) * 8


def moe_block(cfg, p, x, qcfg: QuantConfig, mode: str = "train"):
    """x: (B, S, d) -> (y, aux): y is the held experts' part of the
    routed sum; aux holds the balance loss (``balance``), the (token,
    slot) pairs routed to a held expert (``rows_routed``) and those of
    them dropped for capacity (``slots_dropped``, 0 in training)."""
    b, s, d = x.shape
    x_flat = x.reshape(-1, d)
    t = x_flat.shape[0]
    with jax.named_scope("moe.router"):
        logits, probs = router_probs(cfg, p, x_flat)
        top_w, top_ids = jax.lax.top_k(probs, cfg.top_k)  # (T,k)
        if cfg.norm_topk:
            top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        balance = balance_loss(cfg, probs, top_ids, b)
        routed = jnp.sum(top_ids < cfg.n_held, dtype=jnp.int32)

    from repro.core.actscale import REC

    mesh = active_mesh()
    use_ep = (mesh is not None and mode not in ("decode", "verify")
              and "model" in mesh.axis_names)
    # calibration (REC.recording) forces the dense every-expert path:
    # it is what decode runs, and sort-based dispatch would hand some
    # experts empty/truncated buffers — near-zero amaxes that would
    # catastrophically clip those experts at decode time.  The verify
    # step routes exactly like decode: per-token routing is
    # batch-composition-independent on the dense path, which the
    # token-for-token speculative exactness contract relies on.
    if mode in ("decode", "verify") or REC.recording or (
            mode == "prefill" and not use_ep and cfg.moe_decode_dense
            and t <= 4096):
        y = _dense_moe(cfg, p, x_flat, top_w, top_ids, qcfg)
        return y.reshape(b, s, d), _aux(balance, routed, 0)

    if use_ep:
        token_axes = tuple(a for a in ("pod", "data", "model")
                           if a in mesh.axis_names)
        n_tok_shards = 1
        for a in token_axes:
            n_tok_shards *= mesh.shape[a]
        t_loc = t // n_tok_shards
        cap = _capacity(cfg, t_loc, mode)

        def body(x_loc, ids_loc, w_loc, w_up, w_gate, w_down):
            # FSDP all-gather of expert weights over the data axis
            if "data" in mesh.axis_names:
                w_up = jax.lax.all_gather(w_up.w, "data", axis=1, tiled=True), w_up.s
                w_gate = jax.lax.all_gather(w_gate.w, "data", axis=1, tiled=True), w_gate.s
                w_down = jax.lax.all_gather(w_down.w, "data", axis=2, tiled=True), w_down.s
                w_up, w_gate, w_down = (QT(*w_up), QT(*w_gate), QT(*w_down))
            pl = {"w_up": w_up, "w_gate": w_gate, "w_down": w_down}
            fn = _expert_runner(cfg, pl, qcfg)
            y, dropped = _dispatch_combine_local(cfg, x_loc, ids_loc, w_loc,
                                                 fn, cap, "model")
            return y, dropped[None]

        tok_spec = P(token_axes, None)
        wspec_up = P("model", "data" if "data" in mesh.axis_names else None,
                     None)
        wspec_down = P("model", None,
                       "data" if "data" in mesh.axis_names else None)
        sspec = P("model")
        y, dropped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(tok_spec, P(token_axes), tok_spec,
                      QT(wspec_up, sspec), QT(wspec_up, sspec),
                      QT(wspec_down, sspec)),
            out_specs=(tok_spec, P(token_axes)),
            check_vma=False,
        )(x_flat, top_ids, top_w, p["w_up"], p["w_gate"], p["w_down"])
        return y.reshape(b, s, d), _aux(balance, routed, dropped.sum())

    # single device
    cap = _capacity(cfg, t, mode)
    fn = _expert_runner(cfg, p, qcfg)
    y, dropped = _dispatch_combine_local(cfg, x_flat, top_ids, top_w, fn,
                                         cap, None)
    return y.reshape(b, s, d), _aux(balance, routed, dropped)


def _aux(balance, routed, dropped) -> dict:
    return {"balance": balance, "rows_routed": routed,
            "slots_dropped": jnp.asarray(dropped, jnp.int32)}


def no_aux(cfg) -> dict:
    """The zero of a layer's aux: the balance loss, and for MoE models
    the routing counts ``moe_block`` adds."""
    if not cfg.n_experts:
        return {"balance": jnp.zeros((), jnp.float32)}
    return _aux(jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32), 0)


def _dense_moe(cfg, p, x_flat, top_w, top_ids, qcfg):
    """Masked dense-experts combine for small T (decode): every held
    expert on every token."""
    t, d = x_flat.shape
    e = cfg.n_held
    combine = jnp.zeros((t, cfg.n_experts), jnp.float32).at[
        jnp.arange(t)[:, None], top_ids].set(top_w)[:, :e]
    ys = _experts_vmapped(cfg, p, jnp.broadcast_to(x_flat, (e, t, d)),
                          qcfg)                           # (E,T,d)
    y = jnp.einsum("etd,te->td", ys.astype(jnp.float32), combine)
    return y.astype(x_flat.dtype)
