"""Token-choice top-k Mixture of Experts with explicit expert parallelism.

Train/prefill path (mesh present): shard_map over (pod, data, model) —
tokens are split across *all* mesh axes for dispatch, experts live on
the ``model`` axis, and two ``all_to_all`` collectives move token
buffers to/from their experts (the torch-EP pattern, expressed
jax-natively; the collectives land in the HLO where the roofline
collective term can count them).

Dispatch is sort-based (argsort by expert id + capacity truncation) —
never materializes a (T, E, C) one-hot.  Per-device buffer is
(E, C_local, d) with C_local = ceil(T_local·k·cf/E).

Decode path (T small): masked dense-experts combine — every expert runs
on every token.  With batch≥experts·top_k the full expert weights are
read anyway, so the memory roofline is identical and decode stays
simple and shardable (DESIGN.md §3).

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
from jax.sharding import PartitionSpec as P

from repro.core.formats import QuantConfig
from repro.core.linear import QT, qlinear, qlinear_grouped
from repro.core.runtime_flags import moe_expert_path
from repro.distributed.sharding import active_mesh
from .layers import PDef


def moe_defs(cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    defs = {
        "router": PDef((d, e), (None, None)),          # f32, not quantized
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

    ``sizes`` is the ragged per-expert valid-row count from dispatch;
    None (the post-all_to_all EP case, where the counts live on the
    source shards) means every capacity slot is treated as full —
    dense-equivalent compute, still one launch per GEMM."""
    e, c, d = xs.shape
    if sizes is None:
        sizes = jnp.full((e,), c, jnp.int32)
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


def _dispatch_combine_local(cfg, x_loc, ids_loc, w_loc, expert_fn,
                            capacity: int, model_axis: str | None):
    """Per-device dispatch -> (all_to_all) -> experts -> (all_to_all) ->
    combine.  Runs inside shard_map (or standalone without a mesh)."""
    t_loc, d = x_loc.shape
    k = ids_loc.shape[-1]
    e = cfg.n_experts

    flat_ids = ids_loc.reshape(-1)                       # (T·k,)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    # position within expert group
    group_start = jnp.searchsorted(sorted_ids, jnp.arange(e))
    group_end = jnp.searchsorted(sorted_ids, jnp.arange(e), side="right")
    # ragged per-expert valid-row counts — the grouped kernel's group
    # sizes (capacity truncation applied, zero-size experts allowed)
    sizes = jnp.minimum(group_end - group_start,
                        capacity).astype(jnp.int32)
    pos = jnp.arange(t_loc * k) - group_start[sorted_ids]
    token_of = order // k
    keep = pos < capacity
    # scatter tokens into (E, C, d); dropped tokens overflow to a trash row
    buf = jnp.zeros((e * capacity + 1, d), x_loc.dtype)
    dest = jnp.where(keep, sorted_ids * capacity + pos, e * capacity)
    buf = buf.at[dest].set(x_loc[token_of])
    xs = buf[:-1].reshape(e, capacity, d)

    if model_axis is not None:
        xs = jax.lax.all_to_all(xs, model_axis, split_axis=0,
                                concat_axis=1, tiled=True)
        ys = expert_fn(xs, None)   # counts live on the source shards
        ys = jax.lax.all_to_all(ys, model_axis, split_axis=1,
                                concat_axis=0, tiled=True)
    else:
        ys = expert_fn(xs, sizes)                        # (E, C, d)

    ybuf = jnp.concatenate(
        [ys.reshape(e * capacity, d),
         jnp.zeros((1, d), ys.dtype)], axis=0)
    gathered = ybuf[dest]                                # (T·k, d) sorted
    # unsort back to (T, k, d)
    unsort = jnp.argsort(order, stable=True)
    per_slot = gathered[unsort].reshape(t_loc, k, d)
    y = jnp.einsum("tkd,tk->td", per_slot.astype(jnp.float32),
                   w_loc.astype(jnp.float32))
    return y.astype(x_loc.dtype)


def _capacity(cfg, t_local: int) -> int:
    c = int(t_local * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return -(-c // 8) * 8                                # round up to 8


def moe_block(cfg, p, x, qcfg: QuantConfig, mode: str = "train"):
    """x: (B, S, d) -> (y, aux_loss)."""
    b, s, d = x.shape
    x_flat = x.reshape(-1, d)
    t = x_flat.shape[0]
    logits, probs = router_probs(cfg, p, x_flat)
    top_w, top_ids = jax.lax.top_k(probs, cfg.top_k)     # (T,k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    aux = load_balance_loss(probs, top_ids, cfg.n_experts, cfg.top_k)

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
            not use_ep and cfg.moe_decode_dense and t <= 4096):
        y = _dense_moe(cfg, p, x_flat, probs, top_w, top_ids, qcfg)
        return y.reshape(b, s, d), aux

    if use_ep:
        token_axes = tuple(a for a in ("pod", "data", "model")
                           if a in mesh.axis_names)
        n_tok_shards = 1
        for a in token_axes:
            n_tok_shards *= mesh.shape[a]
        m = mesh.shape["model"]
        t_loc = t // n_tok_shards
        cap = _capacity(cfg, t_loc)

        def body(x_loc, ids_loc, w_loc, w_up, w_gate, w_down):
            # FSDP all-gather of expert weights over the data axis
            if "data" in mesh.axis_names:
                w_up = jax.lax.all_gather(w_up.w, "data", axis=1, tiled=True), w_up.s
                w_gate = jax.lax.all_gather(w_gate.w, "data", axis=1, tiled=True), w_gate.s
                w_down = jax.lax.all_gather(w_down.w, "data", axis=2, tiled=True), w_down.s
                w_up, w_gate, w_down = (QT(*w_up), QT(*w_gate), QT(*w_down))
            pl = {"w_up": w_up, "w_gate": w_gate, "w_down": w_down}
            fn = _expert_runner(cfg, pl, qcfg)
            return _dispatch_combine_local(cfg, x_loc, ids_loc, w_loc, fn,
                                           cap, "model")

        tok_spec = P(token_axes, None)
        wspec_up = P("model", "data" if "data" in mesh.axis_names else None,
                     None)
        wspec_down = P("model", None,
                       "data" if "data" in mesh.axis_names else None)
        sspec = P("model")
        y = jax.shard_map(
            body, mesh=mesh,
            in_specs=(tok_spec, P(token_axes), tok_spec,
                      QT(wspec_up, sspec), QT(wspec_up, sspec),
                      QT(wspec_down, sspec)),
            out_specs=tok_spec,
            check_vma=False,
        )(x_flat, top_ids, top_w, p["w_up"], p["w_gate"], p["w_down"])
        return y.reshape(b, s, d), aux

    # single-device fallback (smoke tests)
    cap = _capacity(cfg, t)
    fn = _expert_runner(cfg, p, qcfg)
    y = _dispatch_combine_local(cfg, x_flat, top_ids, top_w, fn, cap, None)
    return y.reshape(b, s, d), aux


def _dense_moe(cfg, p, x_flat, probs, top_w, top_ids, qcfg):
    """Masked dense-experts combine for small T (decode)."""
    t, d = x_flat.shape
    combine = jnp.zeros((t, cfg.n_experts), jnp.float32).at[
        jnp.arange(t)[:, None], top_ids].set(top_w)
    ys = _experts_vmapped(cfg, p, jnp.broadcast_to(x_flat, (cfg.n_experts, t, d)),
                          qcfg)                           # (E,T,d)
    y = jnp.einsum("etd,te->td", ys.astype(jnp.float32), combine)
    return y.astype(x_flat.dtype)
