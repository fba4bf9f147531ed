"""Jaxpr introspection: structural op counts for the serving contract.

The pre-quantized serving path (docs/serving.md) promises that the
decode graph contains **zero weight quantize / weight max-reduction
ops**, and the fused decode-attention path
(docs/decode-attention.md) that it contains **zero cache-sized
dequantization upcasts or dots** — structural properties, checked
directly on the jaxpr rather than inferred from wall clock (which on
CPU measures fp8 emulation).  Used by ``tests/test_serving.py``,
``tests/test_decode_attn.py`` and ``benchmarks/run.py``'s
``BENCH_serve.json`` / ``BENCH_decode.json`` rows.
"""

from __future__ import annotations

from typing import Iterator

import jax
import jax.numpy as jnp

from jax.extend.core import ClosedJaxpr, Jaxpr


def iter_eqns(jaxpr, skip_into: tuple[str, ...] = ()) -> Iterator:
    """Depth-first over every equation of a (Closed)Jaxpr, descending
    into sub-jaxprs (scan/while bodies, cond branches, pjit calls,
    custom_vjp calls) via the eqn params.  Primitives named in
    ``skip_into`` are yielded but NOT descended into — pass
    ``("pallas_call",)`` to count XLA-level (HBM-visible) ops only,
    excluding arithmetic that happens on VMEM blocks inside a kernel
    body."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in skip_into:
            continue
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from iter_eqns(sub, skip_into)


def _sub_jaxprs(val):
    if isinstance(val, (ClosedJaxpr, Jaxpr)):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _sub_jaxprs(v)
    elif callable(val) and hasattr(val, "jaxpr"):   # pjit's WrappedFun-likes
        sub = getattr(val, "jaxpr")
        if isinstance(sub, (ClosedJaxpr, Jaxpr)):
            yield sub


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name`` (e.g. "reduce_max") anywhere in
    the jaxpr, sub-jaxprs included.  NOTE: an op inside a scan body is
    counted once, not once per trip — counts are *structural*."""
    return sum(1 for e in iter_eqns(jaxpr) if e.primitive.name == name)


def count_reduce_max_over(jaxpr, sizes: set[int]) -> int:
    """reduce_max equations whose operand element count is in ``sizes``
    — with the quantized weight-slice sizes this counts *weight* amax
    reductions (the in-graph scale computation pre-quantization
    removes)."""
    n = 0
    for e in iter_eqns(jaxpr):
        if e.primitive.name != "reduce_max":
            continue
        op_size = 1
        for d in e.invars[0].aval.shape:
            op_size *= d
        if op_size in sizes:
            n += 1
    return n


_FP8_DTYPES = (jnp.float8_e4m3fn, jnp.float8_e5m2)


def count_fp8_casts(jaxpr, sizes: set[int] | None = None) -> int:
    """convert_element_type-to-fp8 equations, optionally restricted to
    operands whose element count is in ``sizes`` — pass the quantized
    weight-slice sizes (``weight_slice_sizes``) to count *weight*
    quantizations only (activation casts have per-token-batch sizes,
    disjoint from weight sizes for any realistic config)."""
    n = 0
    for e in iter_eqns(jaxpr):
        if e.primitive.name != "convert_element_type":
            continue
        if e.params.get("new_dtype") not in _FP8_DTYPES:
            continue
        op_size = 1
        for d in e.invars[0].aval.shape:
            op_size *= d
        if sizes is None or op_size in sizes:
            n += 1
    return n


def _op_size(var) -> int:
    n = 1
    for d in var.aval.shape:
        n *= d
    return n


def count_fp8_dequant_upcasts(jaxpr, sizes: set[int]) -> int:
    """convert_element_type equations FROM an fp8 dtype to a wider one
    whose operand element count is in ``sizes`` — with the KV-cache
    slice sizes (``kv_cache_slice_sizes``) this counts decode-attention
    *dequantizations* of the cache payload: the scale-folding einsum
    path upcasts the whole e4m3 K and V to feed the MXU (2 per layer),
    the fused kernel reads the payload directly (0).  pallas_call
    bodies are not descended into — in-kernel upcasts act on VMEM
    blocks, not HBM-resident tensors."""
    n = 0
    for e in iter_eqns(jaxpr, skip_into=("pallas_call",)):
        if e.primitive.name != "convert_element_type":
            continue
        if e.invars[0].aval.dtype not in _FP8_DTYPES:
            continue
        if e.params.get("new_dtype") in _FP8_DTYPES:
            continue
        if _op_size(e.invars[0]) in sizes:
            n += 1
    return n


# Primitives a quantizer's *scale arithmetic* may route through between
# an amax reduction and the final fp8 cast: abs/max chains, the
# FP8_MAX / TINY normalization, E8M0 encode (log2/ceil/clip) and decode
# (bit shifts + bitcast), the zero-denominator guard (comparisons +
# select_n), and shape plumbing.  Deliberately EXCLUDES ``exp`` and
# ``dot_general`` so a softmax's max-subtraction chain (max → sub → exp)
# dies at the exp and never reaches a downstream quantize through the
# attention output (tests/test_introspect.py's negative controls).
_SCALE_CHAIN_PRIMS = frozenset({
    "abs", "max", "min", "div", "mul", "sub", "add", "neg", "sign",
    "reduce_max", "reduce_min", "reshape", "broadcast_in_dim", "squeeze",
    "convert_element_type", "clamp", "select_n", "gt", "lt", "ge", "le",
    "eq", "ne", "log", "log2", "ceil", "floor", "round", "exp2",
    "integer_pow", "pow", "rsqrt", "sqrt", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "or", "and", "xor",
    "bitcast_convert_type", "transpose", "slice", "dynamic_slice",
    "stop_gradient", "concatenate", "copy", "is_finite",
})


def count_quant_reductions(jaxpr) -> int:
    """max/abs-reduction equations whose result *feeds a quantize* — a
    ``convert_element_type`` to an fp8 dtype — through scale arithmetic
    only.

    This is the structural definition of "the graph computes a
    quantization scale at runtime": every just-in-time quantizer
    (per-tensor, per-group, MOSS two-level, KV-cache write) starts with
    a ``reduce_max`` over ``|x|`` and ends in an fp8 cast, with nothing
    between them but scale arithmetic (``_SCALE_CHAIN_PRIMS``).  The
    delayed/predicted-scale serving path (docs/serving.md) consumes
    cached scales instead, so its decode jaxpr counts **zero** — while
    a softmax's max (max → sub → **exp**) or a masking max is never
    miscounted: the allowlisted chain stops at the first non-scale
    primitive.

    Reachability FOLLOWS CALL BOUNDARIES: the fp8 cast often sits in a
    ``pjit`` sub-jaxpr of the scan/custom_vjp body holding the
    reduction, so taint maps positionally through call-like eqns
    (eqn invar i ↔ body invar i, eqn outvar j ↔ body outvar j — exact
    for pjit / scan / custom_vjp / remat; ``cond`` shifts by the
    predicate).  Counts are structural — a reduction inside a scan
    body counts once, not once per trip."""
    total = 0
    seen: set[int] = set()

    def walk(jx):
        nonlocal total
        if isinstance(jx, ClosedJaxpr):
            jx = jx.jaxpr
        if id(jx) in seen:
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            if (eqn.primitive.name == "reduce_max"
                    and _taint_flow(jx, {id(v) for v in eqn.outvars})[0]):
                total += 1
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    walk(sub)

    walk(jaxpr)
    return total


def _is_var(v) -> bool:
    return hasattr(v, "aval") and not hasattr(v, "val")  # not a Literal


def _taint_flow(jx, start_ids=frozenset(), in_positions=()):
    """Propagate taint forward through ONE jaxpr (eqns are in
    topological order) and into call-like sub-jaxprs by positional
    invar/outvar mapping.  Returns ``(reached_fp8_cast,
    tainted_outvar_positions)``."""
    if isinstance(jx, ClosedJaxpr):
        jx = jx.jaxpr
    tainted = set(start_ids)
    for i in in_positions:
        if i < len(jx.invars):
            tainted.add(id(jx.invars[i]))
    found = False
    for eqn in jx.eqns:
        tin = [i for i, v in enumerate(eqn.invars)
               if _is_var(v) and id(v) in tainted]
        if not tin:
            continue
        name = eqn.primitive.name
        subs = [s for val in eqn.params.values() for s in _sub_jaxprs(val)]
        if subs:
            off = 1 if name == "cond" else 0
            pos = [i - off for i in tin if i >= off]
            for sub in subs:
                f, tout = _taint_flow(sub, in_positions=pos)
                found = found or f
                for o in tout:
                    if o < len(eqn.outvars):
                        tainted.add(id(eqn.outvars[o]))
            continue
        if (name == "convert_element_type"
                and eqn.params.get("new_dtype") in _FP8_DTYPES):
            found = True
            continue
        if name in _SCALE_CHAIN_PRIMS:
            for v in eqn.outvars:
                tainted.add(id(v))
        # else: chain dies at a non-scale primitive
    tout = {i for i, v in enumerate(jx.outvars)
            if _is_var(v) and id(v) in tainted}
    return found, tout


def count_dot_general_over(jaxpr, sizes: set[int]) -> int:
    """dot_general equations with an operand whose element count is in
    ``sizes`` — with the KV-cache slice sizes this counts the einsum
    decode path's score and combine contractions against the cache
    (2 per layer; the fused kernel leaves 0 at the XLA level — its
    in-kernel dots act on blocks and are excluded via skip_into)."""
    n = 0
    for e in iter_eqns(jaxpr, skip_into=("pallas_call",)):
        if e.primitive.name != "dot_general":
            continue
        if any(_op_size(v) in sizes for v in e.invars):
            n += 1
    return n


def kv_cache_slice_sizes(cfg, batch: int, max_len: int) -> set[int]:
    """Element count of ONE layer's K (or V) cache payload — the shape
    the scan-over-layers decode body sees, i.e. the operand size of a
    cache dequant upcast / cache dot in the decode jaxpr.  Callers must
    pick test shapes where this doesn't collide with activation or
    weight slice sizes (trivially true for the smoke configs)."""
    from repro.models.attention import cache_len

    c = cache_len(cfg, max_len)
    return {batch * cfg.n_kv * c * cfg.head_dim}


def weight_slice_sizes(cfg) -> set[int]:
    """Element counts of every quantized weight's per-(layer, expert)
    slice — the shapes the scan-over-layers forward quantizes (and the
    shapes a weight-quantize cast would have in the decode jaxpr)."""
    from repro.models.layers import PDef, is_pdef
    from repro.models.transformer import model_defs
    from repro.train.steps import _scale_dims

    defs = model_defs(cfg)
    sdims = _scale_dims(defs)
    sizes: set[int] = set()

    def add(d: PDef, nd: int):
        n = 1
        for dim in d.shape[nd:]:
            n *= dim
        if d.quantized:
            sizes.add(n)

    jax.tree.map(add, defs, sdims, is_leaf=is_pdef)
    return sizes


def count_pallas_calls(compiled) -> int:
    """Pallas TPU kernel launches in a compiled executable's HLO — zero
    on CPU, where kernels run as the jnp reference or interpreted."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')
