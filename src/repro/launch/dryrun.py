import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell
with ShapeDtypeStruct inputs — proving the distribution config is
coherent without hardware — and record memory/cost/collective stats for
the roofline analysis (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.core import runtime_flags
from repro.configs.base import SHAPES, shape_applicable
from repro.configs.registry import ASSIGNED, get_config
from repro.distributed.sharding import use_mesh
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.train.steps import (
    TrainHParams,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
    "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e8m0fnu": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(tok: str) -> int:
    m = _SHAPE_RE.match(tok)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


# Per-device wire-byte factors (ring algorithms, large n): an all-reduce
# moves ~2x its (per-device) result shape over the links; gather/scatter/
# a2a/permute move ~1x.
_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0,
                "reduce-scatter": 1.0, "all-to-all": 1.0,
                "collective-permute": 1.0}

_RESULT_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+\[[\d,]*\]))\S*\s+(" + "|".join(_COLLECTIVES)
    + r")(?:-start)?\(")
_WHILE_RE = re.compile(
    r"while\(.*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_COMP_RE = re.compile(r"^%?([\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*(?://.*)?$")


def _split_computations(hlo_text: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    depth = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if cur is None:
            if s.endswith("{") and ("(" in s or s.startswith("ENTRY")):
                m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)", s)
                if m:
                    cur = m.group(1)
                    comps[cur] = []
                    depth = 1
            continue
        depth += s.count("{") - s.count("}")
        if depth <= 0:
            cur = None
            continue
        comps[cur].append(s)
    return comps


def _trip_count(cond_lines: list[str]) -> int:
    """Heuristic: the loop bound constant in the while condition."""
    consts = []
    for ln in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", ln):
            consts.append(int(m.group(1)))
    return max(consts) if consts else 1


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective wire bytes, including collectives inside
    while loops (scan-over-layers!) multiplied by their trip counts."""
    comps = _split_computations(hlo_text)
    # map computation -> ENTRY? figure entry name
    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY\s+%?([\w.\-]+)", line)
            entry = m.group(1) if m else None
            break
    if entry is None or entry not in comps:
        entry = next(iter(comps)) if comps else None

    bytes_by = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    by_dtype: dict[str, float] = {}
    calls_seen: set[str] = set()

    def shape_bytes(shape_str: str, mult: float = 0.0) -> int:
        total = 0
        for dt, dims in _SHAPE_RE.findall(shape_str):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            b = n * _DTYPE_BYTES.get(dt, 4)
            total += b
            if mult:
                by_dtype[dt] = by_dtype.get(dt, 0.0) + b * mult
        return total

    def walk(comp: str, mult: float):
        if comp not in comps:
            return
        key = f"{comp}@{mult}"
        if key in calls_seen:     # defensive against cycles
            return
        calls_seen.add(key)
        for ln in comps[comp]:
            m = _RESULT_RE.search(ln)
            if m:
                tuple_shapes, single, coll = m.groups()
                b = shape_bytes(tuple_shapes or single or "", mult)
                bytes_by[coll] += b * mult
                counts[coll] += int(mult)
            wm = _WHILE_RE.search(ln)
            if wm:
                cond, body = wm.groups()
                trips = _trip_count(comps.get(cond, []))
                walk(body, mult * trips)
            else:
                # follow call/fusion-to-computation edges
                cm = re.search(r"(?:calls|to_apply)=%?([\w.\-]+)", ln)
                if cm and cm.group(1) in comps:
                    walk(cm.group(1), mult)

    if entry:
        walk(entry, 1.0)
    wire = sum(_WIRE_FACTOR[k] * v for k, v in bytes_by.items())
    return {"bytes": {k: int(v) for k, v in bytes_by.items()},
            "counts": counts,
            "bytes_by_dtype": {k: int(v) for k, v in by_dtype.items()},
            "total_bytes": int(sum(bytes_by.values())),
            "wire_bytes_per_device": int(wire)}


def _memory_stats(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    stats = {k: int(getattr(m, k, 0)) for k in keys}
    stats["total_per_device"] = (stats["argument_size_in_bytes"]
                                 + stats["output_size_in_bytes"]
                                 + stats["temp_size_in_bytes"]
                                 - stats["alias_size_in_bytes"])
    return stats


def default_microbatches(cfg) -> int:
    """Bound the per-layer activation carry: wider residual streams,
    deeper stacks, and many-expert MoE (dispatch buffers) get more
    gradient-accumulation steps."""
    if cfg.d_model * cfg.n_layers >= 160_000 or cfg.n_experts >= 32:
        return 8
    return 4


def segment_probes(cfg, shape, mesh, n_mb: int) -> dict:
    """XLA's cost_analysis counts a while body ONCE, so scan-over-layers
    (and the microbatch scan) under-report FLOPs/bytes.  We compile a
    per-segment single-unit probe at the in-loop shapes and scale:

      adjusted = full + Σ_seg (reps_seg − 1) · probe_seg

    where reps = n_layers·n_microbatches (train) or n_layers (serve).
    The probe is fwd+bwd for train, fwd for prefill/decode — matching
    what the scan body contains.
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.core.linear import QT
    from repro.distributed.sharding import resolve_spec
    from repro.models.layers import (abstract_tree, quant_mask_tree,
                                     spec_tree)
    from repro.models.transformer import build_segments

    qcfg = cfg.quant
    kind = shape.kind
    b = shape.global_batch // (n_mb if kind == "train" else 1)
    s = 1 if kind == "decode" else shape.seq_len
    x_abs = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    x_sh = NamedSharding(mesh, resolve_spec(("batch", None, "embed"),
                                            mesh, x_abs.shape))
    positions = (0 if kind == "decode" else None)

    from repro.train.steps import _scale_dims

    probes = {}
    for seg in build_segments(cfg):
        mask = quant_mask_tree(seg.defs)
        sdims = _scale_dims(seg.defs)
        p_abs = abstract_tree(seg.defs)
        p_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                            spec_tree(seg.defs, mesh))
        mask_flat, treedef = jax.tree.flatten(mask)
        abs_flat = treedef.flatten_up_to(p_abs)
        sd_flat = treedef.flatten_up_to(sdims)
        # scales as traced args (constants would constant-fold slowly)
        sc_abs = tuple(jax.ShapeDtypeStruct(d.shape[:nd], jnp.float32)
                       for d, m, nd in zip(abs_flat, mask_flat, sd_flat)
                       if m)
        sc_sh = tuple(NamedSharding(mesh, resolve_spec((), mesh))
                      for _ in sc_abs)

        def wrap(p_l, sc, mask_flat=mask_flat, treedef=treedef):
            leaves = treedef.flatten_up_to(p_l)
            it = iter(sc)
            out = [QT(w, next(it)) if m else w
                   for w, m in zip(leaves, mask_flat)]
            return jax.tree.unflatten(treedef, out)

        if kind == "train":
            def probe_fn(p_l, sc, x, seg=seg, wrap=wrap):
                pos = jnp.arange(x.shape[1], dtype=jnp.int32)

                def f(p_l, x):
                    y, _, aux = seg.apply(cfg, qcfg, wrap(p_l, sc), x,
                                          pos, None, "train")
                    return y.astype(jnp.float32).sum() + aux

                if cfg.remat:   # match the scanned body: remat recompute
                    f = jax.checkpoint(f, prevent_cse=False)
                return jax.grad(f, argnums=(0, 1))(p_l, x)

            args, shs = (p_abs, sc_abs, x_abs), (p_sh, sc_sh, x_sh)
        else:
            cache_abs = (jax.eval_shape(
                lambda: seg.init_cache(cfg, shape.global_batch,
                                       shape.seq_len))
                if seg.init_cache else None)
            cache_sh = None
            if cache_abs is not None and seg.cache_logical:
                logical = seg.cache_logical(cfg)
                cache_sh = jax.tree.map(
                    lambda ax, leaf: NamedSharding(
                        mesh, resolve_spec(tuple(ax), mesh, leaf.shape)),
                    logical, cache_abs,
                    is_leaf=lambda x: isinstance(x, tuple)
                    and all(isinstance(e, (str, type(None))) for e in x))

            def probe_fn(p_l, sc, x, cache, seg=seg, kind=kind,
                         wrap=wrap):
                pos = (jnp.zeros((1,), jnp.int32) if kind == "decode"
                       else jnp.arange(x.shape[1], dtype=jnp.int32))
                y, c, _ = seg.apply(cfg, qcfg, wrap(p_l, sc), x, pos,
                                    cache, kind)
                return y, c

            args = (p_abs, sc_abs, x_abs, cache_abs)
            shs = (p_sh, sc_sh, x_sh, cache_sh)

        donate = (3,) if kind != "train" and args[3] is not None else ()
        compiled = jax.jit(probe_fn, in_shardings=shs,
                           donate_argnums=donate).lower(*args).compile()
        cost = dict(compiled.cost_analysis() or {})
        reps = seg.n * (n_mb if kind == "train" else 1)
        probes[seg.name] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "reps": reps,
        }
    return probes


def build_cell(arch: str, shape_name: str, mesh, overrides=None):
    """Returns (fn, abstract_args, in_shardings, donate) for one cell.
    ``overrides``: dict of ModelConfig.replace / hparam knobs for the
    §Perf hillclimb (e.g. {"microbatches": 8, "attn_chunk": 1024})."""
    import dataclasses as _dc

    from repro.core.formats import QuantConfig

    overrides = dict(overrides or {})
    n_mb = overrides.pop("microbatches", None)
    cfg = get_config(arch)
    q_kw = {k: v for k, v in overrides.items()
            if k in QuantConfig.__dataclass_fields__}
    if q_kw:
        cfg = cfg.replace(quant=_dc.replace(cfg.quant, **q_kw))
    cfg_kw = {k: v for k, v in overrides.items()
              if k in type(cfg).__dataclass_fields__}
    if cfg_kw:
        cfg = cfg.replace(**cfg_kw)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        hp = TrainHParams(
            microbatches=n_mb or default_microbatches(cfg))
        fn = make_train_step(cfg, hp, mesh)
        state = S.state_abstract(cfg)
        state_sh = S.state_shardings(cfg, mesh)
        batch, batch_sh = S.batch_specs(cfg, shape, mesh)
        return fn, (state, batch), (state_sh, batch_sh), (0,)
    if shape.kind == "prefill":
        fn = make_prefill_step(cfg, max_len=shape.seq_len)
        params = S.params_abstract(cfg)
        params_sh = S.params_shardings(cfg, mesh)
        batch, batch_sh = S.batch_specs(cfg, shape, mesh)
        return fn, (params, batch), (params_sh, batch_sh), ()
    # decode
    import jax.numpy as jnp

    fn = make_decode_step(cfg)
    pdt = overrides.pop("serve_params_dtype", None)
    params = S.params_abstract(
        cfg, jnp.bfloat16 if pdt == "bf16" else None)
    params_sh = S.params_shardings(cfg, mesh)
    caches = S.caches_abstract(cfg, shape)
    caches_sh = S.caches_shardings(cfg, shape, mesh)
    toks = S.decode_tokens_abstract(cfg, shape)
    toks_sh = S.decode_tokens_sharding(cfg, shape, mesh)
    return fn, (params, caches, toks), (params_sh, caches_sh, toks_sh), (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str | None = None, preset: str = "2d",
             overrides=None, tag: str = "") -> dict:
    from repro.distributed.presets import preset_rules
    from repro.distributed.sharding import sharding_rules

    runtime_flags.force_bf16_operands(True)   # TPU operand widths in HLO
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "n_devices": mesh.size, "preset": preset,
              "overrides": {k: str(v) for k, v in
                            (overrides or {}).items()}}
    cfg = get_config(arch)
    ok, reason = shape_applicable(cfg, SHAPES[shape_name])
    if not ok:
        record.update(status="skipped", reason=reason)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            path = os.path.join(
                out_dir, f"{arch}__{shape_name}__{mesh_name}{tag}.json")
            with open(path, "w") as f:
                json.dump(record, f, indent=1)
        return record
    try:
        with sharding_rules(preset_rules(preset)):
            fn, args, shardings, donate = build_cell(
                arch, shape_name, mesh, overrides)
        shape = SHAPES[shape_name]
        n_mb = ((overrides or {}).get("microbatches")
                or (default_microbatches(cfg) if shape.kind == "train"
                    else 1))
        with use_mesh(mesh), sharding_rules(preset_rules(preset)):
            jitted = jax.jit(fn, in_shardings=shardings,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            cost = dict(compiled.cost_analysis() or {})
            mem = _memory_stats(compiled)
            coll = parse_collectives(compiled.as_text())
            probes = segment_probes(cfg, shape, mesh, n_mb)
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        flops_adj = flops + sum(p["flops"] * (p["reps"] - 1)
                                for p in probes.values())
        bytes_adj = bytes_acc + sum(p["bytes"] * (p["reps"] - 1)
                                    for p in probes.values())
        record.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            flops=flops,
            bytes_accessed=bytes_acc,
            flops_adjusted=flops_adj,
            bytes_adjusted=bytes_adj,
            probes=probes,
            memory=mem,
            collectives=coll,
        )
    except Exception as e:  # record failures for triage
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    record["wall_s"] = round(time.time() - t0, 1)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(
            out_dir, f"{arch}__{shape_name}__{mesh_name}{tag}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--preset", default="2d")
    ap.add_argument("--tag", default="",
                    help="suffix for the artifact filename (§Perf runs)")
    ap.add_argument("--set", action="append", default=[],
                    help="knob override, e.g. --set microbatches=8 "
                         "--set attn_chunk=1024")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = (int(v) if v.lstrip("-").isdigit()
                        else (v == "True" if v in ("True", "False")
                              else v))

    if args.all:
        cells = [(a, s, mp) for a in ASSIGNED for s in SHAPES
                 for mp in (False, True)]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape, args.multi_pod)]

    failures = 0
    for arch, shape, mp in cells:
        rec = run_cell(arch, shape, mp, args.out, preset=args.preset,
                       overrides=overrides, tag=args.tag)
        status = rec["status"]
        extra = ""
        if status == "ok":
            extra = (f"flops={rec['flops_adjusted']:.3e} "
                     f"coll={rec['collectives']['total_bytes']:.3e}B "
                     f"mem={rec['memory']['total_per_device']/2**30:.2f}GiB "
                     f"[{rec['wall_s']}s]")
        elif status == "error":
            extra = rec["error"][:160]
            failures += 1
        print(f"{rec['mesh']:12s} {arch:24s} {shape:12s} {status:8s} "
              f"{extra}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
