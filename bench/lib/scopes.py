"""Program scopes of the training step, read from the device trace.

The program names parts of its step with ``jax.named_scope``
(``SCOPES``).  XLA keeps each op's name-stack path in the compiled
step's HLO (``metadata={op_name="jit(train_step)/transpose(jvp(head))/
moss.scale/convert_element_type"}``), backward ops under the autodiff
transforms, forward ops that ``jax.checkpoint`` recomputes under
``rematted_computation``.  An op belongs to the innermost scope on its
path.  The profiler's trace names each device op by its HLO instruction
name, so a map from instruction name to scope, built from the compiled
step's text, turns the trace's device time into time per scope.

A program without these scopes resolves nothing, and the readers then
report nothing.
"""

from __future__ import annotations

import re

from . import trace as tr

SCOPES = ("embed", "attn", "attn_core", "mlp", "head", "loss", "optimizer",
          "moss.scale")

# one component of a name-stack path, with the autodiff / batching
# transforms around it taken off: "transpose(jvp(head))" -> "head"
_PART = re.compile(r"^(?:(?:jvp|transpose|vmap)\()*([^()]*)\)*$")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_ARGS = re.compile(r" [\w\-]+\(([^)]*)\)")       # "opcode(%a, %b)"


def resolve(op_name: str) -> str | None:
    """The innermost scope of ``SCOPES`` on an op's name-stack path."""
    for part in reversed(op_name.split("/")):
        m = _PART.match(part)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


def hlo_scopes(text: str) -> dict[str, str | None]:
    """Instruction name -> scope, for every instruction of an HLO
    module's text.  An instruction XLA made, with no ``op_name`` of its
    own (a fusion, a copy), takes the scope most of the ops it fuses
    resolve to, else that of the first operand that has one."""
    out: dict[str, str | None] = {}
    body: dict[str, list[str]] = {}     # computation -> its instructions
    orphans = []                        # (name, fused computation, operands)
    comp = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            body[comp] = []
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        body[comp].append(name)
        op = _OP_NAME.search(line)
        out[name] = resolve(op.group(1)) if op else None
        if op is None:
            args = _ARGS.search(line, m.end())
            calls = _CALLS.search(line)
            orphans.append((name, calls.group(1) if calls else None,
                            re.findall(r"%([\w.\-]+)", args.group(1))
                            if args else []))
    for name, fused, operands in orphans:    # in order: operands first
        votes = [out[i] for i in body.get(fused, []) if out[i]]
        if votes:
            out[name] = max(sorted(set(votes)), key=votes.count)
        else:
            out[name] = next((out[o] for o in operands if out.get(o)), None)
    return out


def compile_step(conf: dict, job: dict):
    """The training cell's step (``bench.lib.train_cell``) on the default
    device, compiled from the shapes of its arguments: the program the
    window runs, so its instruction names are the trace's (on a chip, a
    hit in the persistent compilation cache)."""
    import jax
    import jax.numpy as jnp
    from repro.optim.adamw import AdamWConfig
    from repro.train.steps import (TrainHParams, init_train_state,
                                   make_train_step)

    from . import program, weights
    from .train_cell import batch

    opt = job["optimizer"]
    cfg = program.model_config(conf)
    hp = TrainHParams(
        peak_lr=opt["peak_lr"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], grad_clip=opt["grad_clip"],
        adamw=AdamWConfig(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                          weight_decay=opt["weight_decay"]))
    key = weights.seed_key(0)
    state = jax.eval_shape(lambda k: init_train_state(
        cfg, hp, None, params=program.make_params(conf, k, jnp.float32)), key)
    b = jax.eval_shape(lambda k: batch(conf, job, k, 0), key)
    step = jax.jit(make_train_step(cfg, hp), donate_argnums=(0,))
    return step.lower(state, b).compile()


def scope_ns(trace, lo: float, hi: float, names: dict) -> dict:
    """Device time in [lo, hi] of the ops of each scope (``Trace.leaves``,
    clipped as ``trace.op_ns`` clips them), averaged over the chips.
    ``names`` maps op names to scopes; ops it does not resolve count
    under None."""
    out: dict = {}
    for dev in trace.devices:
        for a, b, n in tr.clip(trace.leaves(dev), lo, hi):
            s = names.get(n)
            out[s] = out.get(s, 0.0) + (b - a)
    k = max(len(trace.devices), 1)
    return {s: t / k for s, t in out.items()}


def split_ms(trace, lo: float, hi: float, names: dict, steps: int):
    """(device ms per step of each scope, the share of the window's device
    time in no scope), or None where no op resolves to a scope."""
    ns = scope_ns(trace, lo, hi, names)
    total = sum(ns.values())
    if total <= 0 or not any(s for s in ns):
        return None
    return ({s: ns.get(s, 0.0) / 1e6 / steps for s in SCOPES},
            ns.get(None, 0.0) / total)


def step_ms(run) -> dict:
    """Device ms per step of each scope in the window of a traced
    one-chip training run, {} where the program has no scopes.  Made
    once a run, after the window, with two earlier stdout lines: the
    split, and the compiles the program counted in the window."""
    if "scope_ms" not in run.extra:
        run.extra["scope_ms"] = _step_ms(run)
    return run.extra["scope_ms"]


def _step_ms(run) -> dict:
    if run.chips != 1 or not run.trace.devices:   # a map of one chip's step
        return {}
    compiled = compile_step(run.conf, run.traffic)
    lo, hi = run.window_ns
    got = split_ms(run.trace, lo, hi, hlo_scopes(compiled.as_text()),
                   run.extra["steps"])
    if got is None:
        return {}
    split, unresolved = got
    run.notes.append(
        "device ms per step by program scope: "
        + ", ".join(f"{s} {v:.3f}" for s, v in split.items())
        + f"; in no scope: {100.0 * unresolved:.3f} % of the window's "
        "device time")
    n = _window_compiles(run)
    if n is not None:
        run.notes.append(f"compiles in the window: {n}")
    return split


def _window_compiles(run) -> int | None:
    """Backend compiles (``repro.obs.metrics``) that ended in the window,
    which starts ``setup_s`` after the run's start on the host's
    monotonic clock; None where the program does not count them."""
    from repro.obs import metrics

    between = getattr(metrics, "compiles_between", None)
    if between is None:
        return None
    t0 = run.t_start + run.end_to_end["setup_s"]
    return between(t0, t0 + run.extra["window_s"])


def read_ms(run, *names: str) -> float | None:
    """A per-layer reader: device ms per step of the scopes ``names``."""
    split = step_ms(run)
    if not split:
        return None
    return sum(split[s] for s in names)
