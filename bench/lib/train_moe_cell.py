"""Runner of a training cell of a latent-attention MoE model (DeepSeek-V2
configurations, ``"kind": "train_moe"``): the training cell of
``bench.lib.train_cell`` — one jitted step of the program's
``make_train_step``, its first three steps checked against a plain
reference, then steps back to back for ``--seconds`` — with the model's
own weights (``bench.lib.weights_moe``), layout (``bench.lib.program_moe``)
and reference (``bench.reference.mla_moe``).

Training dispatch drops nothing: every checked and timed step must report
``moe_slots_dropped`` 0, or it counts as failed.  The routed rows of the
window's steps (``moe_rows_routed``) go to ``run.extra`` for the readers
of the routed GEMMs' work.

The job file adds to the training job's keys ``aux_coef`` (the balance
loss's coefficient) and the reference's block sizes.
"""

from __future__ import annotations

import math
import time
from collections import deque

import jax
import jax.numpy as jnp

from . import moe_scopes, program_moe, train_cell, weights
from . import weights_moe as wm
from .check import compare_train


def hparams(job: dict):
    from repro.optim.adamw import AdamWConfig
    from repro.train.steps import TrainHParams

    opt = job["optimizer"]
    return TrainHParams(
        peak_lr=opt["peak_lr"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], grad_clip=opt["grad_clip"],
        aux_coef=job["aux_coef"],
        adamw=AdamWConfig(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                          weight_decay=opt["weight_decay"]))


def reference(conf: dict, job: dict, key, batches, rounding=None,
              keep_rows: int | None = None) -> dict:
    """Readings of three steps of ``bench.reference.mla_moe``."""
    from bench.reference import mla_moe

    return mla_moe.run(conf, job, key, batches, rounding, keep_rows)


class Cell(train_cell.Cell):
    def setup(self) -> None:
        from repro.optim.adamw import OptState
        from repro.train.steps import init_train_state, make_train_step

        run, conf, job = self.run, self.run.conf, self.run.traffic
        program_moe.check_conf(conf, job["seq"])
        cfg = program_moe.model_config(conf)
        hp = hparams(job)
        self.key = key = weights.seed_key(run.seed)
        params = program_moe.make_params(conf, key, jnp.float32)
        program_moe.check_tree(cfg, params)
        self.state = jax.jit(
            lambda p: init_train_state(cfg, hp, None, params=p),
            donate_argnums=0)(params)
        del params
        step = jax.jit(make_train_step(cfg, hp), donate_argnums=(0,))
        self.step = self.hooks.get("step", lambda s: s)(step)
        self.feed = jax.jit(lambda k, i: train_cell.batch(conf, job, k, i))
        names = wm.flat_names(conf)
        mu_norms = jax.jit(lambda opt_tree: {
            n: jnp.linalg.norm(program_moe.program_leaf(
                conf, jax.tree.map(lambda s: s.mu, opt_tree,
                                   is_leaf=lambda x: isinstance(x, OptState)),
                n)) for n in names})
        losses, grad_norm, dropped, done_at = [], {}, 0, []
        for i in range(train_cell.CHECKED_STEPS):
            self.state, m = self.step(self.state, self.feed(key, i))
            losses.append(float(m["loss"]))
            dropped += int(m["moe_slots_dropped"])
            done_at.append(time.monotonic())
            if i == 0:
                grad_norm = {n: float(v) / (1.0 - job["optimizer"]["b1"])
                             for n, v in mu_norms(self.state.opt).items()}
        run.attempted, run.failed = 0, int(dropped > 0)
        self.prog = {"loss": losses, "grad_norm": grad_norm,
                     "change_norm": self._change_norms(names)}
        self.next_step = train_cell.CHECKED_STEPS
        step_s = done_at[-1] - done_at[-2]
        self.in_flight = max(train_cell.MIN_IN_FLIGHT,
                             math.ceil(train_cell.QUEUED_S / step_s))
        run.notes.append(f"steps dispatched ahead: {self.in_flight} (the "
                         f"last checked step took {step_s:.4f} s); slots "
                         f"dropped in the checked steps: {dropped}")
        b = jax.block_until_ready(self.feed(key, self.next_step))
        compiled = step.lower(self.state, b).compile()
        mem = compiled.memory_analysis()
        if mem is not None:
            run.extra["program_bytes"] = (mem.peak_memory_in_bytes,
                                          mem.argument_size_in_bytes)
        if run.tracing:
            # the per-scope readers (``lib.scopes.read_ms``) read this
            # step's map once the window's trace is loaded
            run.extra["scope_ms"] = moe_scopes.Split(
                run, moe_scopes.hlo_scopes(compiled.as_text()))

    def _change_norms(self, names) -> dict:
        """|| params now - params at the seed || per leaf, the start made
        again from the seed one leaf at a time."""
        conf, out, fns = self.run.conf, {}, {}
        for n in names:
            arr, j = program_moe.locate(conf, self.state.params, n)
            name, layer = (n.split(".", 2)[2], int(n.split(".")[1])) \
                if j is not None else (n, 0)
            if name not in fns:
                fns[name] = jax.jit(
                    lambda a, k, i, j, name=name, stacked=j is not None:
                    jnp.linalg.norm(
                        (a[j] if stacked else a) - program_moe.program_form(
                            name, wm.make_leaf(conf, k, name, i))))
            out[n] = float(fns[name](arr, self.key, jnp.int32(layer),
                                     jnp.int32(j or 0)))
        return out

    def measure(self) -> None:
        """``train_cell.Cell.measure``, with every timed step's dropped
        slots read beside its loss: a step that drops one fails."""
        run, job = self.run, self.run.traffic
        pending: deque = deque()
        steps, bad, routed = 0, run.failed, 0
        run.end_to_end["setup_s"] = time.monotonic() - run.t_start
        t0 = time.monotonic()
        with self.spans.span("bench.window"):
            while pending or time.monotonic() - t0 < run.seconds:
                if time.monotonic() - t0 < run.seconds:
                    with self.spans.span("bench.train_step"):
                        b = self.feed(self.key, self.next_step)
                        self.state, m = self.step(self.state, b)
                    pending.append((m["loss"], m["moe_slots_dropped"],
                                    m["moe_rows_routed"]))
                    self.next_step += 1
                    if len(pending) <= self.in_flight:
                        continue
                loss, dropped, rows = (float(v) for v in pending.popleft())
                steps += 1
                routed += rows
                bad += not math.isfinite(loss) or dropped != 0
        dt = time.monotonic() - t0
        tokens = steps * job["batch"] * job["seq"]
        run.end_to_end["train_tokens_per_s"] = tokens / dt
        run.attempted, run.failed = steps, bad
        run.extra.update(steps=steps, window_s=dt, tokens=tokens,
                         moe_rows_routed=routed / max(steps, 1))
        run.notes.append(f"moe rows routed per step of the window: "
                         f"{routed / max(steps, 1):.1f}")

    def check(self) -> None:
        run = self.run
        batches = [(b["tokens"], b["labels"])
                   for b in (self.feed(self.key, i)
                             for i in range(train_cell.CHECKED_STEPS))]
        ref = reference(run.conf, run.traffic, self.key, batches)
        run.compared = compare_train(self.prog, ref, run.limits["limits"])
        run.extra["readings"] = {"program": self.prog, "reference": ref}
