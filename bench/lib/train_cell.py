"""Runner of a training cell: one jitted step of the program
(``repro.train.steps.make_train_step``) with its state, driven back to
back on batches made from the seed.

Set-up builds the one compiled step and its state, then drives it
through its first three steps on the window's own feed; the reference
follows those three.  The window runs the same object on, step after
step, at least two and two seconds' worth dispatched ahead of the loss
the host reads, for ``--seconds``.

A job file (``bench/traffic/<name>.json``, ``"kind": "train"``) holds
the batch, the sequence length and the optimizer (AdamW, schedule,
clipping) as the program is to run them.
"""

from __future__ import annotations

import math
import time
from collections import deque

import jax
import jax.numpy as jnp

from . import program, weights
from .check import compare_train
from .spans import Spans

DATA_STREAM = 1000          # fold_in path of the batches, apart from leaves
CHECKED_STEPS = 3
# steps dispatched ahead of the loss the host reads: at least this
# many, and enough to keep QUEUED_S of work queued, so that a host
# stall shorter than that leaves the chip busy
MIN_IN_FLIGHT = 2
QUEUED_S = 2.0


def batch(conf: dict, job: dict, key, step):
    """Rows of uniform token ids, all different, for one step."""
    toks = jax.random.randint(
        weights.sub_key(key, DATA_STREAM, step),
        (job["batch"], job["seq"] + 1), 0, conf["vocab_size"], jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


class Cell:
    def __init__(self, run, hooks: dict):
        self.run = run
        self.hooks = hooks
        self.spans = Spans(tracing=run.tracing)

    def setup(self) -> None:
        from repro.optim.adamw import AdamWConfig, OptState
        from repro.train.steps import (TrainHParams, init_train_state,
                                       make_train_step)

        run, conf, job = self.run, self.run.conf, self.run.traffic
        opt = job["optimizer"]
        cfg = program.model_config(conf)
        hp = TrainHParams(
            peak_lr=opt["peak_lr"], warmup_steps=opt["warmup_steps"],
            total_steps=opt["total_steps"], grad_clip=opt["grad_clip"],
            adamw=AdamWConfig(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                              weight_decay=opt["weight_decay"]))
        self.key = key = weights.seed_key(run.seed)
        params = program.make_params(conf, key, jnp.float32)
        program.check_tree(cfg, params)
        self.state = jax.jit(
            lambda p: init_train_state(cfg, hp, None, params=p),
            donate_argnums=0)(params)
        del params
        step = jax.jit(make_train_step(cfg, hp), donate_argnums=(0,))
        self.step = self.hooks.get("step", lambda s: s)(step)
        self.feed = jax.jit(lambda k, i: batch(conf, job, k, i))
        names = flat_names(conf)
        mu_norms = jax.jit(lambda opt_tree: {
            n: jnp.linalg.norm(program.program_leaf(
                jax.tree.map(lambda s: s.mu, opt_tree,
                             is_leaf=lambda x: isinstance(x, OptState)),
                n)) for n in names})
        # the first steps, through the window's own call and feed
        losses, grad_norm, change_norm = [], {}, {}
        done_at = []
        for i in range(CHECKED_STEPS):
            self.state, m = self.step(self.state, self.feed(key, i))
            losses.append(float(m["loss"]))
            done_at.append(time.monotonic())
            if i == 0:
                grad_norm = {n: float(v) / (1.0 - opt["b1"])
                             for n, v in mu_norms(self.state.opt).items()}
        change_norm = self._change_norms(names)
        self.prog = {"loss": losses, "grad_norm": grad_norm,
                     "change_norm": change_norm}
        self.next_step = CHECKED_STEPS
        step_s = done_at[-1] - done_at[-2]
        self.in_flight = max(MIN_IN_FLIGHT, math.ceil(QUEUED_S / step_s))
        run.notes.append(f"steps dispatched ahead: {self.in_flight} (the "
                         f"last checked step took {step_s:.4f} s)")
        # the window's batch maker and step are compiled and warm; the
        # step's memory is read from the same program (a cache hit)
        b = jax.block_until_ready(self.feed(key, self.next_step))
        mem = step.lower(self.state, b).compile().memory_analysis()
        if mem is not None:
            run.extra["program_bytes"] = (mem.peak_memory_in_bytes,
                                          mem.argument_size_in_bytes)

    def _change_norms(self, names) -> dict:
        """|| params now - params at the seed || per leaf, with the start
        made again from the seed one leaf at a time."""
        conf, out, fns = self.run.conf, {}, {}
        for n in names:
            arr, layer = program.locate(self.state.params, n)
            name = n.split(".", 2)[2] if layer is not None else n
            if name not in fns:
                fns[name] = jax.jit(
                    lambda a, k, i, name=name, stacked=layer is not None:
                    jnp.linalg.norm(
                        (a[i] if stacked else a) - program.program_form(
                            conf, name, weights.make_leaf(conf, k, name, i))))
            out[n] = float(fns[name](arr, self.key, jnp.int32(layer or 0)))
        return out

    def measure(self) -> None:
        """Steps back to back for ``--seconds``, ``in_flight`` dispatched
        ahead of the one whose loss the host reads, as a training loop
        runs them; the window closes when the last dispatched step is
        done, and every step in it counts."""
        run, job = self.run, self.run.traffic
        pending: deque = deque()
        steps, bad = 0, 0
        run.end_to_end["setup_s"] = time.monotonic() - run.t_start
        t0 = time.monotonic()
        with self.spans.span("bench.window"):
            while pending or time.monotonic() - t0 < run.seconds:
                if time.monotonic() - t0 < run.seconds:
                    with self.spans.span("bench.train_step"):
                        b = self.feed(self.key, self.next_step)
                        self.state, m = self.step(self.state, b)
                    pending.append(m["loss"])
                    self.next_step += 1
                    if len(pending) <= self.in_flight:
                        continue
                loss = float(pending.popleft())
                steps += 1
                bad += not math.isfinite(loss)
        dt = time.monotonic() - t0
        tokens = steps * job["batch"] * job["seq"]
        run.end_to_end["train_tokens_per_s"] = tokens / dt
        run.attempted, run.failed = steps, bad
        run.extra.update(steps=steps, window_s=dt, tokens=tokens)

    def release(self) -> None:
        del self.state
        self.step = None

    def check(self) -> None:
        from bench.reference import train as ref_train

        run = self.run
        batches = [(b["tokens"], b["labels"])
                   for b in (self.feed(self.key, i)
                             for i in range(CHECKED_STEPS))]
        ref = ref_train.run(run.conf, run.traffic, self.key, batches)
        run.compared = compare_train(self.prog, ref, run.limits["limits"])
        run.extra["readings"] = {"program": self.prog, "reference": ref}


def flat_names(conf: dict) -> list[str]:
    names = list(weights.leaf_names(conf))
    for i in range(conf["num_hidden_layers"]):
        names += [f"layers.{i}.{n}" for n in weights.layer_leaf_names(conf)]
    return names
