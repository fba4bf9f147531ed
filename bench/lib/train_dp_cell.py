"""Runner of a data-parallel training cell (``"kind": "train_dp"``): the
training cell of ``bench.lib.train_cell`` on a mesh of the cell's chips.

The job file adds to the training job's keys ``mesh`` (``data`` x
``model`` chips), ``grad_comm_fp8`` (the gradient all-reduce in fp8 with
error feedback, ``QuantConfig.grad_comm_fp8``) and ``reference_rows``.
``batch`` is the global batch, split over the ``data`` axis.

The program's step is ``make_train_step(cfg, hp, mesh)``, its state born
sharded by the program's own rules (``repro.launch.specs``) and every
batch made sharded on ``data``; the window is ``train_cell``'s.  The
reference is ``bench.reference.train`` with each gradient the mean over
blocks of ``reference_rows`` rows (``bench.reference.train_rows``), so
the whole batch's f32 reference fits one chip.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import program, train_cell, weights
from .check import compare_train


def reference(conf: dict, job: dict, key, batches, rounding=None,
              keep_rows: int | None = None) -> dict:
    """Readings of three steps of the reference, by row blocks."""
    from bench.reference import train_rows

    return train_rows.run(conf, job, key, batches, rounding, keep_rows)


class Cell(train_cell.Cell):
    def setup(self) -> None:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import use_mesh
        from repro.launch.mesh import mesh_from_devices
        from repro.launch.specs import state_shardings
        from repro.optim.adamw import AdamWConfig, OptState
        from repro.train.steps import (TrainHParams, init_train_state,
                                       make_train_step)

        run, conf, job = self.run, self.run.conf, self.run.traffic
        opt, shape = job["optimizer"], job["mesh"]
        mesh = mesh_from_devices(
            np.asarray(run.devices).reshape(shape["data"], shape["model"]),
            ("data", "model"))
        cfg = program.model_config(conf)
        cfg = cfg.replace(quant=dataclasses.replace(
            cfg.quant, grad_comm_fp8=job["grad_comm_fp8"]))
        hp = TrainHParams(
            peak_lr=opt["peak_lr"], warmup_steps=opt["warmup_steps"],
            total_steps=opt["total_steps"], grad_clip=opt["grad_clip"],
            adamw=AdamWConfig(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                              weight_decay=opt["weight_decay"]))
        self.key = key = weights.seed_key(run.seed)
        params = program.make_params(conf, key, jnp.float32)
        program.check_tree(cfg, params)
        with use_mesh(mesh):
            self.state = jax.jit(
                lambda p: init_train_state(cfg, hp, None, params=p),
                out_shardings=state_shardings(cfg, mesh),
                donate_argnums=0)(params)
            del params
            step = jax.jit(make_train_step(cfg, hp, mesh),
                           donate_argnums=(0,))

        def in_mesh(*args):
            with use_mesh(mesh):
                return step(*args)

        self.step = self.hooks.get("step", lambda s: s)(in_mesh)
        rows = NamedSharding(mesh, P("data", None))
        self.feed = jax.jit(lambda k, i: train_cell.batch(conf, job, k, i),
                            out_shardings=rows)
        names = train_cell.flat_names(conf)
        mu_norms = jax.jit(lambda opt_tree: {
            n: jnp.linalg.norm(program.program_leaf(
                jax.tree.map(lambda s: s.mu, opt_tree,
                             is_leaf=lambda x: isinstance(x, OptState)),
                n)) for n in names})
        losses, grad_norm, done_at = [], {}, []
        for i in range(train_cell.CHECKED_STEPS):
            self.state, m = self.step(self.state, self.feed(key, i))
            losses.append(float(m["loss"]))
            done_at.append(time.monotonic())
            if i == 0:
                grad_norm = {n: float(v) / (1.0 - opt["b1"])
                             for n, v in mu_norms(self.state.opt).items()}
        self.prog = {"loss": losses, "grad_norm": grad_norm,
                     "change_norm": self._change_norms(names)}
        self.next_step = train_cell.CHECKED_STEPS
        step_s = done_at[-1] - done_at[-2]
        self.in_flight = max(train_cell.MIN_IN_FLIGHT,
                             math.ceil(train_cell.QUEUED_S / step_s))
        run.notes.append(f"steps dispatched ahead: {self.in_flight} (the "
                         f"last checked step took {step_s:.4f} s); mesh "
                         f"{dict(mesh.shape)}")
        b = jax.block_until_ready(self.feed(key, self.next_step))
        with use_mesh(mesh):
            mem = step.lower(self.state, b).compile().memory_analysis()
        if mem is not None:
            run.extra["program_bytes"] = (mem.peak_memory_in_bytes,
                                          mem.argument_size_in_bytes)

    def check(self) -> None:
        run = self.run
        one = run.devices[0]
        batches = [tuple(jax.device_put(np.asarray(b[k]), one)
                         for k in ("tokens", "labels"))
                   for b in (self.feed(self.key, i)
                             for i in range(train_cell.CHECKED_STEPS))]
        ref = reference(run.conf, run.traffic, self.key, batches)
        run.compared = compare_train(self.prog, ref, run.limits["limits"])
        run.extra["readings"] = {"program": self.prog, "reference": ref}
