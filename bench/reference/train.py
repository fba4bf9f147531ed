"""Three steps of plain reference training, and the numbers a training
cell compares: the loss of each step, the norm of the first gradient as
the optimizer gets it (after clipping), and the norm of each leaf's
change over the three steps.

The job (optimizer, schedule, clipping) is the one the job file states;
AdamW and the schedule are written out here, from their published
definitions, and not taken from the program.  Adam's moments live on
the host between steps, one leaf at a time on the device, so the f32
reference fits one chip beside its weights and gradients.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights
from . import decoder
from .quantize import Rounding


def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``final_frac`` of the peak."""
    peak, warm = opt["peak_lr"], opt["warmup_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(opt["total_steps"] - warm, 1), 0.0),
               1.0)
    floor = peak * opt["final_frac"]
    return floor + 0.5 * (peak - floor) * (1.0 + math.cos(math.pi * prog))


def _adamw(p, g, m, v, factor, lr, t, b1, b2, eps, wd):
    g = g * factor
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    mhat = m / (1.0 - b1 ** t)
    vhat = v / (1.0 - b2 ** t)
    return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m, v


def flat(tree: dict) -> dict:
    """{"head": a, "layers": [{"wq": b}]} -> {"head": a, "layers.0.wq": b}."""
    out = {k: v for k, v in tree.items() if k != "layers"}
    for i, lay in enumerate(tree.get("layers", [])):
        out.update({f"layers.{i}.{k}": v for k, v in lay.items()})
    return out


def run(conf: dict, job: dict, key, batches, rounding: Rounding | None = None,
        keep_rows: int | None = None) -> dict:
    """The reference's readings over ``batches`` (a list of
    (tokens, labels) pairs, one per step).  ``keep_rows`` keeps only the
    first rows of every batch: the planted fault "half of the batch left
    out, the mean taken over the rest"."""
    opt = job["optimizer"]
    maker = weights.LeafMaker(conf)
    params = maker.tree(key)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: decoder.loss(conf, p, t, l, rounding,
                                     job["reference_head_blocks"])))
    sq = jax.jit(lambda x: jnp.sum(jnp.square(x)))
    upd = jax.jit(_adamw, static_argnames=("b1", "b2", "eps", "wd"),
                  donate_argnums=(0, 2, 3))
    moments: dict = {}
    losses, grad_norms = [], {}
    for t, (tokens, labels) in enumerate(batches):
        if keep_rows is not None:
            tokens, labels = tokens[:keep_rows], labels[:keep_rows]
        loss, grads = grad_fn(params, tokens, labels)
        losses.append(float(loss))
        fp, fg = flat(params), flat(grads)
        del grads
        sqs = {n: float(sq(g)) for n, g in fg.items()}
        gnorm = math.sqrt(sum(sqs.values()))
        factor = min(1.0, opt["grad_clip"] / max(gnorm, 1e-9))
        if t == 0:
            grad_norms = {n: math.sqrt(v) * factor for n, v in sqs.items()}
        lr = learning_rate(opt, t)
        for n in fp:
            m, v = moments.get(n, (None, None))
            if m is None:
                m = np.zeros(fp[n].shape, np.float32)
                v = np.zeros(fp[n].shape, np.float32)
            new_p, m, v = upd(fp[n], fg.pop(n), jnp.asarray(m), jnp.asarray(v),
                              jnp.float32(factor), jnp.float32(lr),
                              jnp.float32(t + 1), b1=opt["b1"], b2=opt["b2"],
                              eps=opt["eps"], wd=opt["weight_decay"])
            moments[n] = (np.asarray(m), np.asarray(v))
            fp[n] = new_p
        params = _unflat(fp, conf["num_hidden_layers"])
        del fp
    moments.clear()
    change = {}
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    for n, p in flat(params).items():
        name, layer = _split(n)
        change[n] = float(diff(p, maker(key, name, layer)))
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}


def _split(flat_name: str) -> tuple[str, int]:
    if flat_name.startswith("layers."):
        _, i, name = flat_name.split(".", 2)
        return name, int(i)
    return flat_name, 0


def _unflat(fp: dict, n_layers: int) -> dict:
    out = {k: v for k, v in fp.items() if not k.startswith("layers.")}
    out["layers"] = [{} for _ in range(n_layers)]
    for k, v in fp.items():
        if k.startswith("layers."):
            name, i = _split(k)
            out["layers"][i][name] = v
    return out
