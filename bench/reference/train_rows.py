"""Three steps of plain reference training (``bench.reference.train``)
on a batch too large for one chip's activations: each step's gradient
is the mean of the gradients of equal blocks of rows, which is the
gradient of the whole batch's mean loss.  The blocks are spread over
the devices there are (the weights copied to each, the mean taken by
a ``pmean``), one after another on each device.

Everything else — the decoder, AdamW and the schedule written out, the
readings — is ``bench.reference.train``'s.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from bench.lib import weights
from . import decoder
from .quantize import Rounding
from .train import _adamw, _split, _unflat, flat, learning_rate


def run(conf: dict, job: dict, key, batches, rounding: Rounding | None = None,
        keep_rows: int | None = None) -> dict:
    """``bench.reference.train.run`` with each gradient taken over blocks
    of ``job["reference_rows"]`` rows.  ``keep_rows`` keeps only the
    first rows of every batch (the planted fault "half of the batch left
    out"); it must be a multiple of the block."""
    opt, rows = job["optimizer"], job["reference_rows"]
    maker = weights.LeafMaker(conf)
    params = maker.tree(key)
    value_grad = jax.value_and_grad(
        lambda p, t, l: decoder.loss(conf, p, t, l, rounding,
                                     job["reference_head_blocks"]))
    grad_fns: dict = {}
    sq = jax.jit(lambda x: jnp.sum(jnp.square(x)))
    upd = jax.jit(_adamw, static_argnames=("b1", "b2", "eps", "wd"),
                  donate_argnums=(0, 2, 3))
    moments: dict = {}
    losses, grad_norms = [], {}
    for t, (tokens, labels) in enumerate(batches):
        tokens, labels = np.asarray(tokens), np.asarray(labels)
        if keep_rows is not None:
            tokens, labels = tokens[:keep_rows], labels[:keep_rows]
        n = tokens.shape[0] // rows
        assert n * rows == tokens.shape[0], (tokens.shape, rows)
        if n not in grad_fns:
            grad_fns[n] = _block_mean(value_grad, n, rows)
        fn, mesh = grad_fns[n]
        rep = NamedSharding(mesh, P())
        loss, grads = fn(jax.device_put(params, rep),
                         *(jax.device_put(a, NamedSharding(mesh, P("blocks")))
                           for a in (tokens, labels)))
        loss = float(loss)
        acc = {name: np.asarray(g) for name, g in flat(grads).items()}
        del grads
        losses.append(loss)
        fp = flat(params)
        sqs = {name: float(sq(jnp.asarray(g))) for name, g in acc.items()}
        gnorm = math.sqrt(sum(sqs.values()))
        factor = min(1.0, opt["grad_clip"] / max(gnorm, 1e-9))
        if t == 0:
            grad_norms = {name: math.sqrt(v) * factor
                          for name, v in sqs.items()}
        lr = learning_rate(opt, t)
        for name in fp:
            m, v = moments.get(name, (None, None))
            if m is None:
                m = np.zeros(fp[name].shape, np.float32)
                v = np.zeros(fp[name].shape, np.float32)
            new_p, m, v = upd(fp[name], jnp.asarray(acc.pop(name)),
                              jnp.asarray(m), jnp.asarray(v),
                              jnp.float32(factor), jnp.float32(lr),
                              jnp.float32(t + 1), b1=opt["b1"], b2=opt["b2"],
                              eps=opt["eps"], wd=opt["weight_decay"])
            moments[name] = (np.asarray(m), np.asarray(v))
            fp[name] = new_p
        params = _unflat(fp, conf["num_hidden_layers"])
        del fp
    moments.clear()
    change = {}
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    for name, p in flat(params).items():
        leaf, layer = _split(name)
        change[name] = float(diff(p, maker(key, leaf, layer)))
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}


def _block_mean(value_grad, n: int, rows: int):
    """(jitted (params, tokens, labels) -> mean loss and gradient over
    ``n`` blocks of ``rows`` rows, the mesh it runs on): the blocks are
    split over as many devices as divide ``n``, each device's in turn."""
    devs = jax.devices()
    d = max(k for k in range(1, min(n, len(devs)) + 1) if n % k == 0)
    mesh = Mesh(np.asarray(devs[:d]), ("blocks",))

    def local(p, tokens, labels):
        loss, grads = 0.0, None
        for i in range(n // d):
            rows_i = slice(i * rows, (i + 1) * rows)
            lb, g = value_grad(p, tokens[rows_i], labels[rows_i])
            loss = loss + lb
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        scale = 1.0 / (n // d)
        return jax.lax.pmean((loss * scale, jax.tree.map(
            lambda g: g * scale, grads)), "blocks")

    fn = jax.jit(jax.shard_map(local, mesh=mesh,
                               in_specs=(P(), P("blocks"), P("blocks")),
                               out_specs=(P(), P())))
    return fn, mesh
