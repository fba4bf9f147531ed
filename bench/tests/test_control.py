"""Each cell's control, at a tiny size on the CPU: the plain reference
computed one precision below the one the configuration states must
read as not correct against the cell's limits (the readings on the chip,
at the cell's own size, are in PERF.md)."""

import jax
import pytest

import tiny
from bench.lib import check, train_cell, weights
from bench.reference import train as ref_train
from bench.reference.quantize import Rounding

SEED = 2**31 + 777


@pytest.mark.parametrize("workload", ["train-olmo7b-moss", "train-olmo7b-bf16"])
def test_training_control_is_not_correct(workload):
    _, conf, job, limits = tiny.files(workload)
    key = weights.seed_key(SEED)
    feed = jax.jit(lambda k, i: train_cell.batch(conf, job, k, i))
    batches = [(b["tokens"], b["labels"]) for b in
               (feed(key, i) for i in range(train_cell.CHECKED_STEPS))]
    ref = ref_train.run(conf, job, key, batches)
    ctl = limits["control"]
    low = ref_train.run(conf, job, key, batches,
                        rounding=Rounding(ctl["fwd"], ctl["bwd"],
                                          ctl.get("group")))
    compared = check.compare_train(low, ref, limits["limits"])
    assert any(v > lim for v, lim in compared.values()), compared

