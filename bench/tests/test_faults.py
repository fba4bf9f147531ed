"""Runs of the harness at a tiny size on the CPU, past its look for a
chip, with the timed path sound and with it broken underneath: each
fault the cell can have must turn ``correct`` false.

  - a training step that returns its state unchanged;
  - half of the batch left out, the mean taken over the rest.
"""

import time

import jax
import jax.numpy as jnp
import pytest

import tiny
from bench.lib import harness


def _run(workload, hooks=None, seconds=1.0):
    return harness.run_cell(workload, 2**31 + 12345, seconds, False,
                            time.monotonic(), allow_cpu=True, hooks=hooks,
                            spec=tiny.spec(), files=tiny.files(workload))


def _unchanged(step):
    def fault(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return fault


def _half_batch(step):
    def fault(state, batch):
        return step(state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    return fault


TRAIN = ["train-olmo7b-moss", "train-olmo7b-bf16"]


@pytest.mark.parametrize("workload", TRAIN)
@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_training_fault_is_not_correct(workload, fault):
    """At this size sound runs read the change gap within a few times
    the limit set at the cell's size; each fault reads it tens of times
    over."""
    out = _run(workload, hooks={"step": fault})
    change = out["compared"]["change_gap"]
    assert not out["correct"]
    assert change["value"] > 10 * change["limit"], out["compared"]

