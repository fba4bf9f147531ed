"""A configuration's architecture keys are honoured or refused, never
ignored, on both sides; the controls' rounding works per micro-group."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench.lib import program
from bench.reference import decoder
from bench.reference.quantize import round_to


@pytest.mark.parametrize("key,value", [("hidden_act", "gelu"),
                                       ("tie_word_embeddings", True)])
def test_unimplemented_architecture_is_refused(key, value):
    _, conf, job, _ = tiny.files("train-olmo7b-moss")
    conf = dict(conf, **{key: value})
    with pytest.raises(ValueError, match=key):
        program.model_config(conf)
    with pytest.raises(ValueError, match=key):
        decoder.check_conf(conf, job["seq"])


def test_sequence_past_max_positions_is_refused():
    _, conf, job, _ = tiny.files("train-olmo7b-moss")
    decoder.check_conf(conf, conf["max_position_embeddings"])
    with pytest.raises(ValueError, match="max_position_embeddings"):
        decoder.check_conf(conf, conf["max_position_embeddings"] + 1)


def test_stated_architecture_is_mapped():
    _, conf, _, _ = tiny.files("train-olmo7b-moss")
    cfg = program.model_config(conf)
    assert (cfg.act, cfg.tie_embeddings, cfg.norm) == ("swiglu", False,
                                                       "layernorm")


def test_round_to_scales_each_micro_group():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * jnp.repeat(
        jnp.array([1.0, 1e3]), 32)              # two groups far apart
    for axis, xs in ((1, x), (0, x.T)):
        y = np.asarray(round_to(xs, "int4", axis, 32))
        g = np.moveaxis(np.asarray(xs), axis, -1).reshape(-1, 2, 32)
        e = np.moveaxis(y, axis, -1).reshape(-1, 2, 32) - g
        step = np.abs(g).max(-1, keepdims=True) / 7
        assert np.all(np.abs(e) <= step / 2 + 1e-6 * step)
    # one scale per row would round the small group to nought
    assert np.all(np.asarray(round_to(x, "int4", 1))[:, :32] == 0)
    with pytest.raises(ValueError):
        round_to(x, "int4", 1, 48)
