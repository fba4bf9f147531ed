"""Per-architecture smoke tests: reduced config, one forward + one train
step on CPU, asserting output shapes + no NaNs (harness deliverable f)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SHAPES, shape_applicable
from repro.configs.registry import ASSIGNED, get_config
from repro.core.formats import MOSS_CONFIG
from repro.models.layers import init_tree, quant_mask_tree, wrap_qt_nojit
from repro.models.transformer import forward, model_defs
from repro.train.steps import (
    TrainHParams,
    init_train_state,
    make_train_step,
)

B, S = 2, 64


def _batch(cfg, key=1):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(key),
                                          (B, S), 0, cfg.vocab)}
    if cfg.input_mode == "embeddings":
        batch["embeds"] = jax.random.normal(
            jax.random.PRNGKey(key + 1), (B, S, cfg.d_model),
            jnp.float32).astype(jnp.bfloat16)
    batch["labels"] = batch["tokens"]
    return batch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch, smoke=True)
    defs = model_defs(cfg)
    params = init_tree(defs, jax.random.PRNGKey(0))
    qp = wrap_qt_nojit(params, quant_mask_tree(defs))
    batch = _batch(cfg)
    logits, _, aux = forward(cfg, MOSS_CONFIG, qp, batch, mode="train")
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux["balance"]))
    if cfg.n_experts:
        assert float(aux["balance"]) > 0.0   # load-balance loss active


@pytest.mark.slow
@pytest.mark.parametrize("arch", ASSIGNED)
def test_one_train_step(arch):
    cfg = get_config(arch, smoke=True)
    hp = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=10)
    state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, hp))
    state, metrics = step(state, _batch(cfg))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert int(state.step) == 1
    for leaf in jax.tree.leaves(state.params):
        assert bool(jnp.isfinite(leaf).all())


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "rwkv6-3b",
                                  "recurrentgemma-2b",
                                  "deepseek-v2-lite-16b"])
def test_loss_decreases_on_repeated_batch(arch):
    cfg = get_config(arch, smoke=True)
    hp = TrainHParams(peak_lr=2e-3, warmup_steps=2, total_steps=30)
    state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, hp))
    batch = _batch(cfg)
    losses = []
    for _ in range(10):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_shape_applicability_matrix():
    """long_500k runs exactly on the sub-quadratic archs."""
    runnable = {a: [s for s in SHAPES
                    if shape_applicable(get_config(a), SHAPES[s])[0]]
                for a in ASSIGNED}
    subq = {"rwkv6-3b", "recurrentgemma-2b", "h2o-danube-3-4b"}
    for a in ASSIGNED:
        assert "train_4k" in runnable[a]
        assert "prefill_32k" in runnable[a]
        assert "decode_32k" in runnable[a]
        assert ("long_500k" in runnable[a]) == (a in subq), a
    total = sum(len(v) for v in runnable.values())
    assert total == 33       # 40 cells - 7 documented skips


@pytest.mark.slow
@pytest.mark.parametrize("arch", ASSIGNED)
def test_microbatched_step_matches_full(arch):
    """Gradient accumulation is loss-equivalent to the full batch.
    Run in bf16 so per-microbatch quantization scales (which legally
    differ from full-batch scales) don't blur the comparison."""
    from repro.core.formats import BF16_CONFIG

    cfg = get_config(arch, smoke=True).replace(quant=BF16_CONFIG)
    hp1 = TrainHParams(peak_lr=0.0, warmup_steps=1, total_steps=2,
                       microbatches=1, grad_clip=1e9)
    hp2 = hp1._replace(microbatches=2)
    batch = _batch(cfg)
    s1 = init_train_state(cfg, hp1, jax.random.PRNGKey(0))
    s2 = init_train_state(cfg, hp2, jax.random.PRNGKey(0))
    _, m1 = jax.jit(make_train_step(cfg, hp1))(s1, batch)
    _, m2 = jax.jit(make_train_step(cfg, hp2))(s2, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 0.02


def test_yarn_rope_as_published():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4096 positions, β 32/1,
    mscale 0.707 on both): the ramp runs over rope pairs 10..23
    (c(n) = 64 ln(4096/(2πn)) / (2 ln 10^4): 10.47 and 22.54), pairs
    below keep θ^(-2i/64), pairs from 23 on divide it by 40, the
    softmax scale is 192^-0.5 · m² with m = 0.1·0.707·ln 40 + 1, and
    cos/sin keep magnitude 1.  Plain rope configs are untouched."""
    import numpy as np

    from repro.models.layers import (rope_for, rope_frequencies,
                                     yarn_frequencies, yarn_ramp)
    from repro.models.mla import softmax_scale

    cfg = get_config("deepseek-v2-lite-16b")
    assert yarn_ramp(cfg, 64) == (10, 23)
    f = np.asarray(rope_frequencies(64, 1e4))
    y = np.asarray(yarn_frequencies(cfg, 64))
    np.testing.assert_allclose(y[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(y[23:], f[23:] / 40, rtol=1e-6)
    i = 16                                   # (16 - 10) / 13 of the way
    np.testing.assert_allclose(y[i], f[i] * (7 / 13) + f[i] / 40 * (6 / 13),
                               rtol=1e-6)
    assert abs(softmax_scale(cfg) / 192 ** -0.5 - 1.5896) < 1e-4
    assert rope_for(cfg, 64)[1] == 1.0
    plain = get_config("olmo-7b")
    assert rope_for(plain, 128) == (None, 1.0)
    assert softmax_scale(cfg.replace(yarn_factor=0.0)) == 192 ** -0.5
