"""The MoE training cell (``train-dsv2lite-moss``) at a tiny size on the
CPU: the program's held-expert layer against the plain reference, the
cell's step through the Pallas kernels (interpreted) against the
reference, its control and planted faults, the MoE scopes of its step,
and the work its readers count."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import tiny_moe
from bench.lib import (check, harness, moe_scopes, program_moe, scopes,
                       train_cell, train_moe_cell, weights, work, work_moe)
from bench.lib import trace as tr
from bench.lib import weights_moe as wm
from bench.reference import mla_moe
from bench.reference.quantize import Rounding

SEED = 2**31 + 99
# At d = 128 the fp8 operands' noise is several times the chip size's:
# the sound step reads grad_gap 0.04-0.07 and change_gap about 0.01 here
# (0.01-0.03 and 1-3e-3 at the cell's size), the int4-per-32 control
# grad_gap 0.15.  A bf16-everywhere reference reads 0.002-0.005, below
# the fp8 program, so it is no control of it.
TINY_LIMITS = {"loss_gap": 0.01, "grad_gap": 0.1, "change_gap": 0.03}


def _batches(conf, job, key):
    feed = jax.jit(lambda k, i: train_cell.batch(conf, job, k, i))
    return [(b["tokens"], b["labels"]) for b in
            (feed(key, i) for i in range(train_cell.CHECKED_STEPS))]


def test_shares_of_a_layer_add_up_to_the_whole_layer():
    """Each of 8 chips holds 2 of a layer's 16 experts and routes over
    all 16: the routed parts of the 8 shares, plus the shared experts
    counted once, are the uncut reference layer's output."""
    from repro.core.formats import BF16_CONFIG
    from repro.models import moe
    from repro.models.layers import apply_ffn

    _, conf, _, _ = tiny_moe.files()
    conf = dict(conf, n_routed_experts=16, num_experts_per_tok=6)
    key = weights.seed_key(SEED)
    lw = {n: wm.make_leaf(conf, key, n, 1) for n in wm.MOE}
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 64, conf["hidden_size"]))
    want, _ = mla_moe.moe(conf, lw, h, None)
    cfg = program_moe.model_config(dict(conf, n_routed_experts=2)).replace(
        quant=BF16_CONFIG)
    shared = apply_ffn(cfg, {"w_gate": _qt(lw["s_gate"]),
                             "w_up": _qt(lw["s_up"]),
                             "w_down": _qt(lw["s_down"])},
                       h.astype(jnp.bfloat16), cfg.quant).astype(jnp.float32)
    total = shared
    for j in range(8):
        held = slice(2 * j, 2 * j + 2)
        # this share's experts first: the router's columns turned so
        p = {"router": jnp.roll(lw["router"], -2 * j, axis=1),
             "w_gate": lw["e_gate"][held], "w_up": lw["e_up"][held],
             "w_down": lw["e_down"][held]}
        y, _ = moe.moe_block(cfg, {k: v if k == "router" else
                                   _qt(v) for k, v in p.items()},
                             h.astype(jnp.bfloat16), cfg.quant, mode="train")
        total = total + y.astype(jnp.float32)
    # the uncut layer in the program: the same bf16 arithmetic
    whole = program_moe.model_config(conf).replace(quant=BF16_CONFIG)
    y, _ = moe.moe_block(whole, {k: _qt(lw["e" + k[1:]]) if k != "router"
                                 else lw["router"] for k in
                                 ("router", "w_gate", "w_up", "w_down")},
                         h.astype(jnp.bfloat16), cfg.quant, mode="train")
    uncut = y.astype(jnp.float32) + shared
    err = float(jnp.linalg.norm(total - uncut) / jnp.linalg.norm(uncut))
    assert err < 2e-3, err         # bf16 rounding of the partial sums
    err = float(jnp.linalg.norm(total - want) / jnp.linalg.norm(want))
    assert err < 0.02, err         # bf16 operands of three GEMMs vs f32


def _qt(w):
    from repro.core.linear import QT

    return QT(w, None)


@pytest.fixture(scope="module")
def interpreted():
    """The cell's program, kernels interpreted, and the reference's
    readings of the same seed."""
    cell, conf, job, limits = tiny_moe.files()
    run = harness.Run(cell=cell, conf=conf, traffic=job, limits=limits,
                      seed=SEED, seconds=0.5, tracing=False,
                      t_start=time.monotonic(), devices=jax.devices())
    prior = os.environ.get("REPRO_KERNELS")
    os.environ["REPRO_KERNELS"] = "interpret"
    try:
        c = train_moe_cell.Cell(run, hooks={})
        c.setup()
        c.measure()
    finally:
        if prior is None:
            del os.environ["REPRO_KERNELS"]
        else:
            os.environ["REPRO_KERNELS"] = prior
    c.release()
    key = weights.seed_key(SEED)
    batches = _batches(conf, job, key)
    return run, c.prog, mla_moe.run(conf, job, key, batches), batches


def test_step_through_the_kernels_matches_the_reference(interpreted):
    run, prog, ref, _ = interpreted
    readings = check.train_readings(prog, ref)
    assert all(v <= TINY_LIMITS[k] for k, v in readings.items()), readings
    assert run.attempted >= 1 and run.failed == 0
    # dropless: every held expert's rows of the 2 x 64 tokens routed
    assert run.extra["moe_rows_routed"] > 0


def test_control_one_precision_down_fails(interpreted):
    _, _, ref, batches = interpreted
    _, conf, job, limits = tiny_moe.files()
    ctl = limits["control"]
    low = mla_moe.run(conf, job, weights.seed_key(SEED), batches,
                      rounding=Rounding(ctl["fwd"], ctl["bwd"],
                                        ctl.get("group")))
    readings = check.train_readings(low, ref)
    assert any(v > TINY_LIMITS[k] for k, v in readings.items()), readings


def _unchanged(step):
    def fault(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return fault


def _half_batch(step):
    def fault(state, batch):
        return step(state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    return fault


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_training_fault_is_not_correct(fault):
    out = harness.run_cell(tiny_moe.WORKLOAD, 2**31 + 12345, 0.5, False,
                           time.monotonic(), allow_cpu=True,
                           hooks={"step": fault}, spec=tiny.spec(),
                           files=tiny_moe.files())
    change = out["compared"]["change_gap"]
    assert not out["correct"]
    assert change["value"] > TINY_LIMITS["change_gap"] * 5, out["compared"]


def test_unimplemented_configuration_is_refused():
    _, conf, job, _ = tiny_moe.files()
    for key, value in [("scoring_func", "sigmoid"), ("q_lora_rank", 1536),
                       ("routed_scaling_factor", 16)]:
        with pytest.raises(ValueError, match=key):
            program_moe.model_config(dict(conf, **{key: value}))
    with pytest.raises(ValueError, match="max_position_embeddings"):
        program_moe.check_conf(conf, conf["max_position_embeddings"] + 1)


def test_step_holds_the_moe_scopes():
    """The compiled step's instructions resolve to the MoE layer's own
    scopes beside the step's; ``lib.scopes`` alone resolves the same
    ops to ``mlp``."""
    cell, conf, job, limits = tiny_moe.files()
    from repro.train.steps import init_train_state, make_train_step

    cfg = program_moe.model_config(conf)
    hp = train_moe_cell.hparams(job)
    key = weights.seed_key(0)
    state = jax.eval_shape(lambda k: init_train_state(
        cfg, hp, None, params=program_moe.make_params(conf, k, jnp.float32)),
        key)
    b = jax.eval_shape(lambda k: train_cell.batch(conf, job, k, 0), key)
    text = jax.jit(make_train_step(cfg, hp)).lower(state, b).compile(
        ).as_text()
    got = set(moe_scopes.hlo_scopes(text).values())
    assert {"moe.router", "moe.dispatch", "moe.experts", "attn",
            "attn_core", "mlp", "head", "optimizer", "moss.scale"} <= got
    assert scopes.SCOPES == moe_scopes.SCOPES[:len(scopes.SCOPES)]
    assert "moe.dispatch" not in set(scopes.hlo_scopes(text).values())


def test_split_reads_the_trace_once_it_is_there():
    ms = 1e6
    t = tr.Trace()
    t.devices["/device:TPU:0"] = [(0, 10 * ms, "a"), (10 * ms, 30 * ms, "b"),
                                  (30 * ms, 40 * ms, "c")]
    run = harness.Run(cell={}, conf={}, traffic={}, limits={}, seed=0,
                      seconds=0, tracing=True, t_start=0.0)
    split = moe_scopes.Split(run, {"a": "moe.dispatch", "b": "head"})
    run.extra.update(scope_ms=split, steps=2, window_s=0.04)
    run.trace, run.window_ns = t, (0, 40 * ms)
    run.end_to_end["setup_s"] = 0.0
    assert scopes.read_ms(run, "moe.dispatch") == pytest.approx(5.0)
    assert scopes.read_ms(run, "head", "loss") == pytest.approx(10.0)
    assert split["moe.experts"] == 0.0
    assert run.notes[0].startswith("device ms per step by program scope")
    empty = moe_scopes.Split(run, {})
    assert scopes.read_ms(
        harness.Run(cell={}, conf={}, traffic={}, limits={}, seed=0,
                    seconds=0, tracing=True, t_start=0.0,
                    extra={"scope_ms": empty}), "head") is None


def test_work_counts_by_hand():
    """Hand-worked: one MoE layer, d 4, f 2, 2 held experts, 3 routed
    rows per step."""
    conf = {"hidden_size": 4, "num_attention_heads": 1, "vocab_size": 8,
            "kv_lora_rank": 2, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
            "v_head_dim": 2, "intermediate_size": 6,
            "moe_intermediate_size": 2, "n_shared_experts": 1,
            "n_routed_experts": 2, "n_routed_experts_published": 4,
            "num_hidden_layers": 2, "first_k_dense_replace": 1}
    mla = 4 * 4 + 4 * 2 + 4 * 2 + 2 * 2 + 2 * 2 + 2 * 4     # 48 MACs
    per_token = 2 * mla + 3 * 4 * 6 + 4 * 4 + 3 * 4 * 2 + 4 * 8
    assert sum(k * n for k, n in work_moe.token_shapes(conf)) == per_token
    # seq 1: context 1, 2 layers x 1 head x (4 + 2) x 2
    want = 3 * (2 * (per_token * 5 + 3 * 4 * 2 * 3) + 2 * 2 * 6 * 5)
    assert work_moe.train_flops(conf, 1, 5, 3) == want
    gemms = work_moe.routed_gemms(conf, 3)
    assert len(gemms) == 9
    # up projection forward: 2 x 3 x 2 x 4 ops; 3x4 bf16 in, 2 experts'
    # 4x2 f32 weights, 3x2 bf16 out
    assert gemms[0] == (48.0, 3 * 4 * 2 + 2 * 4 * 2 * 4 + 3 * 2 * 2)
    assert all(ops == 48.0 for ops, _ in gemms)
    peak = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e9}
    assert work.least_s(*gemms[0], peak) == 48.0
    np.testing.assert_allclose(
        work_moe.attn_flops_per_token(conf, 10.0), 2 * 2 * 1 * 6 * 10)


def test_mx_gemms_leave_out_the_router():
    """The MX kernels' GEMMs are every token GEMM but the f32 routers:
    forward, input and weight gradient each."""
    conf = {"hidden_size": 4, "num_attention_heads": 1, "vocab_size": 8,
            "kv_lora_rank": 2, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
            "v_head_dim": 2, "intermediate_size": 6,
            "moe_intermediate_size": 2, "n_shared_experts": 1,
            "n_routed_experts": 2, "n_routed_experts_published": 4,
            "num_hidden_layers": 2, "first_k_dense_replace": 1}
    every = work_moe.token_shapes(conf)
    mx = work_moe.token_shapes(conf, mx_only=True)
    assert len(every) - len(mx) == work_moe.moe_layers(conf)
    assert sum(k * n for k, n in every) - sum(k * n for k, n in mx) == 4 * 4
    gemms = work_moe.mx_gemms(conf, 5)
    assert len(gemms) == 3 * len(mx)
    assert sum(ops for ops, _ in gemms) == 3 * 2 * 5 * sum(
        k * n for k, n in mx)
