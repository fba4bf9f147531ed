"""The data-parallel training cell (``train-olmo7b-moss-dp4``) at a tiny
size on 4 CPU devices, in a child process (the device count is fixed
when JAX starts): the reference by row blocks is the whole batch's
reference; the program on the (4, 1) mesh, fp8 gradient all-reduce
included, reads as the one-device program reads on the same batch; the
planted faults are not correct."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
WORKLOAD = "train-olmo7b-moss-dp4"


def _child(code: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_KERNELS", None)
    head = textwrap.dedent(f"""
        import json, sys, time
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r},
                        {str(ROOT / 'bench' / 'tests')!r}]
        import jax, jax.numpy as jnp
        import tiny
        from bench.lib import check, harness, train_cell, weights
        cell, conf, job, limits = tiny.files({WORKLOAD!r})
        job = dict(job, batch=8)
        files = (cell, conf, job, limits)
        SEED = 2**31 + 4321
    """)
    out = subprocess.run([sys.executable, "-c", head + textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_reference_by_row_blocks_is_the_whole_batch_reference():
    got = _child("""
        from bench.reference import train, train_rows
        key = weights.seed_key(SEED)
        feed = jax.jit(lambda k, i: train_cell.batch(conf, job, k, i))
        batches = [(b["tokens"], b["labels"]) for b in
                   (feed(key, i) for i in range(3))]
        whole = train.run(conf, job, key, batches)
        blocks = train_rows.run(conf, job, key, batches)
        print(json.dumps(check.train_readings(blocks, whole)))
    """)
    assert all(v < 1e-4 for v in got.values()), got


@pytest.fixture(scope="module")
def runs():
    """Readings of the cell's sound run, of each planted fault, and of
    the one-device cell's program on the same batches."""
    return _child("""
        def unchanged(step):
            def fault(state, batch):
                _, metrics = step(jax.tree.map(jnp.copy, state), batch)
                return state, metrics
            return fault

        def half(step):
            def fault(state, batch):
                return step(state, {k: v[:v.shape[0] // 2]
                                    for k, v in batch.items()})
            return fault

        res = {}
        for name, hook in (("sound", None), ("unchanged", unchanged),
                           ("half_batch", half)):
            out = harness.run_cell({WORKLOAD!r}, SEED, 0.5, False,
                                   time.monotonic(), allow_cpu=True,
                                   hooks={"step": hook} if hook else None,
                                   spec=tiny.spec(), files=files)
            res[name] = out
        from bench.lib import train_dp_cell
        run = harness.Run(cell=cell, conf=conf, traffic=job, limits=limits,
                          seed=SEED, seconds=0.0, tracing=False,
                          t_start=time.monotonic(), chips=4,
                          devices=jax.devices())
        dp = train_dp_cell.Cell(run, hooks={})
        dp.setup()
        one = train_cell.Cell(harness.Run(
            cell=dict(cell, chips=1), conf=conf, traffic=job, limits=limits,
            seed=SEED, seconds=0.0, tracing=False, t_start=time.monotonic(),
            devices=jax.devices()[:1]), hooks={})
        one.setup()
        res["dp_vs_one"] = check.train_readings(dp.prog, one.prog)
        print(json.dumps(res))
    """.replace("{WORKLOAD!r}", repr(WORKLOAD)))


def test_sound_run_reads_as_the_one_device_program(runs):
    """The mesh, the sharded state and the fp8 all-reduce of the
    gradients move the readings by less than half of what the fp8
    program's own rounding puts between it and the f32 reference (at
    this size: grad_gap 0.035 and change_gap 5.4e-3 against the
    reference, 0.007 and 1.6e-3 against the one-device program)."""
    sound = runs["sound"]
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert sound["device"]["count"] == 4
    gaps = runs["dp_vs_one"]
    for k, v in sound["compared"].items():
        assert gaps[k] < 0.5 * v["value"], (k, gaps, sound["compared"])


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_training_fault_is_not_correct(runs, fault):
    out = runs[fault]
    change = out["compared"]["change_gap"]
    assert not out["correct"]
    assert change["value"] > 10 * change["limit"], out["compared"]
