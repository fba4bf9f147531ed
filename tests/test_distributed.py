"""Distribution tests — run real multi-device computations on 8 CPU
devices in SUBPROCESSES (the 512-device override belongs only to
dryrun; tests must not pollute this process's device count)."""

import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_dp_training_matches_single_device():
    """Same data, same init: 8-way DP loss == single-device loss."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_config
        from repro.train.steps import TrainHParams, init_train_state, make_train_step
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import use_mesh
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_config("olmo-7b", smoke=True)
        hp = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=10)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8))
        batch = data.batch_for_step(0)

        state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
        _, m_single = jax.jit(make_train_step(cfg, hp))(state, batch)

        mesh = make_host_mesh(model=1)   # 8-way data parallel
        with use_mesh(mesh):
            state2 = init_train_state(cfg, hp, jax.random.PRNGKey(0))
            _, m_dp = jax.jit(make_train_step(cfg, hp, mesh))(state2, batch)
        print("SINGLE", float(m_single["loss"]), "DP", float(m_dp["loss"]))
        assert abs(float(m_single["loss"]) - float(m_dp["loss"])) < 1e-2
    """)
    assert "SINGLE" in out


def test_dp_training_on_pallas_kernels_matches_single_device():
    """A Pallas call cannot be partitioned by GSPMD, so under a mesh the
    dispatch layer runs each MOSS kernel per batch shard in shard_map
    (dW summed over shards).  4-way DP through the interpreted kernels
    == the single-device kernel step."""
    out = run_with_devices("""
        import os
        os.environ["REPRO_KERNELS"] = "interpret"
        import jax
        from repro.configs.registry import get_config
        from repro.train.steps import TrainHParams, init_train_state, make_train_step
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import use_mesh
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_config("olmo-7b", smoke=True)
        hp = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=10)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=4))
        batch = data.batch_for_step(0)

        state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
        _, m1 = jax.jit(make_train_step(cfg, hp))(state, batch)

        mesh = make_host_mesh(model=1)   # 4-way data parallel
        with use_mesh(mesh):
            state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
            _, m4 = jax.jit(make_train_step(cfg, hp, mesh))(state, batch)
        l1, l4 = float(m1["loss"]), float(m4["loss"])
        g1, g4 = float(m1["grad_norm"]), float(m4["grad_norm"])
        print("LOSS", l1, l4, "GNORM", g1, g4)
        assert abs(l1 - l4) < 1e-5 * l1
        assert abs(g1 - g4) < 1e-4 * g1
    """, n=4)
    assert "LOSS" in out


def test_kernel_backend_refuses_tensor_parallel_mesh():
    """The MOSS kernels run per batch shard only: on a (2, 2) data x
    model mesh an interpret-mode train step raises instead of running
    each kernel on the whole weight on both model devices."""
    out = run_with_devices("""
        import os
        os.environ["REPRO_KERNELS"] = "interpret"
        import jax
        from repro.configs.registry import get_config
        from repro.train.steps import TrainHParams, init_train_state, make_train_step
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import use_mesh
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_config("olmo-7b", smoke=True)
        hp = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=10)
        batch = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64,
                                       global_batch=4)).batch_for_step(0)
        mesh = make_host_mesh(model=2)
        with use_mesh(mesh):
            state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
            try:
                jax.jit(make_train_step(cfg, hp, mesh)).lower(state, batch)
            except NotImplementedError as e:
                print("REFUSED", e)
    """, n=4)
    assert "REFUSED" in out and "'model': 2" in out, out


def test_tp_training_matches_single_device():
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.configs.registry import get_config
        from repro.train.steps import TrainHParams, init_train_state, make_train_step
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import use_mesh
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_config("phi3-mini-3.8b", smoke=True)
        hp = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=10)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=4))
        batch = data.batch_for_step(0)
        state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
        _, m1 = jax.jit(make_train_step(cfg, hp))(state, batch)
        mesh = make_host_mesh(model=4)   # 2 data x 4 model
        with use_mesh(mesh):
            state2 = init_train_state(cfg, hp, jax.random.PRNGKey(0))
            _, m2 = jax.jit(make_train_step(cfg, hp, mesh))(state2, batch)
        d = abs(float(m1["loss"]) - float(m2["loss"]))
        print("TPDIFF", d)
        assert d < 1e-2, d
    """)
    assert "TPDIFF" in out


def test_moe_ep_runs_on_mesh():
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.configs.registry import get_config
        from repro.train.steps import TrainHParams, init_train_state, make_train_step
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import use_mesh
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
        hp = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=10)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8))
        mesh = make_host_mesh(model=4)
        with use_mesh(mesh):
            state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
            step = jax.jit(make_train_step(cfg, hp, mesh))
            losses = []
            for t in range(4):
                state, m = step(state, data.batch_for_step(t))
                losses.append(float(m["loss"]))
        print("EPLOSSES", losses)
        assert all(l == l for l in losses)   # finite
    """)
    assert "EPLOSSES" in out


def test_fp8_grad_compression_converges():
    """fp8 all-reduce with error feedback: loss parity with exact DP."""
    out = run_with_devices("""
        import jax, numpy as np
        from repro.configs.registry import get_config
        from repro.core.formats import QuantConfig
        from repro.train.steps import TrainHParams, init_train_state, make_train_step
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import use_mesh
        from repro.data.pipeline import DataConfig, SyntheticLM

        mesh = make_host_mesh(model=1)
        losses = {}
        for comp in (False, True):
            cfg = get_config("olmo-7b", smoke=True).replace(
                quant=QuantConfig(mode="moss", weight_scaling="auto",
                                  grad_comm_fp8=comp))
            hp = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=30)
            data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64,
                                          global_batch=8))
            with use_mesh(mesh):
                state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
                step = jax.jit(make_train_step(cfg, hp, mesh))
                ls = []
                for t in range(30):
                    state, m = step(state, data.batch_for_step(t))
                    ls.append(float(m["loss"]))
            losses[comp] = np.mean(ls[-5:])
        gap = abs(losses[True] - losses[False]) / losses[False]
        print("COMPGAP", gap)
        assert gap < 0.03, gap
    """)
    assert "COMPGAP" in out


def test_elastic_checkpoint_reshard():
    """Save on an 8-device mesh, restore onto 4 devices (elastic)."""
    out = run_with_devices("""
        import tempfile, os
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import manager as ckpt
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import use_mesh, named_sharding

        mesh8 = make_host_mesh(model=1)          # 8x1
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        sh8 = named_sharding(mesh8, ("batch", None), (8, 8))
        xs = jax.device_put(x, sh8)
        d = tempfile.mkdtemp()
        ckpt.save(d, 1, {"x": xs})

        mesh4 = jax.sharding.Mesh(
            np.asarray(jax.devices()[:4]).reshape(4, 1), ("data", "model"))
        sh4 = named_sharding(mesh4, ("batch", None), (8, 8))
        tree, step = ckpt.restore(d, {"x": x}, shardings={"x": sh4})
        assert (np.asarray(tree["x"]) == np.asarray(x)).all()
        print("RESHARD_OK", tree["x"].sharding.num_devices)
    """)
    assert "RESHARD_OK 4" in out


def test_dryrun_single_cell_small_mesh():
    """End-to-end dryrun machinery on an 8-device 4x2 mesh (fast proxy
    for the 256/512-chip meshes exercised by launch/dryrun.py)."""
    out = run_with_devices("""
        import numpy as np, jax
        jax.devices()   # pin the 8-device platform BEFORE importing
        # dryrun (which sets the 512-device XLA flag for its own use)
        from jax.sharding import Mesh
        from repro.core import runtime_flags
        runtime_flags.force_bf16_operands(True)
        from repro.launch.dryrun import build_cell, parse_collectives, SHAPES
        from repro.distributed.sharding import use_mesh

        mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
        fn, args, shardings, donate = build_cell("phi3-mini-3.8b", "train_4k", mesh)
        with use_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=shardings, donate_argnums=donate
                              ).lower(*args)
            compiled = lowered.compile()
            coll = parse_collectives(compiled.as_text())
        print("CELL_OK", compiled.cost_analysis().get("flops", 0) > 0,
              coll["total_bytes"] > 0)
    """)
    assert "CELL_OK True True" in out
