"""Pipeline-parallelism correctness: GPipe schedule over the pod axis
must reproduce the sequential layer stack exactly."""

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_pipeline_matches_sequential():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    code = textwrap.dedent("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.launch.mesh import mesh_from_devices
        from repro.distributed.pipeline import pipeline_forward

        n_stages, n_micro, mb, d = 4, 8, 2, 16
        mesh = mesh_from_devices(
            np.asarray(jax.devices()).reshape(4,), ("pod",))
        key = jax.random.PRNGKey(0)
        W = jax.random.normal(key, (n_stages, d, d)) * 0.3
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (n_micro, mb, d))

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        y_pipe = pipeline_forward(mesh, stage_fn, W, x,
                                  n_stages=n_stages)
        # sequential reference
        y_ref = x
        for s in range(n_stages):
            y_ref = jnp.tanh(y_ref @ W[s])
        err = float(jnp.abs(y_pipe - y_ref).max())
        print("PIPE_ERR", err)
        assert err < 1e-5, err
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2500:]
    assert "PIPE_ERR" in out.stdout
