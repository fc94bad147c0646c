"""Pipeline-parallel skeleton test: shard_map+ppermute schedule == the
sequential oracle, run on 4 placeholder devices in a subprocess."""
import json
import subprocess
import sys
import textwrap

import pytest


@pytest.mark.slow
def test_pipeline_matches_reference_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, json
        import jax.numpy as jnp
        import numpy as np
        from repro.distributed.pipeline import pipeline_apply, \\
            pipeline_reference

        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((4,), ("stage",))
        n_stages, n_micro, mb, d = 4, 6, 2, 8
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (n_stages, d, d)) * 0.3
        b = jax.random.normal(jax.random.fold_in(key, 1),
                              (n_stages, d)) * 0.1
        params = {"w": w, "b": b}

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        x = jax.random.normal(jax.random.fold_in(key, 2), (n_micro, mb, d))
        got = pipeline_apply(stage_fn, params, x, mesh)
        want = pipeline_reference(stage_fn, params, x)
        err = float(jnp.max(jnp.abs(got - want)))
        print(json.dumps({"err": err}))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo", timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err"] < 1e-5, rec
