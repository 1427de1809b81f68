"""GPipe pipeline over a mesh axis: correctness vs sequential execution.

Runs in a subprocess with 4 host devices (the main test process must keep
the default single-device jax)."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.distributed.pipeline import pipeline, stack_stage_params
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("pp",))
    rng = np.random.default_rng(0)
    D = 16
    stages = [{"w": jnp.asarray(rng.normal(0, 0.5, (D, D)), jnp.float32)}
              for _ in range(4)]

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    stacked = stack_stage_params(stages)
    x = jnp.asarray(rng.normal(size=(8, D)), jnp.float32)

    run = pipeline(stage_fn, mesh, "pp", n_micro=4)
    got = run(stacked, x)

    ref = x
    for p in stages:
        ref = stage_fn(p, ref)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err < 1e-5, f"pipeline diverges: {err}"
    print("PIPELINE_OK", err)
""")


def test_pipeline_matches_sequential():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
