"""Int8 error-feedback gradient compression (subprocess, 8 virtual devices)."""

import json
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, json, functools
    import numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from jax import shard_map
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.collectives import quantized_psum

    mesh = make_host_mesh(data=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 1024)) * 3.0

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=P("data", None), out_specs=P("data", None))
    def f(xs):
        out, err = quantized_psum(xs[0], "data", 8)
        return (out + 0 * err)[None]

    approx = f(x)[0]
    exact = jnp.sum(x, axis=0)
    rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
    print("RESULT " + json.dumps({"rel_err": rel}))
""")


def test_quantized_psum_accuracy():
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, cwd="/root/repo",
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][0]
    r = json.loads(line[len("RESULT "):])
    # int8 quantization: relative error well under 2%
    assert r["rel_err"] < 0.02
