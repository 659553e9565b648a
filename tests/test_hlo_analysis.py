"""Trip-count-aware HLO analysis: validated against hand-computed programs
(subprocess — the virtual-device flag must precede jax import)."""

import json
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, json
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=4, model=2)

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y.sum()

    x = jax.ShapeDtypeStruct((256, 512), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    with mesh:
        c = jax.jit(f, in_shardings=(
            NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P(None, "model")))).lower(x, w).compile()
    s = analyze_hlo(c.as_text())
    print("RESULT " + json.dumps({
        "flops": s.flops,
        "coll": s.collective_bytes_by_op,
        "hbm": s.hbm_bytes,
    }))
""")


def test_trip_weighted_flops_and_collectives():
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, cwd="/root/repo",
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][0]
    r = json.loads(line[len("RESULT "):])
    # 5 loop trips x (256x512x512 MACs x2) / 8 devices
    expected = 5 * 2 * 256 * 512 * 512 / 8
    assert abs(r["flops"] - expected) / expected < 0.02
    # the loop all-gather: f32[64,512] per trip x 5
    assert abs(r["coll"]["all-gather"] - 5 * 64 * 512 * 4) < 1e-6
    assert r["hbm"] > expected / 512 * 2      # traffic is nonzero & scaled


def test_parser_handles_empty_module():
    from repro.launch.hlo_analysis import analyze_hlo
    s = analyze_hlo("")
    assert s.flops == 0.0 and s.collective_bytes == 0.0


def test_shape_bytes():
    from repro.launch.hlo_analysis import _type_bytes
    assert _type_bytes("bf16[64,256]{1,0}") == 64 * 256 * 2
    assert _type_bytes("(s32[], f32[8,8])") == 4 + 8 * 8 * 4
    assert _type_bytes("pred[16]") == 16


def test_roofline_terms_math():
    from repro.launch.hlo_analysis import HLOStats, roofline_from_stats
    st = HLOStats(flops=197e12, hbm_bytes=819e9,
                  collective_bytes_by_op={"all-reduce": 50e9})
    t = roofline_from_stats(st, chips=256, model_flops=197e12 * 256 * 0.5)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 1.0) < 1e-9
    assert abs(t.collective_s - 1.0) < 1e-9
    assert t.roofline_fraction == 0.5
    assert t.dominant in ("compute", "memory", "collective")
