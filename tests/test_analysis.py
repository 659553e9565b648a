"""saralint acceptance tests: every check demonstrated by a known-bad
fixture firing at the right ``file:line``, known-good fixtures staying
silent, the inline-suppression round-trip (reasoned pragma suppresses;
reason-less pragma becomes a ``suppression-reason`` error), and the real
tree scanning clean through the CLI."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import run_paths
from repro.analysis.core import render_report

REPO = Path(__file__).resolve().parents[1]


def write(tmp_path, rel, text):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(text))
    return p


def lineno(path: Path, needle: str) -> int:
    """1-indexed line of the first line containing ``needle``."""
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        if needle in line:
            return i
    raise AssertionError(f"{needle!r} not in {path}")


def by_check(findings, check):
    return [f for f in findings if f.check == check and not f.suppressed]


# ---------------------------------------------------------------------------
# dispatch-escape
# ---------------------------------------------------------------------------

def test_dispatch_escape_fires_on_raw_gemms(tmp_path):
    p = write(tmp_path, "models/bad.py", """\
        import jax.numpy as jnp

        def layer(x, q, k, params):
            y = jnp.einsum("mk,kn->mn", x, params["w_proj"])
            s = jnp.einsum("bqd,bkd->bqk", q, k)
            z = x @ params["w1"]
            return y, s, z
        """)
    found = by_check(run_paths([str(tmp_path)]), "dispatch-escape")
    assert len(found) == 3
    sev = {f.line: f.severity for f in found}
    assert sev[lineno(p, "w_proj")] == "error"       # weight operand
    assert sev[lineno(p, "bqd,bkd")] == "warning"    # activation-activation
    assert sev[lineno(p, "@ params")] == "error"     # matmul vs weight
    assert all(f.path == "models/bad.py" for f in found)


def test_dispatch_escape_ignores_dispatch_and_out_of_scope(tmp_path):
    write(tmp_path, "models/good.py", """\
        from repro import dispatch

        def layer(x, w):
            return dispatch.gemm(x, w, site="layer.proj")
        """)
    write(tmp_path, "kernels/free.py", """\
        import jax.numpy as jnp

        def helper(a, w):
            return jnp.einsum("mk,kn->mn", a, w)   # kernels/ not in scope
        """)
    assert by_check(run_paths([str(tmp_path)]), "dispatch-escape") == []


# ---------------------------------------------------------------------------
# pallas-contract
# ---------------------------------------------------------------------------

def test_pallas_contract_blockspec_and_operand_arithmetic(tmp_path):
    p = write(tmp_path, "kernels/bad_kernel.py", """\
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def call(kernel, x, s, out_shape):
            grid = (4, 2)
            return pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=grid,
                    in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 8, 8), lambda i, j, s: (i, j)),
                ),
                out_shape=out_shape,
            )(s, x, x)
        """)
    found = by_check(run_paths([str(tmp_path)]), "pallas-contract")
    msgs = {f.line: f.message for f in found}
    # in_specs[0]: lambda takes 1 arg, grid rank 2 + 1 prefetch needs 3
    assert "requires 3" in msgs[lineno(p, "lambda i: (i, 0)")]
    # out_specs: 3-dim block shape, 2-coordinate index map
    assert "3 dim(s)" in msgs[lineno(p, "lambda i, j, s")]
    # invocation: 3 operands vs prefetch 1 + 1 in_spec = 2
    assert any("operand" in m for m in msgs.values())
    assert len(found) == 3


def test_pallas_contract_clean_call_site(tmp_path):
    write(tmp_path, "kernels/good_kernel.py", """\
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def call(kernel, x, s, out_shape):
            grid = (4, 2)
            return pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=grid,
                    in_specs=[pl.BlockSpec((8, 8), lambda i, j, sr: (i, 0))],
                    out_specs=pl.BlockSpec((8, 8), lambda i, j, sr: (i, j)),
                ),
                out_shape=out_shape,
            )(s, x)
        """)
    assert by_check(run_paths([str(tmp_path)]), "pallas-contract") == []


def test_pallas_contract_ref_twin_registry(tmp_path):
    p = write(tmp_path, "kernels/ops.py", """\
        from repro.kernels import ref

        def covered(x):
            if True:
                return ref.covered_ref(x)
            return covered_pallas(x)

        def named(x):
            return named_pallas(x)

        def orphan(x):
            return orphan_pallas(x)
        """)
    write(tmp_path, "kernels/ref.py", """\
        def named_ref(x):
            return x
        """)
    found = by_check(run_paths([str(tmp_path)]), "pallas-contract")
    assert len(found) == 1
    assert found[0].line == lineno(p, "def orphan")
    assert "orphan_ref" in found[0].message


# ---------------------------------------------------------------------------
# cow-gate
# ---------------------------------------------------------------------------

def test_cow_gate_flags_ungated_writer(tmp_path):
    p = write(tmp_path, "serving/writer.py", """\
        def ungated(arena, rows, k):
            return _arena_write_chunk(arena, rows, k)

        def gated(pool, arena, rows, k):
            pool.ensure_writable("r", 0)
            return _arena_write_chunk(arena, rows, k)
        """)
    found = by_check(run_paths([str(tmp_path)]), "cow-gate")
    assert [f.line for f in found] == [lineno(p, "def ungated") + 1]
    assert "ungated" in found[0].message


def test_cow_gate_gate_function_itself_exempt(tmp_path):
    write(tmp_path, "serving/pool.py", """\
        def ensure_writable(self, rid, i):
            return copy_page(self.arena, i)
        """)
    assert by_check(run_paths([str(tmp_path)]), "cow-gate") == []


# ---------------------------------------------------------------------------
# obs-taxonomy
# ---------------------------------------------------------------------------

_TRACE_FIXTURE = """\
    CATEGORIES = ("step", "request")
    STEP_PHASES = ("decode", "sample")
    COUNTERS = ("jit_compiles",)
    GAUGES = ("kv_pages_in_use",)
    """


def test_obs_taxonomy_checks_literals_against_declarations(tmp_path):
    write(tmp_path, "obs/trace.py", _TRACE_FIXTURE)
    p = write(tmp_path, "serving/emit.py", """\
        def record(obs, timeline, items):
            obs.count("jit_compiles", 1)
            obs.count("jit_compile", 1)
            obs.gauge("kv_pages_in_use", 3)
            timeline.phase("decodee")
            obs.instant("step", "x")
            obs.instant("stepp", "x")
            items.count("not_a_recorder")
        """)
    found = by_check(run_paths([str(tmp_path)]), "obs-taxonomy")
    assert sorted(f.line for f in found) == [
        lineno(p, '"jit_compile"'),
        lineno(p, '"decodee"'),
        lineno(p, '"stepp"'),
    ]
    assert all(f.severity == "error" for f in found)


def test_obs_taxonomy_reads_the_real_step_phases(tmp_path):
    """The program's own taxonomy: every engine step phase is declared,
    and a typo of one is refused."""
    from repro.obs import trace
    write(tmp_path, "obs/trace.py", Path(trace.__file__).read_text())
    p = write(tmp_path, "serving/emit.py", """\
        def step(timeline):
            timeline.phase("inputs")
            timeline.phase("prefill_chunk")
            timeline.phase("paged_decode")
            timeline.phase("readback")
            timeline.phase("commit")
            timeline.phase("readbak")
        """)
    found = by_check(run_paths([str(tmp_path)]), "obs-taxonomy")
    assert [f.line for f in found] == [lineno(p, '"readbak"')]
    assert set(trace.STEP_PHASES) >= {"schedule", "inputs", "prefill_chunk",
                                      "paged_decode", "sync", "readback",
                                      "sample", "commit"}


def test_obs_taxonomy_skips_without_trace_module(tmp_path):
    write(tmp_path, "serving/emit.py", """\
        def record(obs):
            obs.count("anything_goes", 1)
        """)
    assert by_check(run_paths([str(tmp_path)]), "obs-taxonomy") == []


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------

def test_retrace_hazard_patterns(tmp_path):
    p = write(tmp_path, "core/jits.py", """\
        import functools
        import jax

        def f(x, n=2):
            return x * n

        y = jax.jit(f)(3)

        def loopy(xs):
            outs = []
            for x in xs:
                g = jax.jit(f)
                outs.append(g(x))
            return outs

        h = jax.jit(f, static_argnames="m")
        k = jax.jit(f, static_argnums=5)

        @functools.partial(jax.jit, static_argnames="opts")
        def bad_default(x, opts=[]):
            return x

        good = jax.jit(f, static_argnames="n")
        """)
    found = by_check(run_paths([str(tmp_path)]), "retrace-hazard")
    at = {}
    for f in found:
        at.setdefault(f.line, []).append(f)
    inline = at[lineno(p, "jax.jit(f)(3)")]
    assert [x.severity for x in inline] == ["warning"]
    loop = at[lineno(p, "g = jax.jit(f)")]
    assert "loop" in loop[0].message and loop[0].severity == "warning"
    assert "no such parameter" in at[lineno(p, '"m"')][0].message
    assert "out of range" in at[lineno(p, "static_argnums=5")][0].message
    assert "unhashable" in at[lineno(p, "def bad_default")][0].message
    # the correctly-declared static name produced nothing
    assert lineno(p, '"n"') not in at
    assert len(found) == 5


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_suppression_round_trip(tmp_path):
    p = write(tmp_path, "models/supp.py", """\
        import jax.numpy as jnp

        def scores(q, k):
            # saralint: ok[dispatch-escape] activation-activation score
            s = jnp.einsum("bqd,bkd->bqk", q, k)
            t = jnp.einsum("bqd,bkd->bqk", q, k)  # saralint: ok[dispatch-escape]
            return s + t
        """)
    findings = run_paths([str(tmp_path)])
    supp = [f for f in findings if f.suppressed]
    assert {f.line for f in supp} == {lineno(p, "s = jnp"), lineno(p, "t = jnp")}
    assert supp[0].suppress_reason == "activation-activation score"
    # the reason-less pragma suppresses its finding but is itself an error
    active = [f for f in findings if not f.suppressed]
    assert [f.check for f in active] == ["suppression-reason"]
    assert active[0].line == lineno(p, "t = jnp")
    report = render_report(findings)
    assert "2 suppressed" in report and "1 error(s)" in report


def test_wrong_check_id_does_not_suppress(tmp_path):
    write(tmp_path, "models/supp2.py", """\
        import jax.numpy as jnp

        def scores(q, k):
            return jnp.einsum("bqd,bkd->bqk", q, k)  # saralint: ok[cow-gate] wrong id
        """)
    found = by_check(run_paths([str(tmp_path)]), "dispatch-escape")
    assert len(found) == 1 and not found[0].suppressed


# ---------------------------------------------------------------------------
# CLI + real tree
# ---------------------------------------------------------------------------

def _run_cli(*args, cwd=REPO):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        cwd=cwd, env=env, capture_output=True, text=True)


def test_cli_real_tree_is_clean():
    r = _run_cli("src/repro", "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["summary"]["errors"] == 0
    assert payload["summary"]["warnings"] == 0
    # every suppression in the tree documents its reason
    assert all(f["suppress_reason"] for f in payload["findings"]
               if f["suppressed"])


def test_cli_exit_code_on_findings(tmp_path):
    write(tmp_path, "models/bad.py", """\
        import jax.numpy as jnp

        def layer(x, w):
            return jnp.einsum("mk,kn->mn", x, w)
        """)
    r = _run_cli(str(tmp_path))
    assert r.returncode == 1
    assert "dispatch-escape" in r.stdout


def test_cli_list_checks():
    r = _run_cli("--list-checks")
    assert r.returncode == 0
    for cid in ("dispatch-escape", "pallas-contract", "cow-gate",
                "obs-taxonomy", "retrace-hazard"):
        assert cid in r.stdout
