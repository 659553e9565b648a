"""The engine's step phases in the profiler trace, and the four readers
built on them: on a tiny traced run on the CPU, on hand-made spans with
known answers, and on the recorded chip trace, a program without them,
where the readers that were there before read what they read before."""

import os
import shutil

import numpy as np
import pytest

import engine_trace as et
import harness
import trace_reduce as tr
from conftest import TINY_CONF

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW = ("prefill_chunk_ms", "decode_step_ms", "chunk_row_use",
       "step_host_ms")
OLD = ("queue_wait_p90_ms", "slot_occupancy", "prefix_token_hit",
       "step_mfu", "rsa_gemm_roofline", "paged_attn_roofline", "device_idle")
# what the seven readers and the gap labels read on the recorded trace and
# the records of ``recorded_run`` before the engine wrote spans of its own
EXPECTED_OLD = {"queue_wait_p90_ms": 720.0000000000001, "slot_occupancy": 75.0,
                "prefix_token_hit": 28.571428571428573,
                "step_mfu": 8.225393016459006e-05,
                "rsa_gemm_roofline": 10.304391453277734,
                "paged_attn_roofline": 0.627211410699195,
                "device_idle": 99.60095848742806}
EXPECTED_LABELS = ["bench.step"] * 4
EXPECTED_GAPS = [0.0019313400000000001, 0.000705344, 0.0007048660000000001,
                 0.00064165]


def _read(name, run):
    return harness.reader(name)(run)


def test_tiny_traced_run(tiny_cell, tmp_path):
    """A tiny cell driven under the profiler: the readers find the run's
    own trace where the harness keeps it, and read its steps."""
    import jax
    tiny_cell.bench_dir = tmp_path
    cfg, engine, plan = harness.setup(tiny_cell, 3, 2.0)
    run = harness.RunData(tiny_cell, cfg, {})
    harness._spy(engine, run)
    with jax.profiler.trace(str(tmp_path / ".trace" / "tiny.chat-3")):
        harness.drive(engine, plan, 2.0, run)
    path, = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    window, _ = et.load(path)
    run.trace_window = window
    found = et.spans(run)
    steps = [s for s in found if s.name == et.STEP]
    assert len(steps) == len(run.step_walls) > 0

    chunks = [c for c in run.calls if c.kind == "prefill_chunk"]
    decodes = [c for c in run.calls if c.kind == "decode"]
    S, C = TINY_CONF["serving"]["slots"], TINY_CONF["serving"]["prefill_chunk"]
    live = sum(int(c.rows.sum()) for c in chunks)
    assert et.chunk_rows(found) == (live, S * C * len(chunks))
    assert _read("chunk_row_use", run) == pytest.approx(
        100.0 * live / (S * C * len(chunks)))
    assert len(et.program_seconds(found, et.CHUNK)) == len(chunks)
    assert len(et.program_seconds(found, et.DECODE)) == len(decodes)
    host = et.step_host_seconds(found)
    assert all(0 < h < (s.end - s.start) * 1e-9 for h, s in zip(host, steps))
    for name in NEW:
        assert _read(name, run) > 0, name

    run.trace_window = (window[0], window[1] + 1)   # another run's window
    assert et.spans(run) == []


def _span(name, start, end, **args):
    return et.Span(f"engine.{name}", start * 1e6, end * 1e6, args)


def test_readers_on_known_spans(monkeypatch):
    """Two steps (times in ms): a chunk step and a decode step in the
    first, a decode step alone in the second."""
    found = [
        _span("step", 0, 100, step_num=0),
        _span("schedule", 0, 2),
        _span("inputs", 2, 6),
        _span("prefill_chunk", 6, 10, lanes=2, live_rows=96, rows=512),
        _span("sync", 10, 50),
        _span("commit", 50, 52),
        _span("inputs", 52, 55),
        _span("paged_decode", 55, 57, lanes=3, width=8, kv_rows=640),
        _span("sync", 57, 80),
        _span("readback", 80, 90),
        _span("sample", 90, 96),
        _span("commit", 96, 99),
        _span("step", 110, 150, step_num=1),
        _span("schedule", 110, 111),
        _span("inputs", 111, 114),
        _span("paged_decode", 114, 115, lanes=3, width=8, kv_rows=688),
        _span("sync", 115, 135),
        _span("readback", 135, 140),
        _span("sample", 140, 146),
        _span("commit", 146, 150),
    ]
    monkeypatch.setattr(et, "spans", lambda run: found)
    # chunk 6 -> 50 ms; decodes 55 -> 80 and 114 -> 135 ms
    assert _read("prefill_chunk_ms", None) == pytest.approx(44.0)
    assert _read("decode_step_ms", None) == pytest.approx(23.0)
    assert _read("chunk_row_use", None) == pytest.approx(100.0 * 96 / 512)
    # step 0: 100 - 44 - 25 = 31 ms; step 1: 40 - 21 = 19 ms
    assert et.step_host_seconds(found) == pytest.approx([0.031, 0.019])
    assert _read("step_host_ms", None) == pytest.approx(25.0)

    monkeypatch.setattr(et, "spans", lambda run: [])
    assert all(_read(name, None) is None for name in NEW)


@pytest.fixture
def recorded_run(tiny_cell, tmp_path):
    """The recorded chip trace (three steps of one RSA GEMM and one paged
    decode, no engine spans) with hand-made host records around it, laid
    out where the harness keeps a run's trace."""
    from repro.serving import Request
    where = tmp_path / ".trace" / "tiny.chat-1" / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "small.xplane.pb"), where)
    tiny_cell.bench_dir = tmp_path
    run = harness.RunData(tiny_cell, tiny_cell.model.arch_config(TINY_CONF),
                          harness.peaks_for("TPU v5 lite"))
    run.trace = tr.load(DATA)
    run.trace_window = run.trace.window()
    run.device = 0
    run.window_s = 1.0
    run.step_walls = [0.010, 0.012, 0.011]
    run.occupancy = [0.5, 0.75, 1.0]
    run.calls = [harness.StepCall("prefill_chunk", np.array([0, 16, 0, 0]),
                                  np.array([16, 5, 0, 0]))] + [
        harness.StepCall("decode", np.array([30 + i, 12 + i, 0, 7 + i]),
                         np.array([1, 1, 0, 1])) for i in range(3)]
    run.sites = {"decode": [("layer.attn.q", 4, 64, 64),
                            ("unembed", 4, 64, 512)],
                 "prefill_chunk": [("layer.mlp.up", 64, 64, 128)]}
    for i, (admit, cached) in enumerate(((0.2, 16), (0.5, 0), (-1.0, 0))):
        req = Request(f"r{i}", np.zeros(24 + 8 * i, np.int32), 4,
                      arrival_time=0.1 * i)
        req.t_admit, req.cached_prefix_tokens = admit, cached
        run.recs.append(harness.Rec(harness.traffic.Planned(
            i, 0.1 * i, req.prompt, 4), req))
    return run


def test_recorded_trace_reads_as_before(recorded_run):
    got = {name: _read(name, recorded_run) for name in OLD}
    assert got == pytest.approx(EXPECTED_OLD, rel=1e-9)
    t = recorded_run.trace
    lo, hi = t.window()
    gaps = tr.gaps(tr.busy(t.ops[0], lo, hi), lo, hi)
    labels = tr.label_gaps(gaps, t.spans, n=4)
    assert [n for n, _ in labels] == EXPECTED_LABELS
    assert [s for _, s in labels] == pytest.approx(EXPECTED_GAPS, rel=1e-9)
    # a program that writes no engine spans: the new readers read nothing
    assert et.spans(recorded_run) == []
    assert all(_read(name, recorded_run) is None for name in NEW)
