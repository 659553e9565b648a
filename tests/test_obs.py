"""Observability subsystem acceptance tests.

- TraceRecorder tiers: counters/gauges always on, events only with spans
  enabled, ring capacity drops oldest and counts them
- RequestTracker lifecycle invariants: the root span closes exactly once
  under plain retire, mid-prefill preemption, and preempt -> readmit;
  illegal transitions raise TraceError; empty-trace runs export cleanly
- StepTimeline phases are monotonic, non-overlapping, and nest inside the
  engine_step root; the Chrome export round-trips json.loads and passes
  validate_trace
- serving metrics: percentile(None-on-empty) and rolling-window keys
- standardized benchmark result schema (write_result / validate_result)
- engine integration: a traced dense run exports all five categories and
  surfaces jit_compiles in dispatch_stats
"""

import json
import re

import numpy as np
import pytest

from repro.obs import (REQUIRED_CATEGORIES, RequestTracker, StepTimeline, TraceError,
                       TraceRecorder, to_chrome_trace, validate_trace,
                       write_chrome_trace, write_jsonl)

ARCH = "llama3.2-1b"


# ---------------------------------------------------------------------------
# recorder tiers + ring buffer
# ---------------------------------------------------------------------------

def test_counters_and_gauges_always_on_events_gated():
    rec = TraceRecorder(spans=False)
    rec.count("c")
    rec.count("c", 2)
    rec.gauge("g", 0.5)
    rec.instant("arena", "reserve")
    with rec.span("step", "decode"):
        pass
    assert rec.counters["c"] == 3
    assert rec.gauges["g"] == 0.5
    assert len(rec) == 0                       # spans off: no events buffered

    rec = TraceRecorder(spans=True)
    rec.instant("arena", "reserve", rid="r0")
    with rec.span("step", "decode"):
        pass
    assert len(rec) == 2
    assert {e.cat for e in rec.events()} == {"arena", "step"}


def test_ring_capacity_drops_oldest_and_counts():
    rec = TraceRecorder(capacity=4, spans=True)
    for i in range(10):
        rec.instant("arena", f"e{i}")
    assert len(rec) == 4
    assert rec.dropped == 6
    assert [e.name for e in rec.events()] == ["e6", "e7", "e8", "e9"]


# ---------------------------------------------------------------------------
# request lifecycle invariants
# ---------------------------------------------------------------------------

def _root_slices(rec):
    return [e for e in rec.events("request") if e.name == "request"]


def test_retire_closes_root_exactly_once():
    rec = TraceRecorder(spans=True)
    tr = RequestTracker(rec)
    tr.on_submit("r0")
    tr.on_admit("r0", slot=0)
    tr.on_first_token("r0")
    tr.on_retire("r0", tokens=5)
    assert tr.closed == 1
    assert tr.open_requests() == {}
    roots = _root_slices(rec)
    assert len(roots) == 1 and roots[0].args["preempts"] == 0
    with pytest.raises(TraceError):
        tr.on_retire("r0")                     # double close


def test_mid_prefill_preemption_keeps_root_open():
    rec = TraceRecorder(spans=True)
    tr = RequestTracker(rec)
    tr.on_submit("r0")
    tr.on_admit("r0", slot=0)
    tr.on_prefill_chunk("r0", tokens=8, dur=0.01)
    tr.on_preempt("r0")
    assert tr.open_requests() == {"r0": "queued"}
    assert _root_slices(rec) == []             # root still open
    active = [e for e in rec.events("request") if e.name == "active"]
    assert len(active) == 1 and active[0].args["outcome"] == "preempt"


def test_preempt_readmit_cycle_closes_once():
    rec = TraceRecorder(spans=True)
    tr = RequestTracker(rec)
    tr.on_submit("r0")
    for cycle in range(3):
        tr.on_admit("r0", slot=0)
        if cycle < 2:
            tr.on_preempt("r0")
    tr.on_retire("r0")
    assert tr.closed == 1
    roots = _root_slices(rec)
    assert len(roots) == 1 and roots[0].args["preempts"] == 2
    queues = [e for e in rec.events("request") if e.name == "queue"]
    assert [q.args["readmit"] for q in queues] == [False, True, True]


def test_illegal_transitions_raise():
    rec = TraceRecorder(spans=True)
    tr = RequestTracker(rec)
    with pytest.raises(TraceError):
        tr.on_retire("ghost")                  # never submitted
    tr.on_submit("r0")
    with pytest.raises(TraceError):
        tr.on_submit("r0")                     # double submit
    with pytest.raises(TraceError):
        tr.on_retire("r0")                     # retire while queued
    with pytest.raises(TraceError):
        tr.on_preempt("r0")                    # preempt while queued


def test_empty_trace_exports_cleanly(tmp_path):
    rec = TraceRecorder(spans=True)
    doc = json.loads(json.dumps(to_chrome_trace(rec)))
    assert validate_trace(doc) == []
    p = tmp_path / "empty.json"
    write_chrome_trace(str(p), rec)
    assert validate_trace(json.loads(p.read_text())) == []


# ---------------------------------------------------------------------------
# step timeline + export round-trip
# ---------------------------------------------------------------------------

def test_step_phases_monotonic_and_nested():
    rec = TraceRecorder(spans=True)
    tl = StepTimeline(rec)
    for _ in range(3):
        tl.begin()
        with tl.phase("schedule"):
            pass
        with tl.phase("decode", lanes=2):
            pass
        with tl.phase("sample"):
            pass
        tl.end(active=2)
    assert tl.steps == 3
    doc = json.loads(json.dumps(to_chrome_trace(rec)))
    assert validate_trace(doc, require_categories=("step",)) == []
    # per-step: children sorted by ts never overlap and sit in the root
    for step in range(3):
        evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"
               and e["args"].get("step") == step]
        root = next(e for e in evs if e["name"] == "engine_step")
        kids = sorted((e for e in evs if e is not root),
                      key=lambda e: e["ts"])
        assert [k["name"] for k in kids] == ["schedule", "decode", "sample"]
        end = root["ts"]
        for k in kids:
            assert k["ts"] >= end - 1e-3       # us-rounded, monotonic
            end = k["ts"] + k["dur"]
        assert end <= root["ts"] + root["dur"] + 1e-3


def test_timeline_misuse_raises():
    tl = StepTimeline(TraceRecorder(spans=True))
    with pytest.raises(TraceError):
        tl.phase("decode")                     # outside begin()
    with pytest.raises(TraceError):
        tl.end()
    tl.begin()
    with pytest.raises(TraceError):
        tl.begin()                             # already open


def test_validate_trace_catches_corruption():
    assert validate_trace([]) != []
    assert validate_trace({"traceEvents": [{"ph": "Z"}]}) != []
    bad = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "cat": "step", "name": "engine_step",
         "ts": 0.0, "dur": 100.0, "args": {"step": 0}},
        {"ph": "X", "pid": 1, "tid": 0, "cat": "step", "name": "schedule",
         "ts": 0.0, "dur": 60.0, "args": {"step": 0}},
        {"ph": "X", "pid": 1, "tid": 0, "cat": "step", "name": "decode",
         "ts": 50.0, "dur": 20.0, "args": {"step": 0}},   # overlaps schedule
    ]}
    assert any("overlaps" in e for e in validate_trace(bad))
    assert any("no 'compile'" in e for e in
               validate_trace({"traceEvents": []},
                              require_categories=("compile",)))


def test_jsonl_export(tmp_path):
    rec = TraceRecorder(spans=True)
    rec.count("jit_compiles")
    rec.instant("dispatch", "site", track="dispatch", m=64, k=32, n=128)
    p = tmp_path / "t.jsonl"
    write_jsonl(str(p), rec, meta={"arch": ARCH})
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert lines[0]["record"] == "meta"
    assert lines[0]["arch"] == ARCH
    assert lines[0]["counters"]["jit_compiles"] == 1
    assert lines[1] == {"record": "event", "cat": "dispatch", "name": "site",
                        "ph": "i", "ts": lines[1]["ts"], "dur": 0.0,
                        "track": "dispatch",
                        "args": {"m": 64, "k": 32, "n": 128}}


# ---------------------------------------------------------------------------
# serving metrics: percentile None + rolling windows
# ---------------------------------------------------------------------------

def test_percentile_empty_returns_none():
    from repro.serving.metrics import percentile
    assert percentile([], 99) is None
    assert percentile([0.0], 50) == 0.0        # measured zero is not None


def test_rolling_window_metrics():
    from repro.serving.metrics import ServingMetrics
    m = ServingMetrics(rolling_window=4)
    s = m.summary()
    assert s["ttft_p50_s_roll"] is None
    assert s["decode_tok_s_roll"] is None
    for i in range(8):                         # window keeps the last 4
        m.on_first_token(arrival=0.0, t=float(i))
    assert m.summary()["ttft_p50_s_roll"] == pytest.approx(5.5)
    assert m.summary()["ttft_p50_s"] == pytest.approx(3.5)  # lifetime
    m.on_decode_step(active=2, slots=4, tokens=10, seconds=2.0)
    assert m.summary()["decode_tok_s_roll"] == pytest.approx(5.0)
    assert "n/a" in m.report()                 # latency percentiles empty


# ---------------------------------------------------------------------------
# standardized benchmark result schema
# ---------------------------------------------------------------------------

def test_result_schema_roundtrip(tmp_path, monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "RESULTS_DIR", tmp_path)
    doc = common.write_result("bench_x", {"tok_s": 1.5}, {"slots": 4})
    assert common.validate_result(doc) == []
    loaded = json.loads((tmp_path / "bench_x.result.json").read_text())
    assert loaded == doc
    assert loaded["schema"] == common.SCHEMA_VERSION
    assert isinstance(loaded["suite_rev"], str)


def test_result_schema_rejects_malformed():
    from benchmarks.common import validate_result
    assert validate_result([]) == ["result must be an object"]
    assert validate_result({"name": "x"})      # missing fields
    bad = {"name": "x", "config": {}, "suite_rev": "abc",
           "metrics": {"rows": [1, 2]}}        # non-scalar metric
    assert any("scalar" in e for e in validate_result(bad))


# ---------------------------------------------------------------------------
# engine integration: traced run covers every category
# ---------------------------------------------------------------------------

def test_traced_engine_run_exports_all_categories(tmp_path):
    from repro.configs.registry import get_arch
    from repro.serving import EngineConfig, Request, ServingEngine

    cfg = get_arch(ARCH).reduced()
    rng = np.random.default_rng(0)
    eng = ServingEngine(cfg, EngineConfig(
        num_slots=2, max_len=24, temperature=0.0, trace=True))
    reqs = [Request(f"r{i}", rng.integers(0, cfg.vocab_size, 12).astype(
        np.int32), 4) for i in range(3)]
    res = eng.run(reqs)
    assert all(len(v) == 4 for v in res.values())

    # every request span closed exactly once; no step left open
    assert eng.req_spans.closed == 3
    assert eng.req_spans.open_requests() == {}

    p = tmp_path / "trace.json"
    jsonl = eng.export_trace(str(p))
    doc = json.loads(p.read_text())
    assert validate_trace(doc, require_categories=REQUIRED_CATEGORIES) == []
    assert doc["otherData"]["counters"]["jit_compiles"] >= 2
    assert (tmp_path / "trace.jsonl").exists() and jsonl.endswith(".jsonl")

    # satellite: retrace counter surfaced for benchmark assertions
    assert eng.dispatch_stats()["jit_compiles"] == \
        doc["otherData"]["counters"]["jit_compiles"]
    # the registry still names every executed site of every traced scope
    assert eng.registry.scopes()
    assert all(eng.registry.sites(scope) for scope in eng.registry.scopes())


# ---------------------------------------------------------------------------
# step phases on the profiler's clock
# ---------------------------------------------------------------------------

# one step: schedule, then per step program its host inputs, the jitted
# call, the sync, the logits readback (paged decode), sampling (when a
# lane takes a token) and the commit
_STEP_GRAMMAR = re.compile(
    r"schedule(,inputs(,(prefill|prefill_chunk|decode|paged_decode),sync"
    r"(,readback)?(,sample)?,commit)?)*")


def _host_spans(trace_dir, prefix):
    import glob

    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                        for ev in line.events if ev.name.startswith(prefix)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.mark.parametrize("layout,chunk", [("paged", 8), ("dense", None)])
def test_step_phases_on_profiler_clock(tmp_path, layout, chunk):
    """Every step is an ``engine.step`` on the profiler's clock, with no
    EngineConfig.trace: its phases nest inside it, never overlap, come in
    the step's order, and a chunk step's row args are the engine's."""
    import jax

    from repro.configs.registry import get_arch
    from repro.serving import EngineConfig, Request, ServingEngine

    cfg = get_arch(ARCH).reduced()
    eng = ServingEngine(cfg, EngineConfig(
        num_slots=2, max_len=48, block_size=8, temperature=0.0,
        max_prefills_per_step=2, kv_layout=layout, prefill_chunk=chunk))
    chunks = []
    if chunk:
        real = eng._chunk_prefill

        def spy(params, toks, leaves, tables, kv, n):
            chunks.append(np.asarray(n))
            return real(params, toks, leaves, tables, kv, n)

        eng._chunk_prefill = spy
    rng = np.random.default_rng(1)
    reqs = [Request(f"r{i}", rng.integers(0, cfg.vocab_size, n).astype(
        np.int32), 3) for i, n in enumerate((21, 5, 13))]
    with jax.profiler.trace(str(tmp_path)):
        eng.run(reqs)
    assert not eng.obs.spans and len(eng.obs) == 0   # recorder stayed off

    spans = _host_spans(tmp_path, "engine.")
    steps = [s for s in spans if s[0] == "engine.step"]
    assert [s[3]["step_num"] for s in steps] == list(range(eng._step_idx))
    assert all({"stalls", "preempts", "active", "waiting"} <= set(s[3])
               for s in steps)
    phases = [s for s in spans if s[0] != "engine.step"]
    owned = 0
    for _, t0, t1, _ in steps:
        kids = [p for p in phases if t0 <= p[1] and p[2] <= t1]
        owned += len(kids)
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1], (a[0], b[0])       # siblings never overlap
        names = ",".join(p[0][len("engine."):] for p in kids)
        assert _STEP_GRAMMAR.fullmatch(names), names
    assert owned == len(phases)                     # no phase outside a step

    if chunk:
        got = [p[3] for p in phases if p[0] == "engine.prefill_chunk"]
        assert [a["live_rows"] for a in got] == [int(n.sum()) for n in chunks]
        assert [a["lanes"] for a in got] == [int((n > 0).sum())
                                             for n in chunks]
        assert {a["rows"] for a in got} == {2 * chunk}
        decodes = [p[3] for p in phases if p[0] == "engine.paged_decode"]
        assert decodes and all(a["kv_rows"] > 0 and a["width"] >= 1
                               for a in decodes)
