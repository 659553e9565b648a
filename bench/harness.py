"""One run of one cell: load what ``BENCHMARK.json`` names, make the weights
and the engine from the seed, warm every shape, drive the traffic open-loop
for the window, then reduce, check against the reference and report.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric is a file of its own, found by name:

- ``BENCHMARK.json``: the cells, their metrics and the configuration files;
- ``bench/models/<model_type>.py``: a model family's weights, plain
  reference and counts, by the ``model_type`` its configuration file states
  (the interface is in ``bench/models/llama.py``'s docstring);
- ``bench/traffic/<mix>.json``: the mix's parameters (``traffic.py``);
- ``bench/limits/<cell>.json``: the limit of each number compared;
- ``bench/metrics/<metric>.py``: a reader ``read(run) -> float | None``.
"""

from __future__ import annotations

import functools
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))

import traffic  # noqa: E402
from stats import percentile  # noqa: E402

# the correctness sample: finished requests drawn from the seed, the one
# with the most served tokens always among them, until this many served
# tokens or requests are in it
SAMPLE_TOKENS = 1536
SAMPLE_REQUESTS = 16
TRACE_DIR = BENCH / ".trace"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, bad cell files)."""


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    traffic_name: str
    conf: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    model: ModuleType
    bench_dir: Path = BENCH


def _applies(metric: Dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(root: Path, name: str) -> Cell:
    bench_json = root / "BENCHMARK.json"
    if not bench_json.exists():
        raise BenchError(f"{bench_json} not found")
    spec = json.loads(bench_json.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf = json.loads((root / configs[w["config"]]["file"]).read_text())
    conf.setdefault("name", w["config"])
    bench = root / "bench"
    model = model_module(conf.get("model_type"), bench)
    mix = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    limits_path = bench / "limits" / f"{name}.json"
    limits = (json.loads(limits_path.read_text())
              if limits_path.exists() else {})
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, names)]
    return Cell(name, int(w["chips"]), w["traffic"], conf, mix, limits, e2e,
                per_layer, model, bench)


def _load(path: Path, name: str, what: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise BenchError(f"no {what} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench: Path = BENCH) -> Callable:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _load(bench / "metrics" / f"{metric}.py", f"bench_metric_{metric}",
                 f"reader for metric {metric!r}").read


# one module object per file, so that its jitted reference is traced once
# in a process however many cells name it
@functools.lru_cache(maxsize=None)
def model_module(model_type, bench: Path = BENCH) -> ModuleType:
    """``bench/models/<model_type>.py``: the weights, reference and counts
    of the model family a configuration file names by ``model_type``."""
    if not isinstance(model_type, str) or not re.fullmatch(
            r"[A-Za-z0-9_][A-Za-z0-9_-]*", model_type):
        raise BenchError(f"configuration states no usable model_type: "
                         f"{model_type!r}")
    return _load(bench / "models" / f"{model_type}.py",
                 f"bench_model_{model_type}",
                 f"model module for model_type {model_type!r}")


def peaks_for(kind: str) -> Dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json; "
                         "add its published peaks, there is no default")
    return table[kind]


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------

def require_chip(chips: int) -> Dict:
    """The device the run measures; refuses anything but enough TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devs[0].platform!r}; "
                         "refusing to fall back")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} TPU chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or at the
    fixed ``<checkout>/.jax_cache``; every program is cached, however
    quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT /
                                                             ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class GcClock:
    """Python's garbage-collector pauses while it is open: (generation,
    seconds) of each collection."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)


class CompileCounter:
    """Backend compiles (persistent-cache misses) and their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def engine_config(cell: Cell, seed: int):
    from repro.serving import EngineConfig
    s = cell.conf["serving"]
    return EngineConfig(
        num_slots=s["slots"], max_len=s["max_len"],
        block_size=s["block_size"], num_blocks=s["kv_pages"],
        max_prefills_per_step=s["max_prefills_per_step"],
        temperature=0.0, seed=seed, clock="wall", kv_layout="paged",
        prefill_chunk=s["prefill_chunk"], prefix_cache=True)


def decode_widths(cell: Cell) -> List[int]:
    """Every decode table width the mix can reach: powers of two from the
    shortest prompt's pages to the longest context's, capped."""
    from repro.serving.kv_pool import KVBlockPool
    s = cell.conf["serving"]
    bs, cap = s["block_size"], -(-s["max_len"] // s["block_size"])
    lo = KVBlockPool.table_width(-(-(traffic.min_prompt(cell.mix) + 1) // bs),
                                 cap)
    hi = KVBlockPool.table_width(-(-(traffic.max_context(cell.mix) + 1)
                                   // bs), cap)
    out, w = [], lo
    while w <= hi:
        out.append(w)
        w *= 2
    return out


def warm(engine, cell: Cell, seed: int) -> None:
    """Run every program the window will run once: the chunked-prefill step,
    the decode step at each reachable table width (all lanes masked, so
    only the arena's discard page is written), then one real request,
    which warms sampling and the host path."""
    import jax
    import jax.numpy as jnp
    from repro.serving import Request
    s = cell.conf["serving"]
    S, C = s["slots"], engine.prefill_chunk
    z = jnp.zeros((S,), jnp.int32)
    with engine._dispatch_scope("prefill_chunk"):
        _, leaves = engine._chunk_prefill(
            engine.params, jnp.zeros((S, C), jnp.int32), engine.arena.leaves,
            jnp.zeros((S, engine._max_blocks_per_slot), jnp.int32), z, z)
    engine.arena.leaves = jax.block_until_ready(leaves)
    for w in decode_widths(cell):
        with engine._dispatch_scope("decode"):
            _, leaves = engine._paged_decode(
                engine.params, jnp.zeros((S, 1), jnp.int32), engine._state,
                engine.arena.leaves, jnp.zeros((S, w), jnp.int32), z, z)
        engine.arena.leaves = jax.block_until_ready(leaves)
    rng = np.random.default_rng(seed ^ 0x5EED)
    n = traffic.min_prompt(cell.mix)
    engine.submit(Request(rid="warm", max_new_tokens=2,
                          prompt=rng.integers(0, engine.cfg.vocab_size, n,
                                              dtype=np.int32)))
    while engine.step():
        pass


def warm_prefixes(engine, plan: List[traffic.Planned]) -> None:
    """Prefill each shared system prompt of the schedule once, so the
    window opens on a prefix cache that holds them, as a deployment's does
    once each prompt has been seen."""
    from repro.serving import Request
    heads = {}
    for p in plan:
        if p.prefix_id >= 0:
            heads.setdefault(p.prefix_id, p.prompt[:p.prefix_len])
    for i, toks in sorted(heads.items()):
        engine.submit(Request(rid=f"prefix{i}", prompt=toks,
                              max_new_tokens=1))
    while engine.step():
        pass


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclass
class Rec:
    """What the client saw of one request."""

    planned: traffic.Planned
    req: object = None
    tokens: List[float] = field(default_factory=list)   # arrival times


@dataclass
class StepCall:
    """One dispatch of a step program, as the traced run records it."""

    kind: str                      # "decode" | "prefill_chunk"
    starts: np.ndarray             # (S,) rows already in each lane
    rows: np.ndarray               # (S,) new rows per lane (0 = idle)


@dataclass
class RunData:
    """Everything a per-layer reader may read."""

    cell: Cell
    cfg: object
    peaks: Dict
    window_s: float = 0.0
    recs: List[Rec] = field(default_factory=list)
    step_walls: List[float] = field(default_factory=list)
    step_cpus: List[float] = field(default_factory=list)   # host thread
    calls: List[StepCall] = field(default_factory=list)
    sites: Dict[str, list] = field(default_factory=dict)
    occupancy: List[float] = field(default_factory=list)
    trace: object = None
    trace_window: tuple = (0.0, 0.0)
    device: int = 0

    @property
    def model(self) -> ModuleType:
        return self.cell.model


def _spy(engine, run: RunData) -> None:
    """Record the lanes of every step dispatch (traced runs only: reading
    the arguments back costs a small transfer per call)."""
    dec, chunk = engine._paged_decode, engine._chunk_prefill

    def on_decode(params, toks, state, leaves, tables, kv, wm):
        run.calls.append(StepCall("decode", np.asarray(kv), np.asarray(wm)))
        return dec(params, toks, state, leaves, tables, kv, wm)

    def on_chunk(params, toks, leaves, tables, kv, n):
        run.calls.append(StepCall("prefill_chunk", np.asarray(kv),
                                  np.asarray(n)))
        return chunk(params, toks, leaves, tables, kv, n)

    engine._paged_decode, engine._chunk_prefill = on_decode, on_chunk


def drive(engine, plan: List[traffic.Planned], seconds: float,
          run: RunData, rid_prefix: str = "r") -> Dict:
    """Open loop: submit each request when it is due, step the engine while
    it has work, sleep when it has none.  Returns the window's counters."""
    from repro.serving import Request
    recs = [Rec(p) for p in plan]
    live: List[Rec] = []
    t_open = time.perf_counter()
    e_open = engine.now()
    occ0 = len(engine.metrics.slot_occupancy)
    nxt = 0
    late, submit_walls = [], []
    with annotate("bench.window"):
        while True:
            now = time.perf_counter() - t_open
            if now >= seconds:
                break
            if nxt < len(recs) and recs[nxt].planned.due_s <= now:
                t_sub = time.perf_counter()
                with annotate("bench.submit"):
                    while nxt < len(recs) and recs[nxt].planned.due_s <= now:
                        r = recs[nxt]
                        p = r.planned
                        r.req = Request(rid=f"{rid_prefix}{p.index}",
                                        prompt=p.prompt,
                                        max_new_tokens=p.max_new_tokens,
                                        arrival_time=e_open + p.due_s)
                        engine.submit(r.req)
                        late.append(now - p.due_s)
                        live.append(r)
                        nxt += 1
                submit_walls.append(time.perf_counter() - t_sub)
            if engine.sched.idle():
                wake = recs[nxt].planned.due_s if nxt < len(recs) else seconds
                with annotate("bench.wait"):
                    time.sleep(max(0.0, min(wake, seconds) - now))
                continue
            t0, c0 = time.perf_counter(), time.thread_time()
            with annotate("bench.step"):
                engine.step()
            t1 = time.perf_counter()
            run.step_walls.append(t1 - t0)
            run.step_cpus.append(time.thread_time() - c0)
            t = t1 - t_open
            still = []
            for r in live:
                new = len(r.req.generated) - len(r.tokens)
                r.tokens.extend([t] * new)
                if not r.req.outcome:
                    still.append(r)
            live = still
    run.window_s = time.perf_counter() - t_open
    run.recs = [r for r in recs if r.req is not None]
    run.occupancy = list(engine.metrics.slot_occupancy[occ0:])
    return {"submitted": nxt, "late_max_s": max(late, default=0.0),
            "late_p50_s": percentile(late, 50) or 0.0,
            "submit_max_s": max(submit_walls, default=0.0)}


def host_line(run: RunData, ctx: Dict, pauses: List[tuple]) -> str:
    """Where the host's longest waits in the window went: the longest step
    on the wall clock beside the host thread's CPU seconds in it (near
    the wall: Python work; far below: waiting), the longest submit, and
    the garbage collector's pauses."""
    i = int(np.argmax(run.step_walls)) if run.step_walls else -1
    wall = run.step_walls[i] if i >= 0 else 0.0
    cpu = run.step_cpus[i] if i >= 0 else 0.0
    oldest = [s for g, s in pauses if g == 2]
    return (f"host: {len(run.step_walls)} steps, longest {wall:.3f} s "
            f"({cpu:.3f} s of host CPU), step p50 "
            f"{(percentile(run.step_walls, 50) or 0.0):.3f} s; longest "
            f"submit {ctx['submit_max_s']:.3f} s; gc {len(pauses)} "
            f"collections, {sum(s for _, s in pauses):.3f} s, longest "
            f"{max((s for _, s in pauses), default=0.0):.3f} s, "
            f"{len(oldest)} of the oldest generation")


def latencies(run: RunData) -> Dict[str, List[float]]:
    """Seconds from due time to first token of every request due in the
    window (one with no token yet counts at its elapsed time at the
    close), and every gap between consecutive output tokens."""
    close = run.window_s
    return {"ttft": [((r.tokens[0] if r.tokens else close) - r.planned.due_s)
                     for r in run.recs],
            "itl": [b - a for r in run.recs
                    for a, b in zip(r.tokens, r.tokens[1:])]}


def end_to_end(run: RunData, setup_s: float) -> Dict[str, float]:
    """The cell's end-to-end metrics from what the client saw:
    ``<ttft|itl>_p<q>_ms`` is the q-th percentile of those latencies."""
    lat = latencies(run)
    out = {}
    for m in run.cell.end_to_end:
        name = m["name"]
        tail = re.fullmatch(r"(ttft|itl)_p(\d+)_ms", name)
        if tail:
            v = (percentile(lat[tail[1]], int(tail[2])) or 0.0) * 1e3
        elif name == "out_tok_per_s":
            v = sum(len(r.tokens) for r in run.recs) / run.window_s
        elif name == "setup_s":
            v = setup_s
        else:
            raise BenchError(f"no way to measure end-to-end metric {name!r}")
        out[name] = {"value": v, "unit": m["unit"]}
    return out


def latency_line(run: RunData) -> str:
    lat = latencies(run)
    parts = [f"{k} p{q} {(percentile(lat[k], q) or 0.0) * 1e3:.1f}"
             for k in ("ttft", "itl") for q in (50, 75, 90, 95)]
    return f"latency ms over {len(lat['ttft'])} requests: " + ", ".join(parts)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample(run: RunData, seed: int) -> List[Rec]:
    """Finished requests drawn from the seed, the longest always in."""
    done = [r for r in run.recs if r.req.outcome == "done"]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.req.generated))
    rng = np.random.default_rng(seed ^ 0xC0FFEE)
    picked, served = [longest], len(longest.req.generated)
    for i in rng.permutation(len(done)):
        r = done[int(i)]
        if served >= SAMPLE_TOKENS or len(picked) >= SAMPLE_REQUESTS:
            break
        if r is not longest:
            picked.append(r)
            served += len(r.req.generated)
    return picked


def served(picked: List[Rec]):
    """(sequences, positions, tokens) of the sample: each prompt with its
    served tokens, the positions whose logits chose them, and the tokens."""
    seqs, rows, toks = [], [], []
    for r in picked:
        p = r.planned.prompt
        g = np.asarray(r.req.generated, np.int32)
        seqs.append(np.concatenate([p, g[:-1]]))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(g)))
        toks.append(g)
    return seqs, rows, toks


def gap_numbers(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers compared: the widest and the mean gap by which a served
    token's logit lies below the reference's best."""
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean())}


def compare(model: ModuleType, cfg, seed: int,
            picked: List[Rec]) -> Dict[str, float]:
    """The sample's numbers against the model's float32 reference."""
    from reference import logit_gaps
    seqs, rows, toks = served(picked)
    ref = model.served_logits(seed, cfg, seqs, rows)
    gaps = np.concatenate([logit_gaps(lg, t) for lg, t in zip(ref, toks)])
    return dict(gap_numbers(gaps), served_tokens=int(len(gaps)),
                requests=len(picked))


# ---------------------------------------------------------------------------
# a whole run
# ---------------------------------------------------------------------------

def setup(cell: Cell, seed: int, seconds: float):
    """Weights, engine and warm-up; returns (cfg, engine, plan)."""
    import jax
    from repro.serving import ServingEngine
    cfg = cell.model.arch_config(cell.conf)
    s = cell.conf["serving"]
    if traffic.max_context(cell.mix) + 1 > s["max_len"]:
        raise BenchError(f"{cell.traffic_name} reaches "
                         f"{traffic.max_context(cell.mix)} tokens, over "
                         f"max_len {s['max_len']}")
    params = jax.block_until_ready(cell.model.make_params(seed, cfg))
    engine = ServingEngine(cfg, engine_config(cell, seed), params=params)
    if engine.kv_layout != "paged" or engine.prefill_chunk is None:
        raise BenchError("the engine did not take the paged chunked path")
    plan = traffic.generate(cell.mix, seed, seconds, cfg.vocab_size)
    warm(engine, cell, seed)
    warm_prefixes(engine, plan)
    return cfg, engine, plan


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device: Dict, log=print) -> Dict:
    """Set up, measure, check.  Returns the result line's object."""
    import jax
    compiles = CompileCounter()
    cfg, engine, plan = setup(cell, seed, seconds)
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" else {}
    run = RunData(cell, cfg, peaks)
    setup_s = time.perf_counter() - t_start
    mem = jax.devices()[0].memory_stats() or {}
    log(f"setup: {setup_s:.3f} s, {compiles.count} programs compiled or "
        f"loaded ({compiles.seconds:.1f} s), {mem.get('bytes_in_use')} "
        f"bytes in use; {len(plan)} requests planned")

    jit0, comp0 = engine.dispatch_stats()["jit_compiles"], compiles.count
    if trace:
        _spy(engine, run)
        TRACE_DIR.mkdir(exist_ok=True)
        tdir = TRACE_DIR / f"{cell.name}-{seed}"
        jax.profiler.start_trace(str(tdir))
    gcs = GcClock()
    try:
        ctx = drive(engine, plan, seconds, run)
    finally:
        gcs.close()
    if trace:
        jax.block_until_ready(engine.arena.leaves)
        jax.profiler.stop_trace()
    jit_in = engine.dispatch_stats()["jit_compiles"] - jit0
    comp_in = compiles.count - comp0
    log(f"window: {run.window_s:.3f} s, {ctx['submitted']} submitted, "
        f"generator late p50 {ctx['late_p50_s'] * 1e3:.3f} ms max "
        f"{ctx['late_max_s'] * 1e3:.3f} ms; compiles in window: "
        f"{jit_in} traces, {comp_in} backend")
    log(host_line(run, ctx, gcs.pauses))
    run.sites = {scope: [(n, r.m, r.k, r.n)
                         for n, r in engine.registry.sites(scope).items()]
                 for scope in engine.registry.scopes()}
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))

    metrics = end_to_end(run, setup_s)
    log(latency_line(run))
    breakdown = None
    if trace:
        metrics, breakdown, device = per_layer(run, tdir, device)
        shutil.rmtree(tdir, ignore_errors=True)
    failed = sum(1 for r in run.recs
                 if r.req.outcome not in ("", "done"))
    picked = sample(run, seed)
    log(f"served in window: {sum(len(r.tokens) for r in run.recs)} tokens, "
        f"{sum(1 for r in run.recs if r.req.outcome == 'done')} requests "
        f"done, {failed} failed; checking {len(picked)} against the "
        "reference")

    # free the program's state before the reference runs on the chip
    del engine
    gc.collect()
    if picked:
        got = compare(cell.model, cfg, seed, picked)
        names = [n for n in ("max_logit_gap", "mean_logit_gap")
                 if cell.limits.get(n) is not None]
        compared = {n: {"value": got[n], "limit": cell.limits.get(n)}
                    for n in names or ("max_logit_gap", "mean_logit_gap")}
        correct = bool(names) and all(got[n] <= cell.limits[n]
                                      for n in names)
        log(f"checked {got['served_tokens']} served tokens of "
            f"{got['requests']} requests")
    else:
        correct, compared = False, {"finished_requests": {"value": 0,
                                                          "limit": 1}}
    out = {"correct": bool(correct), "attempted": len(run.recs),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out


def per_layer(run: RunData, tdir: Path, device: Dict):
    """Reduce the trace; ask each of the cell's per-layer readers."""
    import trace_reduce as tr
    t = tr.load(str(tdir))
    lo, hi = t.window()
    dev = min(t.ops)
    run.trace, run.trace_window, run.device = t, (lo, hi), dev
    busy = [tr.covered(tr.busy(ops, lo, hi)) * 1e-9 for ops in t.ops.values()]
    device = dict(device, busy_s=float(np.mean(busy)),
                  window_s=(hi - lo) * 1e-9)
    metrics = {}
    for m in run.cell.per_layer:
        v = reader(m["name"], run.cell.bench_dir)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    ops = t.ops[dev]
    gap_iv = tr.gaps(tr.busy(ops, lo, hi), lo, hi)
    breakdown = {"device_ops": tr.top_ops(ops, lo, hi),
                 "idle_gaps": tr.label_gaps(gap_iv, t.spans)}
    return metrics, breakdown, device
