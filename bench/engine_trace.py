"""The engine's own step phases in the profiler trace.

The engine writes each step as a ``StepTraceAnnotation`` ``engine.step``
and each phase of it as a ``TraceAnnotation`` ``engine.<phase>``
(``obs/spans.py``): ``schedule``, ``inputs``, the step program
(``prefill_chunk`` or ``paged_decode``: the jitted call, which enqueues
it), ``sync`` (``block_until_ready``), ``readback``, ``sample``,
``commit``.  Their args are xplane stats: ``lanes``, ``live_rows`` and
``rows`` on a chunk step, ``lanes``, ``width`` and ``kv_rows`` on a
decode step, ``stalls`` and ``preempts`` on the step.  A program that
writes none of them gives no spans here, and the readers built on this
module return nothing.
"""

from __future__ import annotations

import functools
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as tr

PREFIX = "engine."
STEP = PREFIX + "step"
SYNC = PREFIX + "sync"
CHUNK = PREFIX + "prefill_chunk"
DECODE = PREFIX + "paged_decode"
PROGRAMS = (CHUNK, DECODE)


@dataclass
class Span:
    name: str
    start: float           # ns, the host clock of the xplane
    end: float             # ns
    args: Dict[str, float] = field(default_factory=dict)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> Tuple[Optional[Tuple[float, float]],
                                             Tuple[Span, ...]]:
    from jax.profiler import ProfileData
    window, out = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.name, ev.start_ns, ev.end_ns,
                                    dict(ev.stats)))
                elif ev.name == tr.SPAN_PREFIX + "window":
                    window = (ev.start_ns, ev.end_ns)
    out.sort(key=lambda s: (s.start, -s.end))
    return window, tuple(out)


def load(path: str) -> Tuple[Optional[Tuple[float, float]],
                             Tuple[Span, ...]]:
    """(the ``bench.window`` span or None, every ``engine.*`` span) of one
    ``*.xplane.pb``, ordered by start, an enclosing span before what it
    encloses.  One trace is kept parsed: the readers of a run share it."""
    return _load(path, os.stat(path).st_mtime_ns)


def spans(run) -> List[Span]:
    """The ``engine.*`` spans inside the run's traced window.  The harness
    keeps the run's trace under ``<bench>/.trace/<cell>-<seed>/`` until the
    readers have read; the newest trace there whose ``bench.window`` is the
    run's own is the one."""
    pattern = os.path.join(str(run.cell.bench_dir), ".trace",
                           f"{run.cell.name}-*", "**", "*.xplane.pb")
    paths = sorted(glob.glob(pattern, recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return []
    window, found = load(paths[-1])
    if window is None or tuple(window) != tuple(run.trace_window):
        return []
    lo, hi = window
    return [s for s in found if lo <= s.start and s.end <= hi]


def programs(found: Sequence[Span], name: str) -> List[Tuple[float, float]]:
    """For each ``name`` span, the interval from its start to the end of
    the ``engine.sync`` that follows it: the step program from its enqueue
    to its last result on the host.  A span with no sync after it in the
    window has no interval."""
    out = []
    syncs = [s for s in found if s.name == SYNC]
    i = 0
    for p in (s for s in found if s.name == name):
        while i < len(syncs) and syncs[i].start < p.end:
            i += 1
        if i < len(syncs):
            out.append((p.start, syncs[i].end))
    return out


def program_seconds(found: Sequence[Span], name: str) -> List[float]:
    return [(e - s) * 1e-9 for s, e in programs(found, name)]


def step_host_seconds(found: Sequence[Span]) -> List[float]:
    """For each ``engine.step``, its seconds outside its step programs'
    intervals (``programs``): the host work of the step during which none
    of its programs is in flight."""
    progs = [iv for name in PROGRAMS for iv in programs(found, name)]
    return [(st.end - st.start - tr.covered(tr.union(progs, st.start,
                                                     st.end))) * 1e-9
            for st in found if st.name == STEP]


def chunk_rows(found: Sequence[Span]) -> Tuple[int, int]:
    """(live rows, rows) summed over the chunk steps: the chunk tokens the
    lanes carried, and the rows the step program computed."""
    chunks = [s for s in found if s.name == CHUNK]
    return (sum(int(s.args.get("live_rows", 0)) for s in chunks),
            sum(int(s.args.get("rows", 0)) for s in chunks))
