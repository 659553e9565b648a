"""From a profiler trace to the numbers the per-layer readers need.

The JAX profiler writes one ``*.xplane.pb`` per host.  Device planes
(``/device:TPU:<n>``) hold a line of XLA ops, each with a start and a
duration in nanoseconds; the host plane holds the harness's own
``TraceAnnotation`` spans (``bench.*``) on the same clock.  Everything here
works on plain tuples, so the tests can feed it recorded or hand-made events.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."


@dataclass
class Op:
    name: str
    start: float           # ns
    end: float             # ns


@dataclass
class Trace:
    ops: Dict[int, List[Op]] = field(default_factory=dict)   # per device
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    def window(self) -> Interval:
        """The harness's ``bench.window`` span."""
        for name, s, e in self.spans:
            if name == SPAN_PREFIX + "window":
                return s, e
        raise ValueError("the trace has no bench.window span")


def load(trace_dir: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    trace = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    trace.ops[int(m.group(1))] = [
                        Op(ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        trace.spans.append((ev.name, ev.start_ns, ev.end_ns))
    if not trace.ops:
        raise ValueError(f"{paths[-1]}: no device plane with an "
                         f"'{OPS_LINE}' line")
    return trace


def union(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def is_container(op: Op) -> bool:
    """Control flow (``while``, ``conditional``, ``call``): its event spans
    the ops it runs and the gaps between them."""
    return _CONTAINER.match(op.name) is not None


def busy(ops: Sequence[Op], lo: float, hi: float) -> List[Interval]:
    """Intervals in [lo, hi] in which some op runs on the device."""
    return union(((o.start, o.end) for o in ops if not is_container(o)),
                 lo, hi)


def gaps(busy_iv: Sequence[Interval], lo: float,
         hi: float) -> List[Interval]:
    """The complement of ``busy_iv`` in [lo, hi]."""
    out, t = [], lo
    for s, e in busy_iv:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def matches(op: Op, patterns: Sequence[str]) -> bool:
    """A kernel op: a custom call whose instruction name starts with one
    of ``patterns``.  A device op's name is its HLO instruction,
    ``%<name>.<n> = <type> <opcode>(...)``; a Pallas kernel is a
    ``custom-call`` named after the jitted wrapper that launches it
    (``kernels/ops.py``)."""
    return ("custom-call(" in op.name
            and any(op.name.startswith(p) for p in patterns))


def op_seconds(ops: Sequence[Op], patterns: Sequence[str], lo: float,
               hi: float) -> Tuple[float, int]:
    """Device seconds and count of the ops in [lo, hi] that match."""
    total, n = 0.0, 0
    for o in ops:
        if matches(o, patterns):
            s, e = max(o.start, lo), min(o.end, hi)
            if e > s:
                total += e - s
                n += 1
    return total * 1e-9, n


def idle_within(busy_iv: Sequence[Interval],
                spans: Sequence[Interval]) -> Tuple[float, float]:
    """(span seconds, idle seconds) over the union of ``spans``."""
    total = idle = 0.0
    for s, e in union(spans, float("-inf"), float("inf")):
        total += e - s
        idle += (e - s) - covered(union(busy_iv, s, e))
    return total * 1e-9, idle * 1e-9


_KIND = re.compile(r"^%?([A-Za-z_][\w-]*)(?:\.\d+)*(?: = (\S+))?")
_CONTAINER = re.compile(r"^%?(while|conditional|call)(\.\d+)*( |$)")


def op_kind(op: Op) -> str:
    """An op's instruction name without its number, and its result type
    (``%rsa_gemm.62 = bf16[4096,20480]{...} custom-call(...)`` ->
    ``rsa_gemm bf16[4096,20480]``), which groups one kind of op."""
    m = _KIND.match(op.name)
    if not m:
        return op.name
    kind, ty = m.group(1), m.group(2)
    return f"{kind} {ty.split('{')[0]}" if ty else kind


def top_ops(ops: Sequence[Op], lo: float, hi: float,
            n: int = 10) -> List[List]:
    acc: Dict[str, float] = defaultdict(float)
    for o in ops:
        if is_container(o):
            continue
        s, e = max(o.start, lo), min(o.end, hi)
        if e > s:
            acc[op_kind(o)] += (e - s) * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def label_gaps(gap_iv: Sequence[Interval],
               spans: Sequence[Tuple[str, float, float]],
               n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps, each named by the innermost harness
    span around its midpoint (``bench.window`` alone means the harness
    itself, between calls into the engine)."""
    out = []
    for s, e in sorted(gap_iv, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        inside = [(ee - ss, name) for name, ss, ee in spans
                  if ss <= mid <= ee]
        label = min(inside)[1] if inside else "outside bench.window"
        out.append([label, (e - s) * 1e-9])
    return out

