"""Share of the union of the harness's ``engine.step()`` spans in which no
operation runs on the device, from the profiler trace (device layer)."""

import trace_reduce as tr


def read(run):
    lo, hi = run.trace_window
    steps = [(s, e) for name, s, e in run.trace.spans if name == "bench.step"]
    if not steps:
        return None
    total, idle = tr.idle_within(tr.busy(run.trace.ops[run.device], lo, hi),
                                 steps)
    return 100.0 * idle / total if total > 0 else None
