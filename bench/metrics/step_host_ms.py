"""Median over the window's ``engine.step`` spans of the step's host time
with none of its step programs in flight: the span's duration less the
part that its ``engine.prefill_chunk`` / ``engine.paged_decode`` spans,
each through the ``engine.sync`` after it, cover (engine/scheduler
layer, ``serving/engine.py``)."""

import engine_trace as et
from stats import percentile


def read(run):
    v = percentile(et.step_host_seconds(et.spans(run)), 50)
    return None if v is None else v * 1e3
