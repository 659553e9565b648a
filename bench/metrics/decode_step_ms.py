"""Median time of a paged decode step in the traced window, from the start
of the engine's ``engine.paged_decode`` span (the jitted call that
enqueues the step) to the end of the ``engine.sync`` after it (model step
layer, ``serving/engine.py`` ``_decode_paged``)."""

import engine_trace as et
from stats import percentile


def read(run):
    v = percentile(et.program_seconds(et.spans(run), et.DECODE), 50)
    return None if v is None else v * 1e3
