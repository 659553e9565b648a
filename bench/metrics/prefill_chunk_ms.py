"""Median time of a chunked-prefill step in the traced window, from the
start of the engine's ``engine.prefill_chunk`` span (the jitted call that
enqueues the step) to the end of the ``engine.sync`` after it (model step
layer, ``serving/engine.py`` ``_do_chunk_prefills``)."""

import engine_trace as et
from stats import percentile


def read(run):
    v = percentile(et.program_seconds(et.spans(run), et.CHUNK), 50)
    return None if v is None else v * 1e3
