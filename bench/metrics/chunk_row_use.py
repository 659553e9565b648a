"""Share of the rows the window's chunked-prefill steps computed that
carried a prompt token: the ``live_rows`` over the ``rows`` (slots x
chunk) of every ``engine.prefill_chunk`` span (engine/scheduler layer,
``serving/engine.py`` ``_do_chunk_prefills``)."""

import engine_trace as et


def read(run):
    live, rows = et.chunk_rows(et.spans(run))
    return 100.0 * live / rows if rows else None
