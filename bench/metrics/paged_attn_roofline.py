"""Least time of the paged attention calls in the traced window, from the
live KV rows each lane reads and its query rows (decode and chunked
prefill), over the device time of the paged kernel's events (paged
attention kernel layer, ``kernels/paged_attn.py``).  The counts and the
kernels' names come from the cell's model module."""

import costs
import trace_reduce as tr


def read(run):
    lo, hi = run.trace_window
    model = run.model
    kernel_s, n = tr.op_seconds(run.trace.ops[run.device],
                                model.PAGED_ATTN_KERNELS, lo, hi)
    if n == 0 or kernel_s <= 0:
        return None
    cfg, least = run.cfg, 0.0
    for c in run.calls:
        lanes = [(int(s), int(r)) for s, r in zip(c.starts, c.rows) if r > 0]
        least += model.attn_layers(cfg) * costs.least_seconds(
            model.attn_flops(cfg, lanes), model.attn_bytes(cfg, lanes, 2),
            run.peaks)
    return 100.0 * least / kernel_s
