"""Least time of the paged attention calls in the traced window, from the
live KV rows each lane reads and its query rows (decode and chunked
prefill), over the device time of the paged kernel's events (paged
attention kernel layer, ``kernels/paged_attn.py``)."""

import costs
import kernels
import trace_reduce as tr


def read(run):
    lo, hi = run.trace_window
    kernel_s, n = tr.op_seconds(run.trace.ops[run.device],
                                kernels.PAGED_ATTN, lo, hi)
    if n == 0 or kernel_s <= 0:
        return None
    cfg, least = run.cfg, 0.0
    for c in run.calls:
        lanes = [(int(s), int(r)) for s, r in zip(c.starts, c.rows) if r > 0]
        least += cfg.num_layers * costs.least_seconds(
            costs.attn_flops(cfg, lanes), costs.attn_bytes(cfg, lanes, 2),
            run.peaks)
    return 100.0 * least / kernel_s
