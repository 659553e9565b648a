"""90th percentile of queue wait, admission minus due time, over the
window's requests; one still queued at the close counts at its wait so far
(engine/scheduler layer, ``serving/scheduler.py``)."""

from stats import percentile


def read(run):
    close = run.window_s
    waits = []
    for r in run.recs:
        req = r.req
        if req.t_admit >= 0:
            waits.append(req.t_admit - req.arrival_time)
        else:
            waits.append(close - r.planned.due_s)
    v = percentile(waits, 90)
    return None if v is None else v * 1e3
