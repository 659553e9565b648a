"""Mean share of the decode slots holding a request, over the window's
steps (``ServingMetrics.slot_occupancy``; engine/scheduler layer)."""


def read(run):
    if not run.occupancy:
        return None
    return 100.0 * sum(run.occupancy) / len(run.occupancy)
